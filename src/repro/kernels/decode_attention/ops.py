"""Ring-cache decode attention as a view onto the paged kernel.

The old standalone decode kernel is gone: a (B, C, Hkv, D) ring cache is
just B contiguous runs of ``C / page_size`` pages whose stored position
plane (``cache_pos``, -1 for empty rows) supplies the masking, so decode
here reshapes the ring into the paged fused-KV layout and dispatches one
single-query-per-sequence grid of ``repro.kernels.paged_attention``.
There is exactly one decode read path in the repo.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.ops import ragged_paged_attention


@functools.partial(jax.jit, static_argnames=("scale", "cap", "window",
                                             "page_size", "interpret"))
def decode_attention(q, k_cache, v_cache, cache_pos, cur_pos, *, scale: float,
                     cap: Optional[float] = None, window: Optional[int] = None,
                     page_size: int = 512,
                     interpret: Optional[bool] = None):
    """q: (B, 1, H, D); caches: (B, C, Hkv, D); cache_pos: (B, C);
    cur_pos: scalar or (B,). -> (B, 1, H, D)."""
    B, _, H, D = q.shape
    C, Hkv = k_cache.shape[1], k_cache.shape[2]
    ps = min(page_size, C)
    if C % ps:
        ps = C                                           # one page per ring
    n_per = C // ps
    # fused head-interleaved pages: K at 2h, V at 2h+1
    kv = jnp.stack([k_cache, v_cache], axis=3)           # (B, C, Hkv, 2, D)
    kv_pages = kv.reshape(B, C, 2 * Hkv, D).reshape(B * n_per, ps,
                                                    2 * Hkv, D)
    kv_pos = jnp.asarray(cache_pos, jnp.int32).reshape(B * n_per, ps)
    page_table = jnp.arange(B * n_per, dtype=jnp.int32).reshape(B, n_per)
    cu = jnp.arange(B + 1, dtype=jnp.int32)
    kv_lens = jnp.full((B,), C, jnp.int32)
    cur = jnp.asarray(cur_pos, jnp.int32)
    q_pos = (cur.reshape(-1) if cur.ndim else
             jnp.full((B,), cur, jnp.int32))
    q_pos = jnp.broadcast_to(q_pos, (B,))
    out = ragged_paged_attention(
        q.reshape(B, H, D), kv_pages, page_table, cu, kv_lens,
        scale=scale, cap=cap, window=window, q_pos=q_pos,
        kv_pos_pages=kv_pos, max_q_len=1, interpret=interpret)
    return out.reshape(B, 1, H, D)

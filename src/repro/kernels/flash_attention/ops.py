"""Public jit'd wrapper: (B, S, H, D) GQA layout -> flash kernel layout."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_bh


@functools.partial(jax.jit, static_argnames=("scale", "cap", "window", "causal",
                                             "q_block", "kv_block", "interpret"))
def flash_attention(q, k, v, *, scale: float, cap: Optional[float] = None,
                    window: Optional[int] = None, causal: bool = True,
                    q_block: int = 512, kv_block: int = 512,
                    interpret: Optional[bool] = None):
    """q: (B, Sq, H, D); k/v: (B, Skv, Hkv, D) -> (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    # fold (B, Hkv, G) -> BH; replicate k/v over the group dim
    qf = q.reshape(B, Sq, Hkv, G, D).transpose(0, 2, 3, 1, 4).reshape(B * Hkv * G, Sq, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * Hkv * G, Skv, D)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * Hkv * G, Skv, D)
    out = flash_attention_bh(qf, kf, vf, scale=scale, cap=cap, window=window,
                             causal=causal, q_block=q_block, kv_block=kv_block,
                             interpret=interpret)
    return out.reshape(B, Hkv, G, Sq, D).transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D)

"""Host-side dispatch for ragged paged attention.

``ragged_paged_attention`` resolves the launch config (explicit
``block_q``/``block_kv``/``num_buffers`` overrides win, otherwise the
autotuner's cached best config for this page geometry), pads the flat
query block to a whole number of q-tiles (so every tile's fixed-size
block load stays in bounds), routes to the Pallas kernel or the jnp
reference, and slices the padding back off. Interpret mode follows
``repro.kernels.resolve_interpret``: on the CPU the exact kernel lowering
runs in the Pallas interpreter, on the chip it is compiled.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import resolve_interpret

from .kernel import pages_gathered, ragged_paged_attention_pallas
from .ref import ragged_paged_attention_ref
from .tune import resolve_config


class KernelGeometryError(ValueError):
    """The compiled kernel cannot take this page geometry: Mosaic needs
    the page slab's head-dim axis to be a multiple of 128 lanes and its
    fused K/V head axis (``2 * Hkv``) to be 2, 4 or a multiple of 8."""


def check_tpu_geometry(fused_heads: int, head_dim: int) -> None:
    """Raise :class:`KernelGeometryError` for a page geometry the TPU
    compiler refuses (found by compiling for a described v5e)."""
    if head_dim % 128 or not (fused_heads in (2, 4) or fused_heads % 8 == 0):
        raise KernelGeometryError(
            f"ragged_paged_attention does not compile for pages of "
            f"{fused_heads} fused K/V heads x head_dim {head_dim}: needs "
            f"head_dim % 128 == 0 and 2*Hkv in (2, 4) or a multiple of 8")


def serves(fused_heads: int, head_dim: int, platform: str) -> bool:
    """Whether this kernel serves pages of ``fused_heads`` (2*Hkv) x
    ``head_dim`` on ``platform``: compiled on a TPU for a geometry the
    TPU compiler takes. On the CPU it would run in the Pallas
    interpreter, far too slowly to serve."""
    if platform != "tpu":
        return False
    try:
        check_tpu_geometry(fused_heads, head_dim)
    except KernelGeometryError:
        return False
    return True


# page tables reach the kernel padded to a multiple of this width
TABLE_QUANTUM = 128


def _rows_bucket(n: int) -> int:
    """The power of 2 at or above ``n`` (at least 1)."""
    return 1 << (max(1, int(n)) - 1).bit_length()


def launch_pages(page_table, cu_q_lens, kv_lens, *, page_size: int,
                 head_dim: int, max_q_len: int,
                 window: Optional[int] = None) -> int:
    """KV pages one ``ragged_paged_attention`` launch reads with its
    tuned config: the grid's page DMAs (``kernel.pages_gathered``)."""
    max_q = _rows_bucket(max_q_len)
    cfg = resolve_config(page_size, head_dim, max_q)
    return pages_gathered(page_table, cu_q_lens, kv_lens,
                          page_size=page_size, max_q_len=max_q,
                          block_q=cfg.block_q, window=window)


def ragged_paged_attention(q, kv_pages, page_table, cu_q_lens, kv_lens, *,
                           scale: float, cap: Optional[float] = None,
                           window: Optional[int] = None,
                           q_pos=None, kv_pos_pages=None,
                           max_q_len: Optional[int] = None,
                           backend: str = "auto",
                           block_q: Optional[int] = None,
                           block_kv: Optional[int] = None,
                           num_buffers: Optional[int] = None,
                           skip_blocks: bool = True,
                           layer=None,
                           interpret: Optional[bool] = None):
    """Attend T concatenated query rows against paged KV storage.

    q: (T, Hq, D); kv_pages: (P, ps, 2*Hkv, D) fused head-interleaved;
    page_table: (S, W) int32; cu_q_lens: (S+1,) int32 with
    cu_q_lens[-1] == T; kv_lens: (S,) int32. ``max_q_len`` must be a
    static bound on every per-sequence query length (defaults to T,
    which is always safe). ``q_pos``/``kv_pos_pages`` switch on explicit
    position tracking (ring-layout compatibility); both or neither.
    ``block_q``/``block_kv``/``num_buffers`` override the autotuned
    kernel config; ``skip_blocks=False`` forces the ungrouped full-gather
    baseline (bench/parity use). With ``layer`` (a scalar index)
    ``kv_pages`` is the pool stacked over layers, (L, P, ps, 2*Hkv, D),
    and the pages are read from that layer in place. Returns (T, Hq, D)
    in q's dtype.

    The compiled kernel is called on shapes that launches share: the
    query rows and ``max_q_len`` rounded up to powers of 2, the table
    padded with null slots to a multiple of ``TABLE_QUANTUM``. Padding
    rows past ``cu_q_lens[-1]`` and null slots past a sequence's keys
    are never read, so the result is the same; but the step programs of
    many chunk lengths and table widths then share one traced kernel
    instead of tracing their own.
    """
    if (q_pos is None) != (kv_pos_pages is None):
        raise ValueError("q_pos and kv_pos_pages must be given together")
    if backend == "ref":
        return ragged_paged_attention_ref(
            q, kv_pages, page_table, cu_q_lens, kv_lens, scale=scale,
            cap=cap, window=window, q_pos=q_pos,
            kv_pos_pages=kv_pos_pages, layer=layer)
    if backend not in ("auto", "pallas"):
        raise ValueError(f"unknown backend: {backend!r}")
    interpret = resolve_interpret(interpret)
    if not interpret:
        check_tpu_geometry(kv_pages.shape[-2], kv_pages.shape[-1])
    T = q.shape[0]
    ps = kv_pages.shape[-3]
    max_q = _rows_bucket(T if max_q_len is None else max_q_len)
    cfg = resolve_config(ps, q.shape[-1], max_q, block_q, block_kv,
                         num_buffers)
    pad = _rows_bucket(T) - T + -(-max_q // cfg.block_q) * cfg.block_q
    q_pad = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    q_pos_pad = None
    if q_pos is not None:
        q_pos_pad = jnp.pad(jnp.asarray(q_pos, jnp.int32), (0, pad))
    W = page_table.shape[1]
    page_table = jnp.pad(jnp.asarray(page_table, jnp.int32),
                         ((0, 0), (0, -W % TABLE_QUANTUM)))
    out = ragged_paged_attention_pallas(
        q_pad, kv_pages, page_table, cu_q_lens, kv_lens, scale=scale,
        cap=cap, window=window, max_q_len=max_q,
        block_q=cfg.block_q, block_kv=cfg.block_kv,
        num_buffers=cfg.num_buffers, skip_blocks=skip_blocks,
        q_pos_pad=q_pos_pad, kv_pos_pages=kv_pos_pages, layer=layer,
        interpret=interpret)
    return out[:T]

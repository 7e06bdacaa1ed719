"""Pure-jnp reference for ragged paged attention, plus the paged-plane
layout helpers the models share.

Layout contract (DESIGN.md §10): the unit of KV storage is a *page* of
``page_size`` consecutive token positions with K and V fused
head-interleaved —

    kv_pages: (n_pages, page_size, 2 * n_kv_heads, head_dim)

where head ``h``'s key rows sit at index ``2*h`` and its value rows at
``2*h + 1`` (one contiguous DMA per page streams both). A sequence is a
row of a ``page_table`` (int32 page ids): table slot ``j`` covers absolute
positions ``[j*page_size, (j+1)*page_size)``, so key positions are derived
from the slot index — no stored-position array. Null table entries
(page id 0, the reserved all-zeros page) are masked wherever they sit:
trailing padding is masked for free (slot-derived positions exceed every
causal query position) and interior nulls — sparse tables — are masked
by page id, matching the grouped kernel grid that skips them without a
gather.

The attention core scans the table one page at a time with an online
softmax whose accumulator is *exactly* invariant to trailing padding
pages (a fully-masked page contributes p == 0 and a rescale factor of 1),
so outputs are bit-identical across page-table widths and batch
compositions — the property the serving engine's prefix-hit-vs-cold
bit-equality tests rest on.

An optional ``kv_pos_pages`` (n_pages, page_size) int32 plane overrides
the slot-derived positions (-1 = empty row); this is how the legacy
ring-cache decode path folds into the same kernel grid
(``repro.kernels.decode_attention``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# Layout helpers (the write half of the paged compute plane)
# ---------------------------------------------------------------------------


def interleave_kv(k, v):
    """(B, S, Hkv, D) k/v -> fused head-interleaved (B, S, 2*Hkv, D):
    head h's key at index 2h, its value at 2h+1."""
    B, S, Hkv, D = k.shape
    return jnp.stack([k, v], axis=3).reshape(B, S, 2 * Hkv, D)


def split_kv(kv):
    """Inverse of :func:`interleave_kv`: (..., 2*Hkv, D) -> (k, v)."""
    return kv[..., 0::2, :], kv[..., 1::2, :]


def write_tokens_to_pages(kv_pages, kv_new, positions, page_table,
                          active=None, layer=None):
    """Scatter fused-KV rows into the paged plane.

    kv_pages: (P, ps, 2*Hkv, D) pool; kv_new: (B, S, 2*Hkv, D);
    positions: (B, S) absolute token positions; page_table: (B, W) int32.
    Rows whose position falls past the table width, or whose ``active``
    flag is False, are dropped (written nowhere) via an out-of-bounds
    scatter index — a mid-prefill slot's pages are never clobbered by a
    batched decode write. With ``layer`` (a scalar index) the pool is
    stacked over layers, (L, P, ps, 2*Hkv, D), and the rows land in that
    layer's pages by one scatter on (layer, page, row): no layer's pool
    is sliced out and written back."""
    P, ps = kv_pages.shape[-4], kv_pages.shape[-3]
    positions = jnp.asarray(positions, jnp.int32)
    W = page_table.shape[1]
    slot = positions // ps                               # (B, S) table slots
    row = positions % ps
    ok = (positions >= 0) & (slot < W)
    if active is not None:
        ok &= jnp.asarray(active, bool).reshape(-1, 1)
    pid = jnp.take_along_axis(page_table, jnp.clip(slot, 0, W - 1), axis=1)
    pid = jnp.where(ok, pid, P)                          # OOB -> dropped
    at = (pid, row) if layer is None else (layer, pid, row)
    return kv_pages.at[at].set(kv_new.astype(kv_pages.dtype), mode="drop")


# ---------------------------------------------------------------------------
# The attention core: rows form
# ---------------------------------------------------------------------------


def paged_attention_rows(q, kv_pages, page_table, q_pos, *, scale: float,
                         cap: Optional[float] = None,
                         window: Optional[int] = None,
                         kv_pos_pages=None, layer=None):
    """Row-flattened paged attention — the one attend everything shares.

    q: (R, Hq, D) query rows; kv_pages: (P, ps, 2*Hkv, D); page_table:
    (R, W) int32 per-row tables; q_pos: (R,) absolute query positions.
    Extend flattens (B, S) to R = B*S rows, batched decode is R = B rows
    of one token each — both are just rows here. Returns (R, Hq, D) in
    the pool dtype. With ``layer`` the pool is stacked over layers,
    (L, P, ps, 2*Hkv, D), and each page is gathered on (layer, page).

    The page loop keeps a per-row online softmax in fp32; masked pages
    (padding, future positions, outside the window) contribute exactly
    zero and leave the accumulator bit-identical, so the result does not
    depend on the table's padded width."""
    q = jnp.asarray(q)
    kv_pages = jnp.asarray(kv_pages)
    page_table = jnp.asarray(page_table, jnp.int32)
    if kv_pos_pages is not None:
        kv_pos_pages = jnp.asarray(kv_pos_pages, jnp.int32)
    R, Hq, D = q.shape
    ps, H2 = kv_pages.shape[-3], kv_pages.shape[-2]
    Hkv = H2 // 2
    G = Hq // Hkv
    W = page_table.shape[1]
    qf = q.astype(jnp.float32).reshape(R, Hkv, G, D)
    qpos = jnp.asarray(q_pos, jnp.int32)

    m0 = jnp.full((R, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((R, Hkv, G), jnp.float32)
    a0 = jnp.zeros((R, Hkv, G, D), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        pid = page_table[:, j]                           # (R,)
        kv = (kv_pages[pid] if layer is None
              else kv_pages[layer, pid])                 # (R, ps, 2Hkv, D)
        k = kv[:, :, 0::2, :].astype(jnp.float32)        # (R, ps, Hkv, D)
        v = kv[:, :, 1::2, :].astype(jnp.float32)
        if kv_pos_pages is not None:
            kpos = kv_pos_pages[pid]                     # (R, ps)
        else:
            kpos = j * ps + jnp.arange(ps, dtype=jnp.int32)[None]
            kpos = jnp.broadcast_to(kpos, (R, ps))
        s = jnp.einsum("rhgd,rphd->rhgp", qf, k,
                       preferred_element_type=jnp.float32) * scale
        if cap is not None:
            s = jnp.tanh(s / cap) * cap
        valid = (kpos >= 0) & (kpos <= qpos[:, None])
        if window is not None:
            valid &= kpos > (qpos[:, None] - window)
        if kv_pos_pages is None:
            # slot-derived tables reserve page 0 as the null page: a null
            # slot *inside* the causal range (sparse tables) holds no
            # keys and must mask, exactly as the grouped kernel skips it
            valid &= (pid != 0)[:, None]
        vmask = valid[:, None, None, :]                  # (R, 1, 1, ps)
        s = jnp.where(vmask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # explicit zeroing (not just exp of NEG_INF): when every page so
        # far was masked, m_new == NEG_INF and exp(s - m_new) would be 1
        p = jnp.where(vmask, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "rhgp,rphd->rhgd", p, v, preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, W, body, (m0, l0, a0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[..., None]
    return out.reshape(R, Hq, D).astype(kv_pages.dtype)


# ---------------------------------------------------------------------------
# Ragged reference (the kernel's oracle)
# ---------------------------------------------------------------------------


def ragged_paged_attention_ref(q, kv_pages, page_table, cu_q_lens, kv_lens,
                               *, scale: float, cap: Optional[float] = None,
                               window: Optional[int] = None,
                               q_pos=None, kv_pos_pages=None, layer=None):
    """jnp reference for the ragged Pallas kernel.

    q: (T, Hq, D) queries of all sequences concatenated; cu_q_lens:
    (S+1,) int32 cumulative query lengths (T == cu_q_lens[-1]);
    kv_pages/page_table/kv_lens: per the module layout contract —
    ``page_table`` is (S, W), ``kv_lens`` (S,). Query i of sequence s
    sits at absolute position ``kv_lens[s] - q_len_s + i`` unless an
    explicit ``q_pos`` (T,) is given. Decode is every q_len == 1. With
    ``layer`` the pool is stacked over layers, as in
    :func:`paged_attention_rows`."""
    T = q.shape[0]
    cu = jnp.asarray(cu_q_lens, jnp.int32)
    kv_lens = jnp.asarray(kv_lens, jnp.int32)
    seg = jnp.searchsorted(cu[1:], jnp.arange(T, dtype=jnp.int32),
                           side="right")                 # (T,) sequence ids
    if q_pos is None:
        q_lens = cu[1:] - cu[:-1]
        q_pos = (kv_lens[seg] - q_lens[seg]
                 + jnp.arange(T, dtype=jnp.int32) - cu[seg])
    tbl = jnp.asarray(page_table, jnp.int32)[seg]        # (T, W)
    return paged_attention_rows(q, kv_pages, tbl, q_pos, scale=scale,
                                cap=cap, window=window,
                                kv_pos_pages=kv_pos_pages, layer=layer)

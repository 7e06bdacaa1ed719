"""Ragged paged-attention Pallas TPU kernel — grouped, null-skipping grid.

The grid is ``(S, QB, NB)``: per sequence, ``QB`` query-row tiles of
``block_q`` rows × ``NB`` kv-page blocks of ``block_kv`` table slots
(TPU grids run sequentially row-major, so for a fixed (sequence, q-tile)
the page blocks arrive back-to-back and the fp32 online-softmax
accumulators live in VMEM scratch across them: initialized at the first
block, finalized and written out at the last). The three ragged
descriptors — ``cu_q_lens``, ``kv_lens``, ``page_table`` — ride in
scalar-prefetch SMEM so every step sizes its own work before its body
runs.

Each page block first *compacts* its useful table slots into an SMEM
list: slots outside the q-tile's reachable page range (causal upper
bound, sliding-window lower bound — slot-derived key positions make both
computable from the grid alone) and null-page slots (page id 0, the
reserved all-zeros page) are dropped without issuing a DMA. A block
whose list is empty is skipped entirely — on sparse tables (mostly-null
rows) the gather stream shrinks to the pages that actually hold keys,
which is the read-bandwidth term the MRM tier is sized by. The surviving
pages stream through an ``num_buffers``-deep (2–4) VMEM copy pipeline:
buffer ``i % num_buffers`` computes while up to ``num_buffers - 1``
later pages are in flight.

Skipping is bit-neutral by the same argument that makes padding pages
safe: a fully-masked page contributes ``m_new == m``, ``p == 0``,
``corr == 1``, leaving (m, l, acc) bit-identical — so the kernel matches
``ref.paged_attention_rows`` (which masks null/out-of-range slots
explicitly) bit-for-bit in fp32. With ``skip_blocks=False`` the kernel
degenerates to the ungrouped PR 6 gather — every slot up to the
sequence's page count is pulled and masked in-register — which is the
baseline the kernel bench meters the skip win against.

Queries and outputs stay in HBM: each (sequence, q-tile) copies its
``block_q``-row window in at its first page block and out at its last,
so VMEM holds one tile whatever the number of rows. Steps run in
ascending sequence order, so the garbage rows a short sequence's tiles
write past its true length are overwritten by the next sequence (the
host wrapper pads by ``QB * block_q`` rows and slices them off).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _attend_page(qf, kv, kpos, qpos, m, l, acc, *, scale, cap, window,
                 null=None):
    """One page of online softmax for one KV head.

    qf: (N, D) fp32 query block (N = block_q * G rows); kv: (ps, 2, D)
    this head's fused page slab; kpos: (ps,) key positions; qpos: (N, 1)
    query positions; m/l: (N, 1) fp32; acc: (N, D) fp32. ``null`` (traced
    scalar bool) masks the whole page — the ungrouped baseline attends
    null pages it did not skip and must zero them in-register."""
    k = kv[:, 0, :].astype(jnp.float32)                  # (ps, D)
    v = kv[:, 1, :].astype(jnp.float32)
    s = jax.lax.dot_general(qf, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if cap is not None:
        s = jnp.tanh(s / cap) * cap
    kp = kpos[None, :]                                   # (1, ps)
    valid = (kp >= 0) & (kp <= qpos)
    if window is not None:
        valid &= kp > (qpos - window)
    if null is not None:
        valid &= jnp.logical_not(null)
    s = jnp.where(valid, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    # explicit zeroing (not just exp of NEG_INF): when every page so far
    # was masked, m_new == NEG_INF and exp(s - m_new) would be 1
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(axis=-1, keepdims=True)
    acc = acc * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l, acc


def _kernel(cu_ref, kvlen_ref, tbl_ref, q_ref, kv_ref, o_ref,
            kbuf, ksem, plist, m_s, l_s, acc_s, qbuf, obuf, qsem,
            *, ps, block_q, block_kv, nbuf, n_blocks, Hkv, G, D,
            scale, cap, window, skip_blocks,
            qpos_ref=None, kvpos_ref=None, pbuf=None, psem=None):
    has_pos = kvpos_ref is not None
    s = pl.program_id(0)
    qb = pl.program_id(1)
    nb = pl.program_id(2)
    q0 = cu_ref[s]
    qlen = cu_ref[s + 1] - q0
    kv_len = kvlen_ref[s]
    n_pages = jax.lax.div(kv_len + ps - 1, ps)
    q_lo = qb * block_q                  # tile rows: [q_lo, q_lo+block_q)

    # page range this q-tile can reach. Slot-derived key positions make
    # the bounds computable without touching a page: causal — no key
    # beyond the tile's last query position; window — no key below the
    # tile's first reachable position. Explicit-position mode (ring
    # layouts: slot 0 is a real page, positions arbitrary) gathers the
    # full range and lets the in-register mask decide.
    if skip_blocks and not has_pos:
        last_qpos = jnp.minimum(kv_len - qlen + q_lo + block_q, kv_len) - 1
        hi = jnp.minimum(n_pages, jax.lax.div(last_qpos, ps) + 1)
        if window is not None:
            first_qpos = kv_len - qlen + q_lo
            lo = jnp.maximum(first_qpos - window + 1, 0) // ps
        else:
            lo = jnp.int32(0)
    else:
        lo, hi = jnp.int32(0), n_pages

    @pl.when((q_lo < qlen) & (nb * block_kv < hi))
    def _tile():
        # -- compact this block's useful slots into SMEM ----------------
        blk0 = jnp.maximum(nb * block_kv, lo)
        blk1 = jnp.minimum(nb * block_kv + block_kv, hi)

        def scan(j, cnt):
            keep = jnp.logical_and(j >= blk0, j < blk1)
            if skip_blocks and not has_pos:
                keep &= tbl_ref[s, j] != 0

            @pl.when(keep)
            def _():
                plist[cnt] = j
            return cnt + keep.astype(jnp.int32)

        nnz = jax.lax.fori_loop(nb * block_kv,
                                jnp.minimum(nb * block_kv + block_kv, hi),
                                scan, 0)

        def page_copy(i, slot):
            return pltpu.make_async_copy(
                kv_ref.at[tbl_ref[s, plist[i]]], kbuf.at[slot],
                ksem.at[slot])

        def pos_copy(i, slot):
            return pltpu.make_async_copy(
                kvpos_ref.at[tbl_ref[s, plist[i]]], pbuf.at[slot],
                psem.at[slot])

        # -- warm the pipeline: up to nbuf-1 pages in flight ------------
        for b in range(nbuf - 1):
            @pl.when(b < nnz)
            def _(b=b):
                page_copy(b, b).start()
                if has_pos:
                    pos_copy(b, b).start()

        rows = pl.ds(q0 + q_lo, block_q)
        if has_pos:
            qpos = qpos_ref[pl.ds(q0 + q_lo, block_q)].reshape(block_q, 1)
            qpos = jnp.broadcast_to(qpos, (block_q, G)).reshape(
                block_q * G, 1)
        else:
            # row r of a head's (block_q * G, D) query block is tile row
            # r // G; built in its final shape because Mosaic cannot
            # reshape an int32 (block_q, G) vector into (block_q * G, 1)
            qpos = (kv_len - qlen + q_lo + jax.lax.div(
                jax.lax.broadcasted_iota(jnp.int32, (block_q * G, 1), 0),
                jnp.int32(G)))

        @pl.when(nb * block_kv <= lo)
        def _init():
            # first page block this tile sees (blocks below lo were
            # skipped whole): reset the accumulators and fetch the tile's
            # query rows, which stay in VMEM for the tile's later blocks
            m_s[...] = jnp.full_like(m_s[...], NEG_INF)
            l_s[...] = jnp.zeros_like(l_s[...])
            acc_s[...] = jnp.zeros_like(acc_s[...])
            cp = pltpu.make_async_copy(q_ref.at[rows], qbuf, qsem.at[0])
            cp.start()
            cp.wait()

        qblk = qbuf[...]                                 # (block_q, Hq, D)

        def body(i, _):
            slot = jax.lax.rem(i, nbuf)

            @pl.when(i + nbuf - 1 < nnz)
            def _prefetch():
                nxt = i + nbuf - 1
                page_copy(nxt, jax.lax.rem(nxt, nbuf)).start()
                if has_pos:
                    pos_copy(nxt, jax.lax.rem(nxt, nbuf)).start()

            page_copy(i, slot).wait()
            j = plist[i]
            if has_pos:
                pos_copy(i, slot).wait()
                kpos = pbuf[slot]
            else:
                kpos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (ps,), 0)
            null = None
            if not skip_blocks and not has_pos:
                null = tbl_ref[s, j] == 0
            kv = kbuf[slot]                              # (ps, 2*Hkv, D)
            for h in range(Hkv):
                qh = qblk[:, h * G:(h + 1) * G, :].astype(jnp.float32)
                qh = qh.reshape(block_q * G, D)
                m_new, l_new, a_new = _attend_page(
                    qh, kv[:, 2 * h:2 * h + 2, :], kpos, qpos,
                    m_s[h], l_s[h], acc_s[h],
                    scale=scale, cap=cap, window=window, null=null)
                m_s[h] = m_new
                l_s[h] = l_new
                acc_s[h] = a_new
            return 0

        jax.lax.fori_loop(0, nnz, body, 0)

        @pl.when((nb == n_blocks - 1) | (nb * block_kv + block_kv >= hi))
        def _finalize():
            outs = []
            for h in range(Hkv):
                l = l_s[h]
                o = acc_s[h] / jnp.where(l == 0.0, 1.0, l)
                outs.append(o.reshape(block_q, G, D))
            obuf[...] = jnp.concatenate(outs, axis=1).astype(obuf.dtype)
            # waited here so that the next sequence's tile, which may
            # overlap the rows this tile writes past its sequence's end,
            # lands after it
            cp = pltpu.make_async_copy(obuf, o_ref.at[rows], qsem.at[1])
            cp.start()
            cp.wait()


@functools.partial(
    jax.jit,
    static_argnames=("scale", "cap", "window", "max_q_len", "block_q",
                     "block_kv", "num_buffers", "skip_blocks", "interpret"))
def ragged_paged_attention_pallas(q_pad, kv_pages, page_table, cu_q_lens,
                                  kv_lens, *, scale: float,
                                  cap: Optional[float] = None,
                                  window: Optional[int] = None,
                                  max_q_len: int = 1,
                                  block_q: Optional[int] = None,
                                  block_kv: Optional[int] = None,
                                  num_buffers: int = 2,
                                  skip_blocks: bool = True,
                                  q_pos_pad=None, kv_pos_pages=None,
                                  interpret: bool = False):
    """Pallas entry. ``q_pad`` must be padded with at least
    ``ceil(max_q_len / block_q) * block_q`` extra rows so every q-tile's
    block load stays in bounds; callers go through
    ``ops.ragged_paged_attention`` which pads and re-slices.
    ``block_q``/``block_kv``/``num_buffers`` default to the autotuner's
    cached best config for this (page_size, head_dim) geometry;
    ``skip_blocks=False`` selects the ungrouped full-gather baseline."""
    from .tune import best_config

    Tpad, Hq, D = q_pad.shape
    _, ps, H2, _ = kv_pages.shape
    Hkv = H2 // 2
    G = Hq // Hkv
    S, W = page_table.shape
    max_q = max_q_len
    has_pos = kv_pos_pages is not None

    cfg = best_config(ps, D)
    bq = max(1, min(block_q or cfg.block_q, max_q))
    bkv = max(1, min(block_kv or cfg.block_kv, W))
    nbuf = max(2, min(num_buffers or cfg.num_buffers, 4))
    QB = -(-max_q // bq)
    NB = -(-W // bkv)

    scratch = [
        pltpu.VMEM((nbuf, ps, H2, D), kv_pages.dtype),   # kbuf
        pltpu.SemaphoreType.DMA((nbuf,)),                # ksem
        pltpu.SMEM((bkv,), jnp.int32),                   # plist (compacted)
        pltpu.VMEM((Hkv, bq * G, 1), jnp.float32),       # m_s
        pltpu.VMEM((Hkv, bq * G, 1), jnp.float32),       # l_s
        pltpu.VMEM((Hkv, bq * G, D), jnp.float32),       # acc_s
        pltpu.VMEM((bq, Hq, D), q_pad.dtype),            # qbuf
        pltpu.VMEM((bq, Hq, D), q_pad.dtype),            # obuf
        pltpu.SemaphoreType.DMA((2,)),                   # qsem
    ]
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    if has_pos:
        in_specs = [
            hbm,                                         # q
            hbm,                                         # kv_pages
            pl.BlockSpec((Tpad,), lambda s, qb, nb, *_: (0,)),   # q_pos
            hbm,                                         # kv_pos_pages
        ]
        args = [q_pad, kv_pages,
                jnp.asarray(q_pos_pad, jnp.int32),
                jnp.asarray(kv_pos_pages, jnp.int32)]
        scratch += [
            pltpu.VMEM((nbuf, ps), jnp.int32),           # pbuf
            pltpu.SemaphoreType.DMA((nbuf,)),            # psem
        ]
    else:
        in_specs = [hbm, hbm]
        args = [q_pad, kv_pages]

    kernel = functools.partial(
        _kernel, ps=ps, block_q=bq, block_kv=bkv, nbuf=nbuf, n_blocks=NB,
        Hkv=Hkv, G=G, D=D, scale=scale, cap=cap, window=window,
        skip_blocks=skip_blocks)

    def wrapped(cu_ref, kvlen_ref, tbl_ref, q_ref, kv_ref, *rest):
        if has_pos:
            qpos_ref, kvpos_ref, o_ref = rest[0], rest[1], rest[2]
            scratch_refs, (pbuf, psem) = rest[3:-2], rest[-2:]
            kernel(cu_ref, kvlen_ref, tbl_ref, q_ref, kv_ref, o_ref,
                   *scratch_refs, qpos_ref=qpos_ref, kvpos_ref=kvpos_ref,
                   pbuf=pbuf, psem=psem)
        else:
            kernel(cu_ref, kvlen_ref, tbl_ref, q_ref, kv_ref, *rest)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, QB, NB),
        in_specs=in_specs,
        out_specs=hbm,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        wrapped,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tpad, Hq, D), q_pad.dtype),
        interpret=interpret,
    )(jnp.asarray(cu_q_lens, jnp.int32),
      jnp.asarray(kv_lens, jnp.int32),
      jnp.asarray(page_table, jnp.int32),
      *args)


def pages_gathered(page_table, cu_q_lens, kv_lens, *, page_size: int,
                   max_q_len: int, block_q: Optional[int] = None,
                   block_kv: Optional[int] = None,
                   window: Optional[int] = None,
                   skip_blocks: bool = True) -> int:
    """Host-side replica of the kernel's gather decisions: the number of
    page DMAs the grid issues (the achieved page-read stream the kernel
    bench meters, and the analytic twin of the engine's per-page read
    accounting). Slot-derived positions only."""
    import numpy as np

    from .tune import best_config

    tbl = np.asarray(page_table)
    cu = np.asarray(cu_q_lens)
    kvl = np.asarray(kv_lens)
    S, W = tbl.shape
    ps = page_size
    cfg = best_config(ps, 0)
    bq = max(1, min(block_q or cfg.block_q, max(1, max_q_len)))
    total = 0
    for s in range(S):
        qlen = int(cu[s + 1] - cu[s])
        kv_len = int(kvl[s])
        n_pages = -(-kv_len // ps)
        for q_lo in range(0, max(1, max_q_len), bq):
            if q_lo >= qlen:
                continue
            if skip_blocks:
                last_qpos = min(kv_len - qlen + q_lo + bq, kv_len) - 1
                hi = min(n_pages, last_qpos // ps + 1)
                lo = (max(0, kv_len - qlen + q_lo - window + 1) // ps
                      if window is not None else 0)
                total += int(np.count_nonzero(tbl[s, lo:hi]))
            else:
                total += n_pages
    return total

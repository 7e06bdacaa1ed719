"""Ragged paged-attention Pallas TPU kernel — null-skipping, block-wide.

The grid is ``(S, QB)``: per sequence, ``QB`` query-row tiles of
``block_q`` rows. Each step first *compacts* the table slots its tile can
reach into an SMEM list: slots outside the tile's reachable page range
(causal upper bound, sliding-window lower bound — slot-derived key
positions make both computable from the grid alone) and null-page slots
(page id 0, the reserved all-zeros page) are dropped without issuing a
DMA. A tile whose sequence has no keys (``kv_lens == 0``: an idle decode
row) issues nothing and writes nothing. The three ragged descriptors —
``cu_q_lens``, ``kv_lens``, ``page_table`` — and the pool's ``layer``
ride in scalar-prefetch SMEM so every step sizes its own work before its
body runs.

The compacted pages are attended in blocks of ``block_kv`` pages. A
block's pages stream into one VMEM buffer (one DMA per page); up to
``num_buffers - 1`` (1–3) later blocks are in flight while the current
one computes. For each KV head the block's keys and values are read out
of the fused page slab by a strided load, and the head's score and value
matmuls each span the whole block (``block_kv * page_size`` keys) at
once, with the fp32 online-softmax accumulators in VMEM scratch across
the tile's blocks.

Numerics. Keys and values enter the matmuls as stored; queries and the
softmax weights are cast to the pool's dtype for the MXU, and scores,
softmax and accumulation are fp32 — on a bf16 pool that is what XLA's
default matmul precision does with the jnp rows form on the TPU. With an
fp32 pool every operand is fp32. Slots that are masked (null, padding,
past the causal bound, below the window) contribute exactly zero, so a
sequence's output does not depend on its table's padded width, on the
other rows of the launch, or on how many query rows share its tile. With
``block_kv == 1`` the reduction runs page by page, in the reference's
order. ``skip_blocks=False`` selects the ungrouped baseline: every slot
up to the sequence's page count is gathered, nulls masked in-register.

Queries and outputs stay in HBM: each (sequence, q-tile) copies its
``block_q``-row window in first and out last, so VMEM holds one tile
whatever the number of rows. Steps run in ascending sequence order, so
the rows a short sequence's tile writes past its true length are
overwritten by the next sequence (the host wrapper pads by
``QB * block_q`` rows and slices them off).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
# VMEM one buffered block of pages may take: a block holds at most this
# many bytes of pages (MHA's 512 KiB pages at head dim 128 go 4 to a
# block; 16 no longer fit the chip's 16 MiB of scoped VMEM, double
# buffered beside the tile's accumulators)
BLOCK_BYTES = 2 << 20


def _head_kv(kbuf, slot, h, *, Hkv, packed):
    """Head ``h``'s keys and values, (M, D) each, out of one buffered
    block of fused pages (block_kv, ps, 2*Hkv, D): its rows 2h and 2h+1
    of every token, by a strided load over the block seen as 2-D. On the
    chip a bf16 pool packs rows 2h and 2h+1 into one 32-bit word, so one
    strided load of the block as uint32 carries both (K in the low half),
    and each is widened to fp32 exactly by a shift."""
    blk = kbuf.at[slot]
    nb, ps, H2, D = blk.shape
    M = nb * ps
    rows = blk.reshape(M * H2, D)
    if packed:
        w = rows.bitcast(jnp.uint32)[pl.ds(h, M, stride=Hkv), :]
        k = pltpu.bitcast(w << 16, jnp.float32)
        v = pltpu.bitcast(w & jnp.uint32(0xFFFF0000), jnp.float32)
        return k, v
    return (rows[pl.ds(2 * h, M, stride=H2), :],
            rows[pl.ds(2 * h + 1, M, stride=H2), :])


def _kernel(cu_ref, kvlen_ref, tbl_ref, layer_ref, q_ref, kv_ref, o_ref,
            kbuf, ksem, plist, m_s, l_s, acc_s, qh_s, qbuf, qsem,
            *, ps, block_q, block_kv, nbuf, Hkv, G, D, W, scale, cap,
            window, skip_blocks, packed, stacked,
            qpos_ref=None, kvpos_ref=None, pbuf=None, psem=None):
    has_pos = kvpos_ref is not None
    s = pl.program_id(0)
    qb = pl.program_id(1)
    q0 = cu_ref[s]
    qlen = cu_ref[s + 1] - q0
    kv_len = kvlen_ref[s]
    n_pages = jnp.minimum(jax.lax.div(kv_len + ps - 1, ps), W)
    q_lo = qb * block_q                  # tile rows: [q_lo, q_lo+block_q)
    N, M, cdt = block_q * G, block_kv * ps, kv_ref.dtype

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

    @pl.when((q_lo < qlen) & (kv_len > 0))
    def _tile():
        # -- compact the reachable slots into SMEM ----------------------
        def scan(j, cnt):
            keep = jnp.bool_(True)
            if skip_blocks and not has_pos:
                keep = tbl_ref[s, j] != 0

            @pl.when(keep)
            def _():
                plist[cnt] = j
            return cnt + keep.astype(jnp.int32)

        nnz = jax.lax.fori_loop(lo, jnp.maximum(lo, hi), scan, 0)
        n_blk = jax.lax.div(nnz + block_kv - 1, block_kv)

        def page_copy(i, slot, k):
            pid = tbl_ref[s, plist[i]]
            src = kv_ref.at[layer_ref[0], pid] if stacked else kv_ref.at[pid]
            return pltpu.make_async_copy(src, kbuf.at[slot, k],
                                         ksem.at[slot])

        def pos_copy(i, slot, k):
            return pltpu.make_async_copy(
                kvpos_ref.at[tbl_ref[s, plist[i]]], pbuf.at[slot, k],
                psem.at[slot])

        def each_page(b, slot, act):
            def one(k, carry):
                act(page_copy(b * block_kv + k, slot, k))
                if has_pos:
                    act(pos_copy(b * block_kv + k, slot, k))
                return carry
            jax.lax.fori_loop(0, jnp.minimum(nnz - b * block_kv, block_kv),
                              one, 0)

        def start(b, slot):
            each_page(b, slot, lambda c: c.start())

        # -- warm the pipeline: up to nbuf-1 blocks in flight -----------
        for b in range(nbuf - 1):
            @pl.when(b < n_blk)
            def _(b=b):
                start(b, b)

        # reset the accumulators and fetch the tile's query rows, laid
        # out per KV head as the (block_q * G, D) blocks the matmuls take
        rows = pl.ds(q0 + q_lo, block_q)
        m_s[...] = jnp.full_like(m_s[...], NEG_INF)
        l_s[...] = jnp.zeros_like(l_s[...])
        acc_s[...] = jnp.zeros_like(acc_s[...])
        cp = pltpu.make_async_copy(q_ref.at[rows], qbuf, qsem.at[0])
        cp.start()
        cp.wait()
        qblk = qbuf[...]                                 # (block_q, Hq, D)
        for h in range(Hkv):
            qh = qblk[:, h * G:(h + 1) * G, :].astype(jnp.float32)
            qh_s[h] = qh.reshape(N, D).astype(cdt)

        if has_pos:
            qpos = qpos_ref[pl.ds(q0 + q_lo, block_q)].reshape(block_q, 1)
            qpos = jnp.broadcast_to(qpos, (block_q, G)).reshape(N, 1)
        else:
            # row r of a head's (block_q * G, D) query block is tile row
            # r // G; built in its final shape because Mosaic cannot
            # reshape an int32 (block_q, G) vector into (block_q * G, 1)
            qpos = (kv_len - qlen + q_lo + jax.lax.div(
                jax.lax.broadcasted_iota(jnp.int32, (N, 1), 0),
                jnp.int32(G)))

        col = jax.lax.broadcasted_iota(jnp.int32, (1, M), 1)
        col_page = jax.lax.div(col, jnp.int32(ps))
        row_page = jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0), jnp.int32(ps))

        def body(b, _):
            slot = jax.lax.rem(b, nbuf)

            @pl.when(b + nbuf - 1 < n_blk)
            def _prefetch():
                nxt = b + nbuf - 1
                start(nxt, jax.lax.rem(nxt, nbuf))

            each_page(b, slot, lambda c: c.wait())
            cnt = jnp.minimum(nnz - b * block_kv, block_kv)
            # key position of every column of the block: slot-derived
            # from the compacted list, or read from the position pages
            ok = col_page < cnt
            if has_pos:
                kpos = pbuf[slot].reshape(1, M)
            else:
                def place(k, acc):
                    kp, live_col = acc
                    j = plist[b * block_kv + k]
                    at = col_page == k
                    kp = jnp.where(at, kp + j * ps, kp)
                    if not skip_blocks:
                        live_col &= jnp.logical_not(
                            at & (tbl_ref[s, j] == 0))
                    return kp, live_col

                kpos, ok = jax.lax.fori_loop(
                    0, cnt, place, (jax.lax.rem(col, jnp.int32(ps)), ok))
            valid = ok & (kpos >= 0) & (kpos <= qpos)            # (N, M)
            if window is not None:
                valid &= kpos > (qpos - window)
            # buffer rows past the block's pages hold no page of this
            # launch (possibly never-written VMEM): zero their values
            live = row_page < cnt                                # (M, 1)

            # one KV head at a time, unrolled: the scheduler overlaps one
            # head's matmuls with the next head's loads (MHA decode runs
            # about 3 times slower in a loop of heads); the 32 bodies cost
            # MHA about half a second per trace, which launches share
            # (``ops.ragged_paged_attention``)
            def head(h):
                k, v = _head_kv(kbuf, slot, h, Hkv=Hkv, packed=packed)
                v = jnp.where(live, v, 0.0)
                sc = jax.lax.dot_general(
                    qh_s[h], k.astype(cdt), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if cap is not None:
                    sc = jnp.tanh(sc / cap) * cap
                sc = jnp.where(valid, sc, NEG_INF)
                m = m_s[h]
                m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
                # explicit zeroing (not just exp of NEG_INF): when every
                # key so far was masked, m_new == NEG_INF and
                # exp(sc - m_new) would be 1
                p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
                corr = jnp.exp(m - m_new)
                l_s[h] = l_s[h] * corr + p.sum(axis=-1, keepdims=True)
                acc_s[h] = acc_s[h] * corr + jax.lax.dot_general(
                    p.astype(cdt), v.astype(cdt), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_s[h] = m_new

            for h in range(Hkv):
                head(h)
            return 0

        jax.lax.fori_loop(0, n_blk, body, 0)

        outs = []
        for h in range(Hkv):
            l = l_s[h]
            o = acc_s[h] / jnp.where(l == 0.0, 1.0, l)
            outs.append(o.reshape(block_q, G, D))
        qbuf[...] = jnp.concatenate(outs, axis=1).astype(qbuf.dtype)
        # waited here so that the next sequence's tile, which may overlap
        # the rows this tile writes past its sequence's end, lands after it
        cp = pltpu.make_async_copy(qbuf, o_ref.at[rows], qsem.at[1])
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
                                  layer=None, interpret: bool = False):
    """Pallas entry. ``q_pad`` must be padded with at least
    ``ceil(max_q_len / block_q) * block_q`` extra rows so every q-tile's
    block load stays in bounds; callers go through
    ``ops.ragged_paged_attention`` which pads and re-slices.
    ``block_q``/``block_kv``/``num_buffers`` default to the autotuner's
    cached best config for this (page_size, head_dim) geometry;
    ``skip_blocks=False`` selects the ungrouped full-gather baseline.
    With ``layer`` (a scalar index) ``kv_pages`` is the pool stacked over
    layers, (L, P, ps, 2*Hkv, D), and pages are read from that layer:
    nothing slices the layer's pool out."""
    from .tune import best_config

    Tpad, Hq, D = q_pad.shape
    ps, H2 = kv_pages.shape[-3], kv_pages.shape[-2]
    stacked = kv_pages.ndim == 5
    if stacked and layer is None:
        raise ValueError("a pool stacked over layers needs its layer index")
    Hkv = H2 // 2
    G = Hq // Hkv
    S, W = page_table.shape
    max_q = max_q_len
    has_pos = kv_pos_pages is not None
    if has_pos and stacked:
        raise ValueError("explicit positions take an unstacked pool")
    cdt = kv_pages.dtype
    packed = (not interpret) and cdt == jnp.bfloat16

    cfg = best_config(ps, D)
    bq = max(1, min(block_q or cfg.block_q, max_q))
    page_bytes = ps * H2 * D * jnp.dtype(cdt).itemsize
    bkv = max(1, min(block_kv or cfg.block_kv, BLOCK_BYTES // page_bytes))
    nbuf = max(2, min(num_buffers or cfg.num_buffers, 4))
    QB = -(-max_q // bq)

    scratch = [
        pltpu.VMEM((nbuf, bkv, ps, H2, D), cdt),         # kbuf
        pltpu.SemaphoreType.DMA((nbuf,)),                # ksem
        pltpu.SMEM((W,), jnp.int32),                     # plist (compacted)
        pltpu.VMEM((Hkv, bq * G, 1), jnp.float32),       # m_s
        pltpu.VMEM((Hkv, bq * G, 1), jnp.float32),       # l_s
        pltpu.VMEM((Hkv, bq * G, D), jnp.float32),       # acc_s
        pltpu.VMEM((Hkv, bq * G, D), cdt),               # qh_s
        pltpu.VMEM((bq, Hq, D), q_pad.dtype),            # qbuf
        pltpu.SemaphoreType.DMA((2,)),                   # qsem
    ]
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    if has_pos:
        in_specs = [
            hbm,                                         # q
            hbm,                                         # kv_pages
            pl.BlockSpec((Tpad,), lambda s, qb, *_: (0,)),   # q_pos
            hbm,                                         # kv_pos_pages
        ]
        args = [q_pad, kv_pages,
                jnp.asarray(q_pos_pad, jnp.int32),
                jnp.asarray(kv_pos_pages, jnp.int32)]
        scratch += [
            pltpu.VMEM((nbuf, bkv, ps), jnp.int32),      # pbuf
            pltpu.SemaphoreType.DMA((nbuf,)),            # psem
        ]
    else:
        in_specs = [hbm, hbm]
        args = [q_pad, kv_pages]

    kernel = functools.partial(
        _kernel, ps=ps, block_q=bq, block_kv=bkv, nbuf=nbuf, Hkv=Hkv, G=G,
        D=D, W=W, scale=scale, cap=cap, window=window,
        skip_blocks=skip_blocks, packed=packed, stacked=stacked)

    def wrapped(cu_ref, kvlen_ref, tbl_ref, layer_ref, q_ref, kv_ref, *rest):
        if has_pos:
            qpos_ref, kvpos_ref, o_ref = rest[0], rest[1], rest[2]
            scratch_refs, (pbuf, psem) = rest[3:-2], rest[-2:]
            kernel(cu_ref, kvlen_ref, tbl_ref, layer_ref, q_ref, kv_ref,
                   o_ref, *scratch_refs, qpos_ref=qpos_ref,
                   kvpos_ref=kvpos_ref, pbuf=pbuf, psem=psem)
        else:
            kernel(cu_ref, kvlen_ref, tbl_ref, layer_ref, q_ref, kv_ref,
                   *rest)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S, QB),
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
      jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1),
      *args)


def pages_gathered(page_table, cu_q_lens, kv_lens, *, page_size: int,
                   max_q_len: int, block_q: Optional[int] = None,
                   window: Optional[int] = None,
                   skip_blocks: bool = True) -> int:
    """Host-side replica of the kernel's gather decisions: the number of
    page DMAs the grid issues (the achieved page-read stream the kernel
    bench meters, and what ``ComputeBackend`` counts for a launch). Per
    sequence and q-tile, the non-null slots from the window's lower bound
    up to the tile's causal bound; vectorised over sequences and tiles.
    Slot-derived positions only."""
    import numpy as np

    from .tune import best_config

    tbl = np.asarray(page_table)
    cu = np.asarray(cu_q_lens, np.int64)
    kvl = np.asarray(kv_lens, np.int64)
    W = tbl.shape[1]
    ps = page_size
    bq = max(1, min(block_q or best_config(ps, 0).block_q,
                    max(1, max_q_len)))
    qlen = (cu[1:] - cu[:-1])[:, None]                   # (S, 1)
    kv_len = kvl[:, None]
    n_pages = np.minimum(-(-kv_len // ps), W)
    q_lo = np.arange(0, max(1, max_q_len), bq)[None, :]  # (1, QB)
    run = (q_lo < qlen) & (kv_len > 0)                   # (S, QB)
    if not skip_blocks:
        return int(np.sum(np.where(run, n_pages, 0)))
    last_qpos = np.minimum(kv_len - qlen + q_lo + bq, kv_len) - 1
    hi = np.minimum(n_pages, last_qpos // ps + 1)
    lo = (np.maximum(0, kv_len - qlen + q_lo - window + 1) // ps
          if window is not None else np.zeros_like(hi))
    # non-null slots below each bound, by a prefix count over the table
    live = np.concatenate([np.zeros((tbl.shape[0], 1), np.int64),
                           np.cumsum(tbl != 0, axis=1)], axis=1)
    rows = np.arange(tbl.shape[0])[:, None]
    hi = np.clip(hi, 0, W)
    lo = np.clip(np.minimum(lo, hi), 0, W)
    count = live[rows, hi] - live[rows, lo]
    return int(np.sum(np.where(run, count, 0)))

"""Self-attention: chunked (flash-style) prefill/train attention and
single-token decode attention over (ring-buffer) KV caches.

The pure-XLA chunked path is what the dry-run lowers (Mosaic kernels cannot
lower on the CPU backend — DESIGN.md §4); `repro.kernels.flash_attention`
is the Pallas TPU twin validated against `chunked_attention` in tests.

Design notes
- q-chunks are a static python loop so each chunk's KV range is *exact*
  (causal work ~ S^2/2, not S^2; windowed work ~ S*W) — this keeps the
  HLO-derived roofline honest. KV within a range is processed by lax.scan
  with a running (m, l, acc) online softmax in fp32.
- GQA/MQA via a (B, S, Hkv, G, Dh) query layout; MHA is G=1... Hkv=H.
- KV caches are ring buffers of size min(total, window) with an explicit
  stored-position array; masking is position-based so ring order is
  irrelevant (RoPE is applied before caching).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import (
    interleave_kv,
    paged_attention_rows,
    ragged_paged_attention,
    write_tokens_to_pages,
)
from repro.models.layers import apply_rope, rmsnorm, softcap
from repro.models.param import ParamDef

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# Param defs
# ---------------------------------------------------------------------------


def attn_defs(cfg: ModelConfig) -> dict:
    d, h, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    defs = {
        "wq": ParamDef((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamDef((d, hk, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((d, hk, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((hd,), ("head_dim",), init="zeros")
        defs["k_norm"] = ParamDef((hd,), ("head_dim",), init="zeros")
    return defs


def _q_scale(cfg: ModelConfig) -> float:
    return cfg.q_scale if cfg.q_scale is not None else cfg.resolved_head_dim ** -0.5


# ---------------------------------------------------------------------------
# Chunked flash-style attention (training / prefill)
# ---------------------------------------------------------------------------


def _block_attn(q, k, v, qpos, kpos, *, scale, cap, window):
    """One (q-chunk x kv-chunk) online-softmax block.

    q: (B, Hkv, G, Q, D); k/v: (B, K, Hkv, D); qpos: (Q,), kpos: (K,)
    returns scores-post-mask partial (p, m, l-terms) pieces. Masking is
    purely positional (causality + window): prompts are never padded
    (DESIGN.md §5), so there is no pad-validity special case.
    """
    s = jnp.einsum("bhgqd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if cap is not None:
        s = jnp.tanh(s / cap) * cap
    mask = kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > (qpos[:, None] - window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    return s


def _flash_scan(q_i, k_b, v_b, qpos, kpos_b, sc):
    """Online-softmax over kv chunks. q_i: (B,Hkv,G,Q,Dk); k_b/v_b:
    (nkv,B,K,Hkv,D*); returns (out_unnormalized-normalized fp32, m, l)."""
    scale, cap, window = sc
    B, Hkv, G, Q, Dk = q_i.shape
    Dv = v_b.shape[-1]
    m0 = jnp.full((B, Hkv, G, Q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Q), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Q, Dv), jnp.float32)

    def body(carry, xs):
        m, l, acc = carry
        kc, vc, kp = xs
        s = _block_attn(q_i, kc, vc, qpos, kp, scale=scale, cap=cap,
                        window=window)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc,
                        preferred_element_type=jnp.float32)
        acc = acc * corr[..., None] + pv
        return (m_new, l, acc), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (k_b, v_b, kpos_b))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return acc / l_safe[..., None], m, l_safe


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _flash_chunk(q_i, k_b, v_b, qpos, kpos_b, sc):
    out, _, _ = _flash_scan(q_i, k_b, v_b, qpos, kpos_b, sc)
    return out


def _flash_chunk_fwd(q_i, k_b, v_b, qpos, kpos_b, sc):
    out, m, l = _flash_scan(q_i, k_b, v_b, qpos, kpos_b, sc)
    return out, (q_i, k_b, v_b, qpos, kpos_b, out, m, l)


def _flash_chunk_bwd(sc, res, g):
    """Flash-attention backward: recompute each block's probabilities from
    the saved (m, l) stats; O(block) live memory instead of O(S^2)."""
    scale, cap, window = sc
    q_i, k_b, v_b, qpos, kpos_b, out, m, l = res
    g = g.astype(jnp.float32)
    delta = jnp.sum(g * out, axis=-1)  # (B,Hkv,G,Q)
    dq0 = jnp.zeros(q_i.shape, jnp.float32)

    def body(dq, xs):
        kc, vc, kp = xs
        s_scaled = jnp.einsum("bhgqd,bkhd->bhgqk", q_i, kc,
                              preferred_element_type=jnp.float32) * scale
        if cap is not None:
            t = jnp.tanh(s_scaled / cap)
            s_post = t * cap
        else:
            s_post = s_scaled
        mask = kp[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kp[None, :] > (qpos[:, None] - window)
        s_post = jnp.where(mask[None, None, None], s_post, NEG_INF)
        p = jnp.exp(s_post - m[..., None]) / l[..., None]  # (B,Hkv,G,Q,K)
        dv_c = jnp.einsum("bhgqk,bhgqd->bkhd", p, g,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhgqd,bkhd->bhgqk", g, vc,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None])  # wrt post-softcap logits
        if cap is not None:
            ds = ds * (1.0 - jnp.square(t))  # through tanh softcap
        ds = ds * scale
        dq = dq + jnp.einsum("bhgqk,bkhd->bhgqd", ds, kc,
                             preferred_element_type=jnp.float32)
        dk_c = jnp.einsum("bhgqk,bhgqd->bkhd", ds, q_i,
                          preferred_element_type=jnp.float32)
        return dq, (dk_c, dv_c)

    dq, (dk_b, dv_b) = jax.lax.scan(body, dq0, (k_b, v_b, kpos_b))
    return (dq.astype(q_i.dtype), dk_b.astype(k_b.dtype),
            dv_b.astype(v_b.dtype), None, None)


_flash_chunk.defvjp(_flash_chunk_fwd, _flash_chunk_bwd)


def chunked_attention(
    q, k, v,
    *,
    window: Optional[int] = None,
    cap: Optional[float] = None,
    scale: float,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
):
    """q: (B, Sq, H, D); k/v: (B, Skv, Hkv, D) -> (B, Sq, H, D).

    Causal self-attention over an unpadded sequence: query i sits at
    position i against keys at positions 0..Skv-1. (The per-query offset
    and pad-validity parameters of the padded whole-prompt era are gone —
    chunk-internal KV zero-padding is masked by causality alone, since a
    padded key's position always exceeds every query's.)
    """
    B, Sq, H, Dk = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]  # MLA: value head dim may differ from key dim
    G = H // Hkv
    q = q.reshape(B, Sq, Hkv, G, Dk).transpose(0, 2, 3, 1, 4)  # (B,Hkv,G,Sq,Dk)

    q_chunk = min(q_chunk, Sq)
    while Sq % q_chunk:
        q_chunk //= 2
    kv_chunk = min(kv_chunk, Skv)
    nq = Sq // q_chunk

    outs = []
    for i in range(nq):
        q_i = jax.lax.slice_in_dim(q, i * q_chunk, (i + 1) * q_chunk, axis=3)
        qpos = i * q_chunk + jnp.arange(q_chunk)
        hi = min(Skv, (i + 1) * q_chunk)  # causal end (static)
        lo = 0
        if window is not None:
            lo = max(0, i * q_chunk - window + 1)
            lo = (lo // kv_chunk) * kv_chunk  # align to chunk grid
        span = hi - lo
        nkv = max(1, -(-span // kv_chunk))
        span_pad = nkv * kv_chunk
        lo = max(0, min(lo, Skv - span_pad))  # keep the padded span in-bounds
        if lo + span_pad > Skv:  # Skv < span_pad: pad KV once below
            span_pad = ((Skv - lo + kv_chunk - 1) // kv_chunk) * kv_chunk
            nkv = span_pad // kv_chunk
        k_sl = jax.lax.slice_in_dim(k, lo, min(lo + span_pad, Skv), axis=1)
        v_sl = jax.lax.slice_in_dim(v, lo, min(lo + span_pad, Skv), axis=1)
        pad = lo + span_pad - Skv
        if pad > 0:
            k_sl = jnp.pad(k_sl, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v_sl = jnp.pad(v_sl, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpos0 = lo + jnp.arange(span_pad)

        k_b = k_sl.reshape(B, nkv, kv_chunk, Hkv, Dk).transpose(1, 0, 2, 3, 4)
        v_b = v_sl.reshape(B, nkv, kv_chunk, Hkv, Dv).transpose(1, 0, 2, 3, 4)
        kpos_b = kpos0.reshape(nkv, kv_chunk)

        out = _flash_chunk(q_i, k_b, v_b, qpos, kpos_b,
                           (scale, cap, window))
        outs.append(out)

    out = jnp.concatenate(outs, axis=3) if len(outs) > 1 else outs[0]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, Dv)
    return out.astype(v.dtype)


# ---------------------------------------------------------------------------
# Decode attention (one token against a ring-buffer cache)
# ---------------------------------------------------------------------------


def decode_attention(q, k_cache, v_cache, cache_pos, cur_pos, *,
                     window: Optional[int] = None, cap: Optional[float] = None,
                     scale: float):
    """q: (B, 1, H, D); caches: (B, C, Hkv, D); cache_pos: (B, C) stored
    absolute positions (-1 = empty); cur_pos: () or (B,). -> (B, 1, H, D)."""
    B, _, H, D = q.shape
    Hkv = k_cache.shape[2]
    G = H // Hkv
    qq = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qq, k_cache, preferred_element_type=jnp.float32)
    s = s * scale
    if cap is not None:
        s = jnp.tanh(s / cap) * cap
    cur = jnp.asarray(cur_pos)
    cur = cur[:, None] if cur.ndim == 1 else cur[None, None]
    mask = (cache_pos >= 0) & (cache_pos <= cur)
    if window is not None:
        mask &= cache_pos > (cur - window)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhgk,bkhd->bhgd", (p / l).astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, D).astype(v_cache.dtype)


# ---------------------------------------------------------------------------
# Extend attention (a chunk of new tokens against a ring-buffer cache) —
# the compute half of chunked prefill: queries at absolute positions `qpos`
# attend to everything already resident in the cache (earlier chunks) plus
# the chunk itself, with the same position-based masking as decode.
# ---------------------------------------------------------------------------


def extend_attention(q, k_cache, v_cache, cache_pos, qpos, *,
                     window: Optional[int] = None, cap: Optional[float] = None,
                     scale: float):
    """q: (B, S, H, D); caches: (B, C, Hkv, D); cache_pos: (B, C) stored
    absolute positions (-1 = empty); qpos: (S,) absolute query positions.
    The chunk's own keys must already be written into the cache.
    -> (B, S, H, Dv)."""
    B, S, H, D = q.shape
    Hkv = k_cache.shape[2]
    Dv = v_cache.shape[-1]
    G = H // Hkv
    qq = q.reshape(B, S, Hkv, G, D).transpose(0, 2, 3, 1, 4)  # (B,Hkv,G,S,D)
    s = jnp.einsum("bhgqd,bkhd->bhgqk", qq, k_cache,
                   preferred_element_type=jnp.float32)
    s = s * scale
    if cap is not None:
        s = jnp.tanh(s / cap) * cap
    qpos = jnp.asarray(qpos, jnp.int32)
    mask = (cache_pos[:, None, :] >= 0) & (cache_pos[:, None, :] <= qpos[None, :, None])
    if window is not None:
        mask &= cache_pos[:, None, :] > (qpos[None, :, None] - window)
    s = jnp.where(mask[:, None, None], s, NEG_INF)  # (B,Hkv,G,S,C)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    l = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", (p / l).astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, Dv)
    return out.astype(v_cache.dtype)


# ---------------------------------------------------------------------------
# Cache plumbing
# ---------------------------------------------------------------------------


def cache_len_for(window: Optional[int], max_len: int) -> int:
    return min(max_len, window) if window is not None else max_len


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype) -> dict:
    hk, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, cache_len, hk, hd), dtype),
        "v": jnp.zeros((batch, cache_len, hk, hd), dtype),
        "pos": jnp.full((batch, cache_len), -1, jnp.int32),
    }


def abstract_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype) -> dict:
    hk, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "k": jax.ShapeDtypeStruct((batch, cache_len, hk, hd), dtype),
        "v": jax.ShapeDtypeStruct((batch, cache_len, hk, hd), dtype),
        "pos": jax.ShapeDtypeStruct((batch, cache_len), jnp.int32),
    }


def fill_cache_from_prefill(cache: dict, k, v) -> dict:
    """Write a maximal first chunk's keys/values (B, S, Hkv, D) — always
    unpadded, token i at position i — into a (possibly smaller, windowed)
    cache. Keeps the last `cache_len` tokens."""
    S = k.shape[1]
    C = cache["k"].shape[1]
    take = min(S, C)
    ksl = jax.lax.slice_in_dim(k, S - take, S, axis=1)
    vsl = jax.lax.slice_in_dim(v, S - take, S, axis=1)
    pos = jnp.arange(S - take, S, dtype=jnp.int32)
    # ring placement: slot = pos % C
    slots = pos % C
    k_new = cache["k"].at[:, slots].set(ksl.astype(cache["k"].dtype))
    v_new = cache["v"].at[:, slots].set(vsl.astype(cache["v"].dtype))
    pos_new = cache["pos"].at[:, slots].set(pos[None, :])
    return {"k": k_new, "v": v_new, "pos": pos_new}


def write_chunk_to_cache(cache: dict, k, v, positions) -> dict:
    """Write a chunk's keys/values (B, S, Hkv, D) at absolute positions
    `positions` (S,) into the ring cache (slot = pos % C). Chunks must not
    exceed the cache length, or intra-chunk ring slots would collide."""
    C = cache["k"].shape[1]
    positions = jnp.asarray(positions, jnp.int32)
    slots = positions % C
    k_new = cache["k"].at[:, slots].set(k.astype(cache["k"].dtype))
    v_new = cache["v"].at[:, slots].set(v.astype(cache["v"].dtype))
    pos_new = cache["pos"].at[:, slots].set(positions[None, :])
    return {"k": k_new, "v": v_new, "pos": pos_new}


def append_to_cache(cache: dict, k1, v1, pos, active=None) -> dict:
    """Append one token (B, 1, Hkv, D) at absolute position(s) `pos` —
    a scalar (dry-run fast path: one dynamic_update_slice) or (B,) per-
    sequence positions (continuous batching: scatter per row).

    ``active`` ((B,) bool, optional): rows where False keep their cache
    untouched — required when decode rounds interleave with chunked
    prefill, so a mid-prefill slot's ring entries aren't clobbered by the
    batched decode write."""
    C = cache["k"].shape[1]
    B = cache["pos"].shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        # masked elementwise write, NOT dynamic_update_slice: a DUS at a
        # traced index on a sharded cache-sequence dim makes GSPMD
        # all-gather + re-shard the whole cache every layer; the masked
        # write stays local on every shard (found via the §Perf byte
        # breakdown of the decode cells).
        slot = pos % C
        hit = (jnp.arange(C) == slot)[None, :, None, None]
        k_new = jnp.where(hit, k1.astype(cache["k"].dtype), cache["k"])
        v_new = jnp.where(hit, v1.astype(cache["v"].dtype), cache["v"])
        pos_new = jnp.where(hit[:, :, 0, 0], pos, cache["pos"])
    else:
        slot = pos % C  # (B,)
        rows = jnp.arange(B)
        k_new = cache["k"].at[rows, slot].set(k1[:, 0].astype(cache["k"].dtype))
        v_new = cache["v"].at[rows, slot].set(v1[:, 0].astype(cache["v"].dtype))
        pos_new = cache["pos"].at[rows, slot].set(pos)
    if active is not None:
        act = jnp.asarray(active, bool)
        k_new = jnp.where(act[:, None, None, None], k_new, cache["k"])
        v_new = jnp.where(act[:, None, None, None], v_new, cache["v"])
        pos_new = jnp.where(act[:, None], pos_new, cache["pos"])
    return {"k": k_new, "v": v_new, "pos": pos_new}


# ---------------------------------------------------------------------------
# Full attention sublayer (projections + rope + attention + out-proj)
# ---------------------------------------------------------------------------


def paged_pages_gathered(rows: int, width: int) -> int:
    """KV pages one attention layer of a paged step gathers for ``rows``
    query rows (B·S) over a ``width``-slot page table when it attends in
    the rows form: each query row reads its sequence's whole table
    (``jnp.repeat(page_table, S)``), padding included (MLA's call does the
    same). The kernel path's count is ``kernel.pages_gathered``.
    ``ComputeBackend`` counts on the host with these, so a change of
    either call changes its count here."""
    return rows * width


def attention_sublayer(
    cfg: ModelConfig,
    p: dict,
    x,
    *,
    positions,
    window: Optional[int],
    sh=None,
    cache: Optional[dict] = None,
    mode: str = "train",  # train | prefill | extend | decode
    cur_pos=None,
    decode_active=None,   # (B,) bool: rows whose cache the decode may touch
    page_table=None,      # (B, W) int32: paged compute plane (DESIGN.md §10)
    layer=None,           # index into a pool stacked over layers
    attn_kernel: bool = False,  # paged: the ragged kernel, not the rows form
) -> Tuple[jax.Array, Optional[dict]]:
    """x: (B, S, d) -> (attn_out (B, S, d), updated cache or None)."""
    B, S, d = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if sh is not None:
        q = sh.c(q, ("act_batch", None, "act_heads", None))

    scale = _q_scale(cfg)
    new_cache = None
    if cache is not None and "kv_pages" in cache:
        # paged compute plane: write this step's KV straight into the
        # shared page pool and attend page-by-page — extend and decode
        # are the same call, only the positions differ. With ``layer``
        # the pool is the whole stack, updated in place. ``attn_kernel``
        # attends through the ragged kernel: each of the B sequences
        # (S query rows each) reads only the live pages of its table up
        # to its last position; else every query row reads its whole
        # table in the rows form.
        assert page_table is not None
        if mode == "decode":
            cur = jnp.asarray(cur_pos, jnp.int32)
            pos2d = (cur.reshape(-1, 1) if cur.ndim
                     else jnp.full((B, 1), cur, jnp.int32))
            act = decode_active
        else:
            pos2d = jnp.broadcast_to(
                jnp.asarray(positions, jnp.int32).reshape(1, S), (B, S))
            act = None
        with jax.named_scope("kv_write"):
            kvp = write_tokens_to_pages(cache["kv_pages"],
                                        interleave_kv(k, v), pos2d,
                                        page_table, active=act, layer=layer)
        Hq, hd = q.shape[2], q.shape[3]
        if attn_kernel:
            kv_lens = pos2d[:, -1] + 1
            if act is not None:
                # an idle decode row attends nothing and writes nothing:
                # its output rows are zeroed, not left as the kernel's
                # unwritten output
                act = jnp.asarray(act, bool)
                kv_lens = jnp.where(act, kv_lens, 0)
            out = ragged_paged_attention(
                q.reshape(B * S, Hq, hd), kvp, page_table,
                jnp.arange(B + 1, dtype=jnp.int32) * S, kv_lens,
                scale=scale, cap=cfg.attn_softcap, window=window,
                max_q_len=S, layer=layer).reshape(B, S, Hq, hd)
            if act is not None:
                out = jnp.where(act[:, None, None, None], out, 0)
        else:
            out = paged_attention_rows(
                q.reshape(B * S, Hq, hd), kvp,
                jnp.repeat(page_table, S, axis=0), pos2d.reshape(B * S),
                scale=scale, cap=cfg.attn_softcap, window=window,
                layer=layer,
            ).reshape(B, S, Hq, hd)
        out = jnp.einsum("bshk,hkd->bsd", out.astype(q.dtype), p["wo"])
        return out, {"kv_pages": kvp}
    if mode == "decode":
        assert cache is not None
        new_cache = append_to_cache(cache, k, v, cur_pos, active=decode_active)
        if sh is not None:
            new_cache = sh.kv(cfg, new_cache)
        out = decode_attention(q, new_cache["k"], new_cache["v"], new_cache["pos"],
                               cur_pos, window=window, cap=cfg.attn_softcap, scale=scale)
    elif mode == "extend":
        # chunked prefill: `positions` are the chunk's absolute positions;
        # write the chunk's KV into the ring cache, then attend against the
        # whole cache (earlier chunks + this one) with position masking.
        assert cache is not None
        new_cache = write_chunk_to_cache(cache, k, v, positions)
        if sh is not None:
            new_cache = sh.kv(cfg, new_cache)
        out = extend_attention(q, new_cache["k"], new_cache["v"],
                               new_cache["pos"], positions, window=window,
                               cap=cfg.attn_softcap, scale=scale)
    else:
        # train and first-chunk prefill share the full-sequence flash
        # path; a prefill additionally fills the fresh ring cache. Inputs
        # are always unpadded (DESIGN.md §5), so causal masking is exact.
        out = chunked_attention(q, k, v, window=window,
                                cap=cfg.attn_softcap, scale=scale,
                                q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
        if mode == "prefill":
            assert cache is not None
            new_cache = fill_cache_from_prefill(cache, k, v)
            if sh is not None:
                new_cache = sh.kv(cfg, new_cache)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return out, new_cache

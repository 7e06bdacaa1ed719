"""Multi-head Latent Attention (DeepSeek-V2).

KV is cached in *compressed* form — the latent c_kv (kv_lora_rank) plus a
shared rope key (qk_rope_dim) per token — which is the KV-read-bandwidth
optimization that makes this the most paper-representative architecture
(DESIGN.md §3): the decode read stream per token shrinks ~an order of
magnitude vs materialized GQA KV.

Two decode paths:
- baseline (``mla_absorb=False``): expand the cached latents to per-head
  k/v every step (faithful naive formulation);
- absorbed (``mla_absorb=True``): fold W_UK into the query and W_UV into
  the output so attention runs directly over the compressed cache — the
  §Perf hillclimb lever for deepseek-v2-lite decode.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import (
    paged_attention_rows,
    write_tokens_to_pages,
)
from repro.models.attention import NEG_INF, chunked_attention
from repro.models.layers import apply_rope, rmsnorm
from repro.models.param import ParamDef


def mla_defs(cfg: ModelConfig) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    r, dn, dr, dv = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    defs = {
        "w_dkv": ParamDef((d, r + dr), ("embed", "lora")),
        "kv_norm": ParamDef((r,), ("lora",), init="zeros"),
        "w_uk": ParamDef((r, h, dn), ("lora", "heads", "head_dim")),
        "w_uv": ParamDef((r, h, dv), ("lora", "heads", "head_dim")),
        "wo": ParamDef((h, dv, d), ("heads", "head_dim", "embed")),
    }
    if cfg.q_lora_rank:
        defs["w_dq"] = ParamDef((d, cfg.q_lora_rank), ("embed", "lora"))
        defs["q_norm"] = ParamDef((cfg.q_lora_rank,), ("lora",), init="zeros")
        defs["w_uq"] = ParamDef((cfg.q_lora_rank, h, dn + dr), ("lora", "heads", "head_dim"))
    else:
        defs["wq"] = ParamDef((d, h, dn + dr), ("embed", "heads", "head_dim"))
    return defs


def _project_q(cfg: ModelConfig, p: dict, x, positions):
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    if cfg.q_lora_rank:
        cq = rmsnorm(x @ p["w_dq"], p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    qn, qr = q[..., :dn], q[..., dn:]
    qr = apply_rope(qr, positions, cfg.rope_theta)
    return qn, qr


def _compress_kv(cfg: ModelConfig, p: dict, x, positions):
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    ckv = x @ p["w_dkv"]  # (B, S, r+dr)
    c, kr = ckv[..., :r], ckv[..., r:]
    c = rmsnorm(c, p["kv_norm"], cfg.norm_eps)
    kr = apply_rope(kr[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return c, kr


def mla_cache_init(cfg: ModelConfig, batch: int, cache_len: int, dtype, abstract=False) -> dict:
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    mk = jax.ShapeDtypeStruct if abstract else (lambda s, d: jnp.zeros(s, d))
    cache = {
        "c": mk((batch, cache_len, r), dtype),
        "kr": mk((batch, cache_len, dr), dtype),
    }
    if abstract:
        cache["pos"] = jax.ShapeDtypeStruct((batch, cache_len), jnp.int32)
    else:
        cache["pos"] = jnp.full((batch, cache_len), -1, jnp.int32)
    return cache


def _expand(cfg: ModelConfig, p: dict, c):
    """latents (B, C, r) -> k_nope (B, C, H, dn), v (B, C, H, dv)."""
    kn = jnp.einsum("bcr,rhk->bchk", c, p["w_uk"])
    v = jnp.einsum("bcr,rhk->bchk", c, p["w_uv"])
    return kn, v


def _cache_attention(cfg: ModelConfig, p: dict, qn, qr, cache_c, cache_kr,
                     mask, scale, out_dtype):
    """Queries (B, S, H, ·) against the compressed latent cache (B, C, ·)
    under ``mask`` (B, S, C) — the one cache-attention kernel decode
    (S=1) and extend (a whole chunk) share, in both the absorbed and the
    naive-expansion formulation."""
    if cfg.mla_absorb:
        # fold W_UK into q, W_UV into out: attention over compressed cache
        qc = jnp.einsum("bshk,rhk->bshr", qn, p["w_uk"])
        s = jnp.einsum("bshr,bcr->bshc", qc, cache_c,
                       preferred_element_type=jnp.float32)
        s += jnp.einsum("bshk,bck->bshc", qr, cache_kr,
                        preferred_element_type=jnp.float32)
        s = jnp.where(mask[:, :, None, :], s * scale, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        oc = jnp.einsum("bshc,bcr->bshr", pr.astype(out_dtype), cache_c)
        return jnp.einsum("bshr,rhk->bshk", oc, p["w_uv"])
    kn_e, v_e = _expand(cfg, p, cache_c)  # (B,C,H,*) every step
    s = jnp.einsum("bshk,bchk->bshc", qn, kn_e,
                   preferred_element_type=jnp.float32)
    s += jnp.einsum("bshk,bck->bshc", qr, cache_kr,
                    preferred_element_type=jnp.float32)
    s = jnp.where(mask[:, :, None, :], s * scale, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bshc,bchk->bshk", pr.astype(out_dtype), v_e)


def mla_sublayer(
    cfg: ModelConfig,
    p: dict,
    x,
    *,
    positions,
    sh=None,
    cache: Optional[dict] = None,
    mode: str = "train",
    cur_pos=None,
    decode_active=None,
    page_table=None,  # (B, W) int32: paged compute plane (DESIGN.md §10)
    layer=None,       # index into a pool stacked over layers
) -> Tuple[jax.Array, Optional[dict]]:
    """Modes: ``train``/``prefill`` (full-sequence chunked attention over
    the *unpadded* layout — token i at absolute position i, so causal
    masking is exact and the cached latent positions are truthful;
    DESIGN.md §5), ``extend`` (chunked-prefill continuation: the chunk's
    compressed latents are written into the ring cache at their absolute
    positions, then each query attends the whole cache under position
    masking — the latent cache is *positional*, exactly like attention
    KV, so a prefix snapshot seeds any page-aligned boundary and, with a
    sub-page tail copy, the exact mid-page token boundary; DESIGN.md §8,
    §9), and ``decode`` (one token). ``decode_active`` ((B,) bool, decode
    only): rows where False keep their cached latents untouched."""
    B, S, d = x.shape
    dn, dr, dv, r = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    scale = (dn + dr) ** -0.5
    qn, qr = _project_q(cfg, p, x, positions)
    c, kr = _compress_kv(cfg, p, x, positions)
    new_cache = None

    if cache is not None and "kv_pages" in cache:
        # paged compute plane, always absorbed: the fused page row stores
        # K' = [c, kr] and V' = [c, 0] (one Hkv=1 head of width r+dr), so
        # q' = [qn·W_UK, qr] gives q'·K' = qc·c + qr·kr — the absorbed
        # score exactly — and p@V' carries the latent context in its
        # first r lanes, expanded through W_UV after the kernel.
        assert page_table is not None
        k_f = jnp.concatenate([c, kr], axis=-1)               # (B, S, r+dr)
        v_f = jnp.concatenate([c, jnp.zeros_like(kr)], axis=-1)
        kv_new = jnp.stack([k_f, v_f], axis=2)                # (B, S, 2, r+dr)
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
            kvp = write_tokens_to_pages(cache["kv_pages"], kv_new, pos2d,
                                        page_table, active=act, layer=layer)
        qc = jnp.einsum("bshk,rhk->bshr", qn, p["w_uk"])
        q_f = jnp.concatenate([qc, qr], axis=-1)              # (B, S, H, r+dr)
        H = q_f.shape[2]
        o = paged_attention_rows(
            q_f.reshape(B * S, H, r + dr), kvp,
            jnp.repeat(page_table, S, axis=0), pos2d.reshape(B * S),
            scale=scale, layer=layer).reshape(B, S, H, r + dr)
        out = jnp.einsum("bshr,rhk->bshk", o[..., :r].astype(x.dtype),
                         p["w_uv"])
        out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        return out, {"kv_pages": kvp}
    if mode == "decode":
        assert cache is not None
        C = cache["c"].shape[1]
        cur = jnp.asarray(cur_pos, jnp.int32)
        if cur.ndim == 0:
            # masked write (not DUS): keeps seq-sharded caches local under
            # GSPMD — see models/attention.py append_to_cache
            slot = cur % C
            hit = (jnp.arange(C) == slot)[None, :, None]
            c_new = jnp.where(hit, c.astype(cache["c"].dtype), cache["c"])
            kr_new = jnp.where(hit, kr.astype(cache["kr"].dtype), cache["kr"])
            pos_new = jnp.where(hit[:, :, 0], cur, cache["pos"])
        else:  # (B,) per-sequence positions (continuous batching)
            slot = cur % C
            rows = jnp.arange(B)
            c_new = cache["c"].at[rows, slot].set(c[:, 0].astype(cache["c"].dtype))
            kr_new = cache["kr"].at[rows, slot].set(kr[:, 0].astype(cache["kr"].dtype))
            pos_new = cache["pos"].at[rows, slot].set(cur)
        if decode_active is not None:
            act = jnp.asarray(decode_active, bool)
            c_new = jnp.where(act[:, None, None], c_new, cache["c"])
            kr_new = jnp.where(act[:, None, None], kr_new, cache["kr"])
            pos_new = jnp.where(act[:, None], pos_new, cache["pos"])
        new_cache = {"c": c_new, "kr": kr_new, "pos": pos_new}
        if sh is not None:
            # latents shard over (batch, cache-seq) — must match the input
            # cache sharding or GSPMD reshards the cache every layer
            new_cache = {k: sh.c(v, ("act_batch", "act_kv_seq", None)[: v.ndim])
                         for k, v in new_cache.items()}
        cur_b = cur if cur.ndim else cur[None]
        mask = (new_cache["pos"] >= 0) & (new_cache["pos"] <= cur_b[:, None])
        out = _cache_attention(cfg, p, qn, qr, new_cache["c"],
                               new_cache["kr"], mask[:, None, :], scale,
                               x.dtype)
    elif mode == "extend":
        # chunked-prefill continuation: write the chunk's compressed
        # latents into the ring cache at their absolute positions, then
        # attend against the whole cache (earlier chunks + this chunk)
        # under the same position masking decode uses — stale entries
        # beyond a seeded prefix boundary stay masked until overwritten.
        assert cache is not None
        C = cache["c"].shape[1]
        qpos = jnp.asarray(positions, jnp.int32)  # (S,) absolute positions
        slots = qpos % C
        new_cache = {
            "c": cache["c"].at[:, slots].set(c.astype(cache["c"].dtype)),
            "kr": cache["kr"].at[:, slots].set(kr.astype(cache["kr"].dtype)),
            "pos": cache["pos"].at[:, slots].set(qpos[None, :]),
        }
        if sh is not None:
            new_cache = {k: sh.c(v, ("act_batch", "act_kv_seq", None)[: v.ndim])
                         for k, v in new_cache.items()}
        mask = ((new_cache["pos"][:, None, :] >= 0)
                & (new_cache["pos"][:, None, :] <= qpos[None, :, None]))
        out = _cache_attention(cfg, p, qn, qr, new_cache["c"],
                               new_cache["kr"], mask, scale, x.dtype)
    else:
        kn, v = _expand(cfg, p, c)
        k_full = jnp.concatenate([kn, jnp.broadcast_to(kr[:, :, None, :], kn.shape[:3] + (dr,))], -1)
        q_full = jnp.concatenate([qn, qr], -1)
        out = chunked_attention(q_full, k_full, v, scale=scale,
                                q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
        if mode == "prefill":
            assert cache is not None
            C = cache["c"].shape[1]
            take = min(S, C)
            pos = jnp.arange(S - take, S, dtype=jnp.int32)
            new_cache = {
                "c": cache["c"].at[:, pos % C].set(
                    jax.lax.slice_in_dim(c, S - take, S, axis=1).astype(cache["c"].dtype)),
                "kr": cache["kr"].at[:, pos % C].set(
                    jax.lax.slice_in_dim(kr, S - take, S, axis=1).astype(cache["kr"].dtype)),
                "pos": cache["pos"].at[:, pos % C].set(pos[None, :]),
            }
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return out, new_cache

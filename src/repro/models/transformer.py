"""Model assembly: embeddings -> scanned layer groups -> head.

Layers are grouped into runs of identical (or 2-alternating) LayerSpecs
(`ModelConfig.scan_groups`) and executed under `lax.scan` with stacked
parameters — this keeps HLO size and compile time bounded for 80-layer
models and is what makes the 512-device dry-run tractable.

Entry points:
- ``loss_and_metrics`` — training forward (+ seq-chunked CE so the
  (B, S, vocab) logits tensor never materializes);
- ``prefill``          — the *maximal first chunk* of the one unpadded
  serving path (DESIGN.md §5): embeds the meta/frontend prefix + prompt
  tokens at absolute positions 0..S-1 into fresh per-group caches
  (ring-buffered to the window for local-attention layers);
- ``extend``           — every later chunk: new tokens at absolute
  positions against the carried caches;
- ``decode``           — one-token step against the caches.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig, ScanGroup
from repro.models import param as prm
from repro.models.attention import (abstract_cache, attention_sublayer, attn_defs,
                                    cache_len_for, init_cache)
from repro.models.hybrid import hybrid_defs, hybrid_sublayer
from repro.models.layers import embed, embed_defs, lm_logits, mlp, mlp_defs, rmsnorm, rmsnorm_def
from repro.models.mla import mla_cache_init, mla_defs, mla_sublayer
from repro.models.moe import moe_defs, moe_sublayer
from repro.models.param import ParamDef
from repro.models.ssm import (ssm_cache_init, ssm_defs, ssm_paged_init,
                              ssm_sublayer)

# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


def block_defs(cfg: ModelConfig, spec: LayerSpec) -> dict:
    defs: dict = {"norm1": rmsnorm_def(cfg.d_model)}
    if spec.kind == "attn":
        defs["mixer"] = attn_defs(cfg)
    elif spec.kind == "mla":
        defs["mixer"] = mla_defs(cfg)
    elif spec.kind == "ssm":
        defs["mixer"] = ssm_defs(cfg)
    elif spec.kind == "hybrid":
        defs["mixer"] = hybrid_defs(cfg)
    if cfg.post_norms:
        defs["post_norm1"] = rmsnorm_def(cfg.d_model)
    if spec.mlp != "none":
        defs["norm2"] = rmsnorm_def(cfg.d_model)
        defs["mlp"] = moe_defs(cfg) if spec.mlp == "moe" else mlp_defs(cfg, cfg.d_ff)
        if cfg.post_norms:
            defs["post_norm2"] = rmsnorm_def(cfg.d_model)
    return defs


def model_defs(cfg: ModelConfig) -> dict:
    groups = []
    for g in cfg.scan_groups():
        unit_defs = tuple(prm.stack_defs(block_defs(cfg, spec), g.repeats) for spec in g.unit)
        groups.append(unit_defs)
    defs = {
        "embed": embed_defs(cfg),
        "groups": tuple(groups),
        "final_norm": rmsnorm_def(cfg.d_model),
    }
    if cfg.n_meta_tokens:
        defs["meta_tokens"] = ParamDef((cfg.n_meta_tokens, cfg.d_model),
                                       (None, "embed"), std=0.02)
    return defs


def init_params(cfg: ModelConfig, key: jax.Array):
    return prm.materialize(model_defs(cfg), key, cfg.param_dtype)


def abstract_params(cfg: ModelConfig, shardings=None):
    return prm.abstract(model_defs(cfg), cfg.param_dtype, shardings)


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------


def apply_block(cfg: ModelConfig, spec: LayerSpec, p: dict, x, *, positions,
                sh=None, cache=None, mode="train", cur_pos=None,
                decode_active=None, page_table=None, page_tokens=None,
                layer=None, attn_kernel=False):
    """Pre-norm residual block. Returns (x, new_cache, aux). With
    ``page_table`` every mixer family computes in place on pooled pages
    (DESIGN.md §10): KV pages for attention/MLA, conv+SSD state pages for
    SSM, both for the hybrid union (``page_tokens`` is the static page
    size the point stacks need to resolve state-page slots; ``layer``
    indexes a pool stacked over the group's layers; ``attn_kernel``
    attends KV pages through the ragged kernel)."""
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("norm"):
        h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    with jax.named_scope(_MIXER_SCOPE.get(spec.kind, "mixer")):
        h, new_cache = _mixer(cfg, spec, p, h, positions=positions, sh=sh,
                              cache=cache, mode=mode, cur_pos=cur_pos,
                              decode_active=decode_active,
                              page_table=page_table, page_tokens=page_tokens,
                              layer=layer, attn_kernel=attn_kernel)
    if cfg.post_norms:
        with jax.named_scope("norm"):
            h = rmsnorm(h, p["post_norm1"], cfg.norm_eps)
    x = x + h
    if sh is not None:
        x = sh.c(x, ("act_batch", "act_seq_res", "act_embed"))

    if spec.mlp != "none":
        with jax.named_scope("norm"):
            h = rmsnorm(x, p["norm2"], cfg.norm_eps)
        with jax.named_scope("mlp"):
            if spec.mlp == "moe":
                h, aux = moe_sublayer(cfg, p["mlp"], h, sh=sh)
            else:
                h = mlp(cfg, p["mlp"], h,
                        constrain=(sh.c if sh is not None else None))
        if cfg.post_norms:
            with jax.named_scope("norm"):
                h = rmsnorm(h, p["post_norm2"], cfg.norm_eps)
        x = x + h
        if sh is not None:
            x = sh.c(x, ("act_batch", "act_seq_res", "act_embed"))
    return x, new_cache, aux


# op-metadata scope of each mixer family (jax.named_scope)
_MIXER_SCOPE = {"attn": "attention", "mla": "attention", "ssm": "ssm",
                "hybrid": "hybrid"}


def _mixer(cfg: ModelConfig, spec: LayerSpec, p: dict, h, *, positions,
           sh, cache, mode, cur_pos, decode_active, page_table, page_tokens,
           layer, attn_kernel):
    """The block's token mixer. Returns (h, new_cache)."""
    if spec.kind == "attn":
        h, new_cache = attention_sublayer(cfg, p["mixer"], h, positions=positions,
                                          window=spec.window, sh=sh, cache=cache,
                                          mode=mode, cur_pos=cur_pos,
                                          decode_active=decode_active,
                                          page_table=page_table, layer=layer,
                                          attn_kernel=attn_kernel)
    elif spec.kind == "mla":
        h, new_cache = mla_sublayer(cfg, p["mixer"], h, positions=positions, sh=sh,
                                    cache=cache, mode=mode, cur_pos=cur_pos,
                                    decode_active=decode_active,
                                    page_table=page_table, layer=layer)
    elif spec.kind == "ssm":
        h, new_cache = ssm_sublayer(cfg, p["mixer"], h, sh=sh, cache=cache,
                                    mode=mode, decode_active=decode_active,
                                    positions=positions, cur_pos=cur_pos,
                                    page_table=page_table,
                                    page_tokens=page_tokens, layer=layer)
    elif spec.kind == "hybrid":
        h, new_cache = hybrid_sublayer(cfg, p["mixer"], h, positions=positions,
                                       window=spec.window, sh=sh, cache=cache,
                                       mode=mode, cur_pos=cur_pos,
                                       decode_active=decode_active,
                                       page_table=page_table,
                                       page_tokens=page_tokens, layer=layer,
                                       attn_kernel=attn_kernel)
    else:
        raise ValueError(spec.kind)
    return h, new_cache


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------


def _unit_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, max_len: int,
                dtype, abstract: bool):
    if spec.kind == "attn":
        clen = cache_len_for(spec.window, max_len)
        return (abstract_cache if abstract else init_cache)(cfg, batch, clen, dtype)
    if spec.kind == "mla":
        return mla_cache_init(cfg, batch, max_len, dtype, abstract=abstract)
    if spec.kind == "ssm":
        return ssm_cache_init(cfg, batch, dtype, abstract=abstract)
    if spec.kind == "hybrid":
        clen = cache_len_for(spec.window, max_len)
        return {
            "attn": (abstract_cache if abstract else init_cache)(cfg, batch, clen, dtype),
            "ssm": ssm_cache_init(cfg, batch, dtype, abstract=abstract),
        }
    raise ValueError(spec.kind)


def init_caches(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
                abstract: bool = False):
    """Per-group tuple of per-unit-position caches stacked over repeats."""
    def stack(tree, r):
        if abstract:
            return jax.tree.map(lambda s: jax.ShapeDtypeStruct((r,) + s.shape, s.dtype), tree)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (r,) + a.shape).copy()
                            if hasattr(a, "shape") else a, tree)

    groups = []
    for g in cfg.scan_groups():
        groups.append(tuple(
            stack(_unit_cache(cfg, spec, batch, max_len, dtype, abstract), g.repeats)
            for spec in g.unit))
    return tuple(groups)


def _paged_unit_cache(cfg: ModelConfig, spec: LayerSpec, n_pages: int,
                      page_tokens: int, dtype):
    """One unit's paged-plane pool (DESIGN.md §10). Page id 0 is the
    reserved null page. Attention pages hold fused head-interleaved KV;
    MLA pages hold one fused latent head: K' = [c, kr], V' = [c, 0];
    SSM pages hold the conv left-context + SSD recurrent state after the
    last written token of the page (point-state pages); hybrid pages are
    the union of the attention and SSM pools under one table."""
    if spec.kind == "attn":
        shape = (n_pages, page_tokens, 2 * cfg.n_kv_heads,
                 cfg.resolved_head_dim)
        return {"kv_pages": jnp.zeros(shape, dtype)}
    if spec.kind == "mla":
        shape = (n_pages, page_tokens, 2, cfg.kv_lora_rank + cfg.qk_rope_dim)
        return {"kv_pages": jnp.zeros(shape, dtype)}
    if spec.kind == "ssm":
        return ssm_paged_init(cfg, n_pages, dtype)
    if spec.kind == "hybrid":
        shape = (n_pages, page_tokens, 2 * cfg.n_kv_heads,
                 cfg.resolved_head_dim)
        return {"attn": {"kv_pages": jnp.zeros(shape, dtype)},
                "ssm": ssm_paged_init(cfg, n_pages, dtype)}
    raise ValueError(spec.kind)


def init_paged_caches(cfg: ModelConfig, n_pages: int, page_tokens: int,
                      dtype=jnp.bfloat16):
    """Per-group tuple of per-unit page pools stacked over repeats —
    shaped like ``init_caches`` output so the scan machinery is shared,
    but sized by pool pages instead of (batch, ring)."""
    groups = []
    for g in cfg.scan_groups():
        groups.append(tuple(
            jax.tree.map(lambda a: jnp.broadcast_to(
                a, (g.repeats,) + a.shape).copy(),
                _paged_unit_cache(cfg, spec, n_pages, page_tokens, dtype))
            for spec in g.unit))
    return tuple(groups)


# ---------------------------------------------------------------------------
# Trunk
# ---------------------------------------------------------------------------


def _embed_inputs(cfg: ModelConfig, params, batch: dict, sh=None):
    """tokens (+ frontend embeds + meta tokens) -> x (B, S_total, d), and the
    index of the first 'real' output position (for loss slicing)."""
    x = embed(cfg, params["embed"], batch["tokens"])
    prefix = 0
    if cfg.frontend == "vision" and "image_embeds" in batch:
        img = batch["image_embeds"].astype(x.dtype)
        x = jnp.concatenate([img, x], axis=1)
        prefix += img.shape[1]
    if cfg.n_meta_tokens:
        B = x.shape[0]
        meta = jnp.broadcast_to(params["meta_tokens"][None].astype(x.dtype),
                                (B, cfg.n_meta_tokens, cfg.d_model))
        x = jnp.concatenate([meta, x], axis=1)
        prefix += cfg.n_meta_tokens
    if sh is not None:
        x = sh.c(x, ("act_batch", "act_seq_res", "act_embed"))
    return x, prefix


def apply_groups(cfg: ModelConfig, params, x, *, positions, sh=None,
                 caches=None, mode="train", cur_pos=None, decode_active=None,
                 page_table=None, page_tokens=None, attn_kernel=False):
    """Run every scan group. Returns (x, new_caches, aux_total).

    With ``page_table`` (the paged plane) each unit's pool, stacked over
    the group's layers, rides in the scan's carry and every layer updates
    it in place at its own index: no layer's pool is sliced out as a
    scanned input or stacked back as an output (DESIGN.md §10)."""
    groups = cfg.scan_groups()
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = []
    for gi, g in enumerate(groups):
        gp = params["groups"][gi]
        gc = caches[gi] if caches is not None else None

        if page_table is not None:
            def paged_body(carry, xs, _g=g):
                xx, aux, pools = carry
                params_t, layer = xs
                outs = []
                for u, spec in enumerate(_g.unit):
                    xx, pool, aux_u = apply_block(
                        cfg, spec, params_t[u], xx, positions=positions,
                        sh=sh, cache=pools[u], mode=mode, cur_pos=cur_pos,
                        decode_active=decode_active, page_table=page_table,
                        page_tokens=page_tokens, layer=layer,
                        attn_kernel=attn_kernel)
                    outs.append(pool)
                    aux = aux + aux_u
                return (xx, aux, tuple(outs)), None

            (x, aux_total, gc), _ = jax.lax.scan(
                paged_body, (x, aux_total, gc),
                (gp, jnp.arange(g.repeats, dtype=jnp.int32)))
            new_caches.append(gc)
            continue

        def body(carry, xs, _g=g):
            xx, aux = carry
            if caches is not None:
                params_t, caches_t = xs
            else:
                params_t, caches_t = xs, tuple(None for _ in _g.unit)
            outs = []
            for u, spec in enumerate(_g.unit):
                xx, c_new, aux_u = apply_block(
                    cfg, spec, params_t[u], xx, positions=positions, sh=sh,
                    cache=caches_t[u], mode=mode, cur_pos=cur_pos,
                    decode_active=decode_active, page_table=page_table,
                    page_tokens=page_tokens)
                outs.append(c_new)
                aux = aux + aux_u
            return (xx, aux), (tuple(outs) if caches is not None else None)

        if mode == "train" and cfg.remat != "none":
            if cfg.remat == "dots":
                body = jax.checkpoint(body, policy=jax.checkpoint_policies.checkpoint_dots)
            else:
                body = jax.checkpoint(body)

        xs = (gp, gc) if caches is not None else gp
        (x, aux_total), ys = jax.lax.scan(body, (x, aux_total), xs)
        if caches is not None:
            new_caches.append(ys)
    return x, (tuple(new_caches) if caches is not None else None), aux_total


# ---------------------------------------------------------------------------
# Training loss
# ---------------------------------------------------------------------------


def loss_and_metrics(cfg: ModelConfig, params, batch: dict, sh=None,
                     loss_chunk: int = 1024) -> Tuple[jax.Array, dict]:
    """Causal-LM loss. batch: tokens (B,S[,K]) int32, labels (B,S[,K]) int32
    with -100 = masked. Frontend/meta prefix positions never contribute."""
    x, prefix = _embed_inputs(cfg, params, batch, sh)
    B, S_tot = x.shape[0], x.shape[1]
    positions = jnp.arange(S_tot)
    x, _, aux = apply_groups(cfg, params, x, positions=positions, sh=sh, mode="train")
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if prefix:
        x = x[:, prefix:]
    labels = batch["labels"]

    S = x.shape[1]
    chunk = min(loss_chunk, S)
    while S % chunk:
        chunk //= 2
    n = S // chunk

    def ce_chunk(carry, idx):
        tot, cnt, zsum = carry
        xs = jax.lax.dynamic_slice_in_dim(x, idx * chunk, chunk, axis=1)
        ls = jax.lax.dynamic_slice_in_dim(labels, idx * chunk, chunk, axis=1)
        logits = lm_logits(cfg, params["embed"], xs).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(ls, 0)[..., None].astype(jnp.int32), axis=-1)[..., 0]
        mask = (ls >= 0).astype(jnp.float32)
        nll = (lse - gold) * mask
        tot = tot + nll.sum()
        cnt = cnt + mask.sum()
        zsum = zsum + (jnp.square(lse) * mask).sum()
        return (tot, cnt, zsum), None

    (tot, cnt, zsum), _ = jax.lax.scan(
        jax.checkpoint(ce_chunk), (jnp.zeros((), jnp.float32),) * 3, jnp.arange(n))
    cnt = jnp.maximum(cnt, 1.0)
    ce = tot / cnt
    z_loss = 1e-4 * zsum / cnt
    loss = ce + z_loss + aux
    return loss, {"ce": ce, "z_loss": z_loss, "aux": aux, "tokens": cnt}


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------


def prefill(cfg: ModelConfig, params, batch: dict, sh=None,
            max_cache_len: Optional[int] = None):
    """The maximal *first chunk* of the one unpadded prompt path
    (DESIGN.md §5): tokens are never padded — token ``i`` (after the
    meta/frontend prefix) sits at absolute position ``prefix + i``, so
    causal masking is exact and the produced caches are position-aligned
    with every later ``extend`` chunk. Returns (last_logits (B, V[, K]),
    caches); the caches cover the chunk (+ meta/frontend prefix),
    ring-truncated to each layer's window."""
    x, prefix = _embed_inputs(cfg, params, batch, sh)
    B, S_tot = x.shape[0], x.shape[1]
    positions = jnp.arange(S_tot)
    max_len = max_cache_len or S_tot

    # build zero caches, run in prefill mode (blocks fill them)
    caches = init_caches(cfg, B, max_len, jnp.dtype(cfg.dtype))
    x, new_caches, _ = apply_groups(cfg, params, x, positions=positions, sh=sh,
                                    caches=caches, mode="prefill")
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    last = x[:, -1]
    logits = lm_logits(cfg, params["embed"], last)
    return logits, new_caches


def decode(cfg: ModelConfig, params, caches, last_tokens, cur_pos, sh=None,
           active=None):
    """One decode step. last_tokens: (B, 1[, K]); cur_pos: scalar absolute
    position (incl. meta/frontend prefix); active: optional (B,) bool — rows
    where False leave their caches untouched (continuous batching with
    chunked prefill in flight). Returns (logits (B, V[, K]), caches)."""
    x = embed(cfg, params["embed"], last_tokens)
    if sh is not None:
        x = sh.c(x, ("act_batch", None, "act_embed"))
    cp = jnp.asarray(cur_pos, jnp.int32)
    positions = cp if cp.ndim == 0 else cp[:, None]  # (B,) -> (B, 1) for rope
    x, new_caches, _ = apply_groups(cfg, params, x, positions=positions, sh=sh,
                                    caches=caches, mode="decode", cur_pos=cp,
                                    decode_active=active)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(cfg, params["embed"], x[:, 0])
    return logits, new_caches


def supports_extend(cfg: ModelConfig) -> bool:
    """Chunked prefill (``extend``) is implemented for every mixer family
    — attention resumes against its position-masked ring cache, MLA
    against the compressed latent cache, and SSM (incl. the hybrid union)
    continues its recurrence from the carried state (DESIGN.md §3, §8).
    Kept as a capability probe for API stability."""
    return all(spec.kind in ("attn", "mla", "ssm", "hybrid")
               for spec in cfg.layer_specs())


def snapshot_kind(cfg: ModelConfig) -> str:
    """How a published prefix compute snapshot of this stack may be
    reused (DESIGN.md §8):

    - ``"positional"`` — the cache is a position-masked ring (attention
      KV, MLA compressed latents): one snapshot serves *any* shorter
      page-aligned match boundary, because entries beyond the boundary
      stay masked (``cache_pos <= cur``) until overwritten.
    - ``"point"`` — the cache integrates the whole prefix (SSM conv
      left-context + SSD state, and therefore the hybrid attention+SSM
      union): a snapshot is valid only at the *exact* token boundary it
      was captured at.
    """
    if any(spec.kind in ("ssm", "hybrid") for spec in cfg.layer_specs()):
        return "point"
    return "positional"


def extend(cfg: ModelConfig, params, caches, tokens, offset, sh=None):
    """Chunked-prefill continuation: process ``tokens`` (B, S[, K]) at
    absolute positions ``offset + [0, S)`` against existing caches (which
    already hold every earlier chunk — ring entries for attention/MLA,
    recurrent state for SSM/hybrid). ``offset`` may be traced, so one
    compiled executable serves every chunk of a given length.
    Returns (last-position logits (B, V[, K]), updated caches)."""
    x = embed(cfg, params["embed"], tokens)
    if sh is not None:
        x = sh.c(x, ("act_batch", "act_seq_res", "act_embed"))
    S = x.shape[1]
    positions = jnp.asarray(offset, jnp.int32) + jnp.arange(S, dtype=jnp.int32)
    x, new_caches, _ = apply_groups(cfg, params, x, positions=positions, sh=sh,
                                    caches=caches, mode="extend")
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(cfg, params["embed"], x[:, -1])
    return logits, new_caches


# ---------------------------------------------------------------------------
# Paged serving steps (DESIGN.md §10): compute in place on the page pool
# ---------------------------------------------------------------------------


def paged_prefill(cfg: ModelConfig, params, batch: dict, caches, page_table,
                  sh=None, page_tokens=None, attn_kernel=False):
    """First chunk on the paged plane: embeds the meta/frontend prefix +
    prompt at absolute positions 0..S-1 and writes KV straight into the
    pool pages named by ``page_table`` (B, W). Unlike ring ``prefill``
    there is no per-slot cache to build — the pool is the cache — so this
    is just ``extend`` from offset 0 with the prefix embedded.
    Returns (last_logits, caches). ``attn_kernel`` (here and in the other
    paged steps) attends KV pages through the ragged kernel."""
    with jax.named_scope("embed"):
        x, _ = _embed_inputs(cfg, params, batch, sh)
    S_tot = x.shape[1]
    positions = jnp.arange(S_tot, dtype=jnp.int32)
    x, new_caches, _ = apply_groups(cfg, params, x, positions=positions,
                                    sh=sh, caches=caches, mode="extend",
                                    page_table=page_table,
                                    page_tokens=page_tokens,
                                    attn_kernel=attn_kernel)
    return _paged_head(cfg, params, x, -1), new_caches


def paged_extend(cfg: ModelConfig, params, caches, tokens, offset, page_table,
                 sh=None, page_tokens=None, attn_kernel=False):
    """Later chunks on the paged plane: ``tokens`` (B, S[, K]) at absolute
    positions ``offset + [0, S)``; earlier context is whatever the pages
    in ``page_table`` hold — including pages spliced in from a radix or
    migrated prefix hit at zero copy cost. Point stacks read their state
    page for the slot preceding ``offset`` (the engine chunks them so a
    chunk never crosses a page boundary)."""
    with jax.named_scope("embed"):
        x = embed(cfg, params["embed"], tokens)
    if sh is not None:
        x = sh.c(x, ("act_batch", "act_seq_res", "act_embed"))
    S = x.shape[1]
    positions = jnp.asarray(offset, jnp.int32) + jnp.arange(S, dtype=jnp.int32)
    x, new_caches, _ = apply_groups(cfg, params, x, positions=positions,
                                    sh=sh, caches=caches, mode="extend",
                                    page_table=page_table,
                                    page_tokens=page_tokens,
                                    attn_kernel=attn_kernel)
    return _paged_head(cfg, params, x, -1), new_caches


def paged_decode(cfg: ModelConfig, params, caches, last_tokens, cur_pos,
                 page_table, sh=None, active=None, page_tokens=None,
                 attn_kernel=False):
    """One batched decode step on the paged plane. cur_pos: (B,) absolute
    positions; rows where ``active`` is False neither write their pages
    nor advance (their page-table row may be all null pages)."""
    with jax.named_scope("embed"):
        x = embed(cfg, params["embed"], last_tokens)
    if sh is not None:
        x = sh.c(x, ("act_batch", None, "act_embed"))
    cp = jnp.asarray(cur_pos, jnp.int32)
    positions = cp if cp.ndim == 0 else cp[:, None]  # (B,) -> (B, 1) for rope
    x, new_caches, _ = apply_groups(cfg, params, x, positions=positions,
                                    sh=sh, caches=caches, mode="decode",
                                    cur_pos=cp, decode_active=active,
                                    page_table=page_table,
                                    page_tokens=page_tokens,
                                    attn_kernel=attn_kernel)
    return _paged_head(cfg, params, x, 0), new_caches


def _paged_head(cfg: ModelConfig, params, x, at: int):
    """Final norm over ``x`` (B, S, d), then the LM head at position
    ``at``, the one each row samples from. Returns logits (B, V[, K])."""
    with jax.named_scope("norm"):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        return lm_logits(cfg, params["embed"], x[:, at])

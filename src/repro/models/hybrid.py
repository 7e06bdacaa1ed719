"""Hymba-style hybrid block: parallel attention + SSM heads [arXiv:2411.13676].

Both branches read the same normed input; their outputs are RMS-normalized,
averaged, then passed through the block's output. Sliding-window attention
everywhere except cfg.global_layers; 128 learnable meta tokens are prepended
by the transformer assembly (they live in the KV cache / SSM state like any
other token). Cross-layer KV sharing is not modelled (DESIGN.md §5).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import attention_sublayer, attn_defs
from repro.models.layers import rmsnorm
from repro.models.param import ParamDef
from repro.models.ssm import ssm_defs, ssm_sublayer


def hybrid_defs(cfg: ModelConfig) -> dict:
    defs = {
        "attn": attn_defs(cfg),
        "ssm": ssm_defs(cfg),
        "attn_out_norm": ParamDef((cfg.d_model,), ("embed",), init="zeros"),
        "ssm_out_norm": ParamDef((cfg.d_model,), ("embed",), init="zeros"),
    }
    return defs


def hybrid_sublayer(
    cfg: ModelConfig,
    p: dict,
    x,
    *,
    positions,
    window: Optional[int],
    sh=None,
    cache: Optional[dict] = None,
    mode: str = "train",
    cur_pos=None,
    decode_active=None,
    page_table=None,
    page_tokens=None,
    layer=None,
    attn_kernel: bool = False,
) -> Tuple[jnp.ndarray, Optional[dict]]:
    """Both branches run in every mode (incl. ``extend``: the attention
    half resumes against its ring cache positionally while the SSM half
    continues its recurrence from the carried state — the union cache is
    what makes hybrid prefix snapshots *point* snapshots, DESIGN.md §8).
    Prompts are always unpadded (DESIGN.md §5): both halves see token i
    at absolute position i, so neither needs pad masking — the SSM half
    could not mask pads at all (they would integrate into the state),
    which is why the padded whole-prompt path had to go. ``decode_active``
    masks both halves' cache writes for inactive rows. With ``page_table``
    the union cache is paged (DESIGN.md §10): KV pages for the attention
    half, conv/state pages for the SSM half, one table for both; ``layer``
    indexes both pools when they are stacked over layers, and
    ``attn_kernel`` sends the attention half through the ragged kernel."""
    attn_cache = cache["attn"] if cache is not None else None
    ssm_cache = cache["ssm"] if cache is not None else None
    a_out, a_cache = attention_sublayer(
        cfg, p["attn"], x, positions=positions, window=window, sh=sh,
        cache=attn_cache, mode=mode, cur_pos=cur_pos,
        decode_active=decode_active, page_table=page_table, layer=layer,
        attn_kernel=attn_kernel)
    s_out, s_cache = ssm_sublayer(cfg, p["ssm"], x, sh=sh, cache=ssm_cache,
                                  mode=mode, decode_active=decode_active,
                                  positions=positions, cur_pos=cur_pos,
                                  page_table=page_table,
                                  page_tokens=page_tokens, layer=layer)
    out = 0.5 * (rmsnorm(a_out, p["attn_out_norm"], cfg.norm_eps)
                 + rmsnorm(s_out, p["ssm_out_norm"], cfg.norm_eps))
    new_cache = None
    if cache is not None:
        new_cache = {"attn": a_cache, "ssm": s_cache}
    return out, new_cache

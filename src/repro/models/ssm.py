"""Mamba2 SSD (state-space duality) block [arXiv:2405.21060].

Training/prefill uses the chunked SSD algorithm: within-chunk "attention"
with cumulative decay masks + an inter-chunk state recurrence carried by
lax.scan (so the materialized decay mask is (B, H, chunk, chunk), never
(B, H, S, S)). Decode is the O(1) per-token recurrence over the
(H, headdim, state) SSM state — the arch that makes `long_500k` trivial.

The Pallas twin of the chunk computation lives in
`repro.kernels.ssd_scan`; this pure-XLA path is the dry-run/oracle path.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.param import ParamDef


def ssm_defs(cfg: ModelConfig) -> dict:
    d, di = cfg.d_model, cfg.d_inner
    ng, ns, nh, cw = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_conv
    conv_dim = di + 2 * ng * ns
    return {
        "in_proj": ParamDef((d, 2 * di + 2 * ng * ns + nh), ("embed", "ssm_inner")),
        "conv_w": ParamDef((cw, conv_dim), ("conv", "ssm_inner")),
        "conv_b": ParamDef((conv_dim,), ("ssm_inner",), init="zeros"),
        "a_log": ParamDef((nh,), ("ssm_heads",), init="a_log", dtype="float32"),
        "d_skip": ParamDef((nh,), ("ssm_heads",), init="ones", dtype="float32"),
        "dt_bias": ParamDef((nh,), ("ssm_heads",), init="dt_bias", dtype="float32"),
        "norm_g": ParamDef((di,), ("ssm_inner",), init="zeros"),
        "out_proj": ParamDef((di, d), ("ssm_inner", "embed")),
    }


def _split_proj(cfg: ModelConfig, zxbcdt):
    di, ng, ns, nh = cfg.d_inner, cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_nheads
    z = zxbcdt[..., :di]
    x = zxbcdt[..., di:2 * di]
    b = zxbcdt[..., 2 * di:2 * di + ng * ns]
    c = zxbcdt[..., 2 * di + ng * ns:2 * di + 2 * ng * ns]
    dt = zxbcdt[..., 2 * di + 2 * ng * ns:]
    return z, x, b, c, dt


def _causal_conv(xbc, w, b, state=None):
    """Depthwise causal conv via shifted adds. xbc: (B, S, C); w: (W, C).
    state: (B, W-1, C) left context for decode/streaming; returns (y, new_state)."""
    W = w.shape[0]
    Bsz, S, C = xbc.shape
    if state is None:
        state = jnp.zeros((Bsz, W - 1, C), xbc.dtype)
    ext = jnp.concatenate([state, xbc], axis=1)  # (B, S+W-1, C)
    y = jnp.zeros((Bsz, S, C), jnp.float32)
    for i in range(W):
        y = y + ext[:, i:i + S, :].astype(jnp.float32) * w[i].astype(jnp.float32)
    y = y + b.astype(jnp.float32)
    new_state = ext[:, S:, :] if S >= W - 1 else ext[:, -(W - 1):, :]
    return jax.nn.silu(y).astype(xbc.dtype), new_state


def _segsum(a):
    """log-space segment sums: a (..., L) -> (..., L, L) lower-triangular.
    S(i,j) = sum_{t=j+1..i} a_t = cs_i - cs_j for i >= j, else -inf."""
    L = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    s = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool), 0)
    return jnp.where(mask, s, -jnp.inf)


def ssd_chunked(x, dt, a, b, c, chunk: int, init_state=None):
    """SSD over a full sequence, optionally continuing from a carried state.

    x: (B, S, H, P); dt: (B, S, H) (post-softplus); a: (H,) negative;
    b, c: (B, S, G, N) with H % G == 0; ``init_state`` (B, H, P, N) is the
    recurrent state after every earlier token (zeros when starting from
    scratch) — this is what makes chunked prefill / prefix-snapshot
    resumption possible for SSM stacks (DESIGN.md §8: the state is a
    *point* snapshot, only valid at the exact boundary it was taken at).
    Returns (y (B,S,H,P), final state (B, H, P, N)).
    """
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    L = min(chunk, S)
    while S % L:
        L //= 2
    nc = S // L

    xr = x.reshape(B, nc, L, H, P)
    dtr = dt.reshape(B, nc, L, H)
    br = b.reshape(B, nc, L, G, N)
    cr = c.reshape(B, nc, L, G, N)
    # broadcast groups to heads
    bh = jnp.repeat(br, rep, axis=3)  # (B, nc, L, H, N)
    ch = jnp.repeat(cr, rep, axis=3)

    da = dtr * a[None, None, None, :]           # (B, nc, L, H) log-decay
    da_cs = jnp.cumsum(da, axis=2)              # cumulative within chunk
    seg = _segsum(da.transpose(0, 1, 3, 2))     # (B, nc, H, L, L)
    decay_mask = jnp.exp(seg)

    x_dt = xr * dtr[..., None]

    def chunk_step(state, xs):
        # state: (B, H, P, N)
        xc, bc, cc, dmask, dacs = xs  # per-chunk slices; xc is x*dt
        # intra-chunk (the "attention" form)
        cb = jnp.einsum("blhn,bshn->bhls", cc, bc, preferred_element_type=jnp.float32)
        y_in = jnp.einsum("bhls,bshp->blhp", cb * dmask, xc,
                          preferred_element_type=jnp.float32)
        # contribution from carried-in state
        state_decay = jnp.exp(dacs)  # (B, L, H)
        y_off = jnp.einsum("blhn,bhpn,blh->blhp", cc, state, state_decay,
                           preferred_element_type=jnp.float32)
        # update state: state' = decay_total * state + sum_s decay_tail_s * B_s x_s
        tail = jnp.exp(dacs[:, -1:, :] - dacs)  # (B, L, H)
        new_state = jnp.einsum("bshn,bshp,bsh->bhpn", bc, xc, tail,
                               preferred_element_type=jnp.float32)
        total = jnp.exp(dacs[:, -1, :])  # (B, H)
        state = state * total[..., None, None] + new_state
        return state, (y_in + y_off)

    xs = (
        x_dt.transpose(1, 0, 2, 3, 4),
        bh.transpose(1, 0, 2, 3, 4),
        ch.transpose(1, 0, 2, 3, 4),
        decay_mask.transpose(1, 0, 2, 3, 4),
        da_cs.transpose(1, 0, 2, 3),
    )
    if init_state is None:
        state0 = jnp.zeros((B, H, P, N), jnp.float32)
    else:
        state0 = init_state.astype(jnp.float32)
    state, ys = jax.lax.scan(jax.checkpoint(chunk_step), state0, xs)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, S, H, P)
    return y.astype(x.dtype), state


def ssd_decode(x1, dt1, a, b1, c1, state):
    """One-token recurrence. x1: (B, H, P); dt1: (B, H); b1/c1: (B, G, N);
    state: (B, H, P, N) -> (y (B, H, P), new state)."""
    B, H, P = x1.shape
    G, N = b1.shape[1], b1.shape[2]
    rep = H // G
    bh = jnp.repeat(b1, rep, axis=1)  # (B, H, N)
    ch = jnp.repeat(c1, rep, axis=1)
    decay = jnp.exp(dt1 * a[None, :])  # (B, H)
    state = state * decay[..., None, None] + jnp.einsum(
        "bhn,bhp,bh->bhpn", bh, x1.astype(jnp.float32), dt1, preferred_element_type=jnp.float32)
    y = jnp.einsum("bhpn,bhn->bhp", state, ch, preferred_element_type=jnp.float32)
    return y.astype(x1.dtype), state


def ssm_cache_init(cfg: ModelConfig, batch: int, dtype, abstract=False) -> dict:
    di, ng, ns = cfg.d_inner, cfg.ssm_ngroups, cfg.ssm_state
    nh, hd, cw = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_conv
    conv_dim = di + 2 * ng * ns
    shapes = {
        "conv": ((batch, cw - 1, conv_dim), dtype),
        "state": ((batch, nh, hd, ns), jnp.float32),
    }
    if abstract:
        return {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in shapes.items()}
    return {k: jnp.zeros(s, d) for k, (s, d) in shapes.items()}


def ssm_paged_init(cfg: ModelConfig, n_pages: int, dtype) -> dict:
    """Pooled *state pages* for the paged compute plane (DESIGN.md §10):
    slot j of a session's page table maps to the page holding the conv
    left-context and SSD recurrent state *after the last written token of
    page j* (a sealed page holds the exact page-boundary state; the open
    page holds the running state). Page 0 is the reserved null page — all
    zeros, never written — so an empty table entry reads as the
    empty-history init state, exactly like a fresh ring cache."""
    di, ng, ns = cfg.d_inner, cfg.ssm_ngroups, cfg.ssm_state
    nh, hd, cw = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_conv
    conv_dim = di + 2 * ng * ns
    return {
        "conv_pages": jnp.zeros((n_pages, cw - 1, conv_dim), dtype),
        "state_pages": jnp.zeros((n_pages, nh, hd, ns), jnp.float32),
    }


def _paged_state_slots(page_table, q0, page_tokens: int):
    """Resolve the state-page slots for a chunk/step whose first query sits
    at absolute position ``q0`` ((B,) or scalar). Reads the state after
    token ``q0 - 1``: slot ``j0 - 1`` when q0 opens page ``j0 = q0 // pt``
    (the previous page's sealed boundary state), else slot ``j0`` (the open
    page's running state); an empty history maps to null page 0, whose
    zeros ARE the zero init state. Writes always land in slot ``j0`` —
    ``ok`` masks rows whose table entry is null (inactive decode rows carry
    all-null tables; writing page 0 would corrupt the null page)."""
    B, W = page_table.shape
    q0 = jnp.broadcast_to(jnp.asarray(q0, jnp.int32), (B,))
    j0 = q0 // page_tokens
    rs = jnp.where(q0 % page_tokens == 0, j0 - 1, j0)
    rd = jnp.take_along_axis(page_table, jnp.clip(rs, 0, W - 1)[:, None],
                             axis=1)[:, 0]
    pid_read = jnp.where(rs >= 0, rd, 0)
    pid_write = jnp.take_along_axis(page_table, jnp.clip(j0, 0, W - 1)[:, None],
                                    axis=1)[:, 0]
    ok = (j0 < W) & (pid_write != 0)
    return pid_read, pid_write, ok


def ssm_sublayer(
    cfg: ModelConfig,
    p: dict,
    x,
    *,
    sh=None,
    cache: Optional[dict] = None,
    mode: str = "train",
    decode_active=None,
    positions=None,
    cur_pos=None,
    page_table=None,
    page_tokens: Optional[int] = None,
    layer=None,
) -> Tuple[jax.Array, Optional[dict]]:
    """x: (B, S, d_model) -> (out, updated cache or None).

    Modes: ``train`` (no cache), ``prefill``/``extend`` (one code path on
    the unpadded prompt layout, DESIGN.md §5: the cache carries the conv
    left-context and SSD state after every earlier chunk, and a *fresh*
    zero cache IS the empty-history state — a maximal first chunk and a
    mid-prompt continuation are the same recurrence), ``decode`` (O(1)
    per-token step). There is no pad handling anywhere: a padded prompt
    would integrate the pad tokens into the state, which is exactly the
    masking caveat the single-path refactor deleted.
    ``decode_active`` ((B,) bool, decode only): rows where False keep
    their cache untouched — a batched decode round must not clobber the
    recurrent state of a slot whose prompt is still streaming in.

    Paged mode (cache holds ``state_pages``, DESIGN.md §10): the conv
    left-context and SSD state live in pooled pages indexed through
    ``page_table``; the read slot is resolved from the first query
    position (``positions[0]`` for prefill/extend — the engine chunks
    point stacks so every chunk lies within exactly one page — and
    ``cur_pos`` per row for decode), and the updated state is scattered
    back to the page owning that position. Null page 0's zeros are the
    empty-history init, so a cold start and a chunk resumed at a page
    boundary run the identical recurrence. With ``layer`` the pages are
    stacked over layers and read and written on (layer, page)."""
    from repro.models.layers import rmsnorm  # avoid cycle

    B, S, d = x.shape
    di, nh, hd = cfg.d_inner, cfg.ssm_nheads, cfg.ssm_headdim
    ng, ns = cfg.ssm_ngroups, cfg.ssm_state
    paged = cache is not None and "state_pages" in cache
    if paged:
        q0 = cur_pos if mode == "decode" else positions[0]
        pid_read, pid_write, ok = _paged_state_slots(page_table, q0,
                                                     page_tokens)
        lead = () if layer is None else (layer,)
        at_read = lead + (pid_read,)
    zxbcdt = x @ p["in_proj"]
    z, xi, b, c, dt = _split_proj(cfg, zxbcdt)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])

    xbc = jnp.concatenate([xi, b, c], axis=-1)
    if paged:
        conv_state = cache["conv_pages"][at_read]
    else:
        conv_state = cache["conv"] if cache is not None else None
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_state)
    xi, b, c = xbc[..., :di], xbc[..., di:di + ng * ns], xbc[..., di + ng * ns:]
    xh = xi.reshape(B, S, nh, hd)
    if sh is not None:
        xh = sh.c(xh, ("act_batch", None, "act_heads", None))
    bg = b.reshape(B, S, ng, ns)
    cg = c.reshape(B, S, ng, ns)

    new_cache = None
    if mode == "decode":
        assert cache is not None
        state = cache["state_pages"][at_read] if paged else cache["state"]
        y1, new_state = ssd_decode(xh[:, 0], dt[:, 0], a, bg[:, 0], cg[:, 0], state)
        y = y1[:, None]
        if decode_active is not None:
            act = jnp.asarray(decode_active, bool)
            if paged:
                ok = ok & act
            else:
                new_state = jnp.where(act[:, None, None, None], new_state, cache["state"])
                new_conv = jnp.where(act[:, None, None], new_conv, cache["conv"])
    else:
        # prefill starts from the zero-initialized cache state; extend
        # continues the recurrence from the carried state (same code path —
        # a fresh cache IS the zero state, and null page 0 IS the zero state
        # in paged mode)
        if paged:
            init = cache["state_pages"][at_read]
        else:
            init = cache["state"] if cache is not None else None
        y, final_state = ssd_chunked(xh, dt, a, bg, cg, cfg.ssm_chunk,
                                     init_state=init)
        new_state = final_state
    y = y + xh.astype(jnp.float32).astype(y.dtype) * p["d_skip"].astype(y.dtype)[None, None, :, None]
    y = y.reshape(B, S, di)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype), p["norm_g"], cfg.norm_eps)
    out = y @ p["out_proj"]
    if cache is not None:
        if paged:
            # out-of-range / null rows drop (page 0 is never written)
            P = cache["state_pages"].shape[len(lead)]
            at = lead + (jnp.where(ok, pid_write, P),)
            new_cache = {
                "conv_pages": cache["conv_pages"].at[at].set(
                    new_conv.astype(cache["conv_pages"].dtype), mode="drop"),
                "state_pages": cache["state_pages"].at[at].set(
                    new_state, mode="drop"),
            }
        else:
            new_cache = {"conv": new_conv, "state": new_state}
    return out, new_cache

"""Plain reference forward of a dense decoder-only transformer (the
Llama / DeepSeek-LLM / Qwen3 layer), in float32 at the highest matmul
precision, with no cache, paging or batching. It imports nothing of the
system under test and reads only the canonical weights of
``cb.weights`` and the configuration's own keys.

Per layer, for a sequence x of T tokens at positions 0..T-1:

    h = rmsnorm(x) * (1 + g1)
    q, k, v = h Wq, h Wk, h Wv             (heads of head_dim)
    q, k = rmsnorm(q) * (1 + gq), rmsnorm(k) * (1 + gk)   if qk_norm
    q, k = rope(q), rope(k)                (rotate-half, base rope_theta)
    a = softmax(q k^T / sqrt(head_dim) + causal) v   (query head i reads
                                            key/value head i // (Hq/Hkv))
    x = x + a Wo
    h = rmsnorm(x) * (1 + g2)
    x = x + (silu(h Wgate) * h Wup) Wdown

and logits = (rmsnorm(x) * (1 + gf)) Wlm^T.

``control=True`` is the same forward with every matrix stored in fp8
(e4m3, one scale per output column): the precision below the bf16 the
configurations state, used only to show that the comparison rejects it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from cb.weights import dims

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 256         # query rows per attention block (bounds the scores)
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g.astype(jnp.float32))


def _rope(x, pos, theta):
    """x (T, H, D), rotate-half convention."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]       # (T, D/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    half = D // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def fp8_round(w):
    """w in fp8 e4m3 with one scale per output column, back in fp32."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.jit, static_argnames=("m", "eps", "theta", "control"))
def _layer(x, lw, *, m, eps, theta, control):
    m = dict(m)
    T = x.shape[0]
    hq, hkv, hd = m["hq"], m["hkv"], m["hd"]
    w = {k: (fp8_round(v) if control and k in MATRICES
             else v.astype(jnp.float32)) for k, v in lw.items()}
    pos = jnp.arange(T)
    h = _rms(x, w["norm1"], eps)
    q = _mm(h, w["wq"]).reshape(T, hq, hd)
    k = _mm(h, w["wk"]).reshape(T, hkv, hd)
    v = _mm(h, w["wv"]).reshape(T, hkv, hd)
    if m["qk_norm"]:
        q = _rms(q, w["q_norm"], eps)
        k = _rms(k, w["k_norm"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    group = hq // hkv
    k = jnp.repeat(k, group, axis=1)                      # (T, Hq, D)
    v = jnp.repeat(v, group, axis=1)
    outs = []
    for s0 in range(0, T, BLOCK):
        qb = q[s0:s0 + BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * hd ** -0.5
        causal = (pos[None, s0:s0 + BLOCK, None] >= pos[None, None, :])
        sc = jnp.where(causal, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST))
    a = jnp.concatenate(outs, 0).reshape(T, hq * hd)
    x = x + _mm(a, w["wo"])
    h = _rms(x, w["norm2"], eps)
    x = x + _mm(jax.nn.silu(_mm(h, w["w_gate"])) * _mm(h, w["w_up"]),
                w["w_down"])
    return x


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(x, g, table, *, eps, control):
    t = fp8_round(table.T).T if control else table.astype(jnp.float32)
    return _mm(_rms(x, g, eps), t.T)


def logits_at(spec: dict, w: dict, tokens, at, *, control: bool = False):
    """Reference logits (len(at), vocab) at positions ``at`` of the
    sequence ``tokens`` (the logits there predict token at+1). The
    sequence is padded at its end to a multiple of BLOCK: causal masking
    keeps padding out of every earlier position."""
    m = dims(spec)
    T = len(tokens)
    Tp = -(-T // BLOCK) * BLOCK
    ids = np.zeros(Tp, np.int32)
    ids[:T] = tokens
    x = jnp.take(w["embed"], jnp.asarray(ids), axis=0).astype(jnp.float32)
    per_layer = [k for k in w if k not in ("embed", "lm_head", "final_norm")]
    key = tuple(sorted((k, v) for k, v in m.items()))
    eps, theta = float(spec["rms_norm_eps"]), float(spec["rope_theta"])
    for layer in range(m["L"]):
        lw = {k: w[k][layer] for k in per_layer}
        x = _layer(x, lw, m=key, eps=eps, theta=theta, control=control)
    table = w["embed"] if m["tied"] else w["lm_head"]
    sel = x[jnp.asarray(np.asarray(at, np.int32))]
    return np.asarray(_head(sel, w["final_norm"], table, eps=eps,
                            control=control))

"""Seeded weights for a dense decoder, made on the device in one jitted
call, in the type they are served in (bf16).

``canonical`` is the benchmark's own layout: plain matrices with a leading
layer axis, named after what they do. The reference reads it directly;
``to_program`` rearranges the same arrays into the parameter tree of the
system under test. Both sides regenerate from the seed, so the reference
takes nothing the program made.

Norm weights are stored as the deviation g from 1 (the layer multiplies by
1 + g), so both sides apply the same bf16 value.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NORM_STD = 0.1


def dims(spec: dict) -> dict:
    d, hq, hkv = (spec["hidden_size"], spec["num_attention_heads"],
                  spec["num_key_value_heads"])
    return dict(L=spec["num_hidden_layers"], d=d, hq=hq, hkv=hkv,
                hd=spec.get("head_dim") or d // hq,
                ff=spec["intermediate_size"], V=spec["vocab_size"],
                qk_norm=bool(spec.get("qk_norm", False)),
                tied=bool(spec.get("tie_word_embeddings", False)))


def shapes(spec: dict) -> dict:
    """name -> (shape, std) of every canonical tensor, in a fixed order."""
    m = dims(spec)
    L, d, hq, hkv, hd, ff, V = (m[k] for k in
                                ("L", "d", "hq", "hkv", "hd", "ff", "V"))
    out = {"embed": ((V, d), 1.0)}
    if not m["tied"]:
        out["lm_head"] = ((V, d), d ** -0.5)
    out["final_norm"] = ((d,), NORM_STD)
    out["norm1"] = ((L, d), NORM_STD)
    out["norm2"] = ((L, d), NORM_STD)
    out["wq"] = ((L, d, hq * hd), d ** -0.5)
    out["wk"] = ((L, d, hkv * hd), d ** -0.5)
    out["wv"] = ((L, d, hkv * hd), d ** -0.5)
    out["wo"] = ((L, hq * hd, d), (hq * hd) ** -0.5)
    if m["qk_norm"]:
        out["q_norm"] = ((L, hd), NORM_STD)
        out["k_norm"] = ((L, hd), NORM_STD)
    out["w_gate"] = ((L, d, ff), d ** -0.5)
    out["w_up"] = ((L, d, ff), d ** -0.5)
    out["w_down"] = ((L, ff, d), ff ** -0.5)
    return out


def seed_key(seed: int):
    """A key for any whole-number seed (more than 32 bits are kept)."""
    s = int(seed) % 2**64
    return jax.random.fold_in(jax.random.key(s % 2**32, impl="rbg"),
                              s // 2**32)


def canonical(spec: dict, key) -> dict:
    """Traceable: name -> bf16 array, each drawn in fp32 from its own
    fold of ``key`` and scaled by its std."""
    out = {}
    for i, (name, (shape, std)) in enumerate(shapes(spec).items()):
        k = jax.random.fold_in(key, i)
        out[name] = (jax.random.normal(k, shape, jnp.float32)
                     * std).astype(jnp.bfloat16)
    return out


def to_program(cfg, spec: dict, w: dict) -> dict:
    """Traceable: the canonical arrays as the parameter tree of
    ``repro.models.transformer`` (one scan group of identical attention
    layers; vocabulary rows padded with zeros to ``cfg.padded_vocab``)."""
    m = dims(spec)
    L, d, hq, hkv, hd = (m[k] for k in ("L", "d", "hq", "hkv", "hd"))
    pad = cfg.padded_vocab - m["V"]

    def rows(t):
        return jnp.pad(t, ((0, pad), (0, 0))) if pad else t

    mixer = {"wq": w["wq"].reshape(L, d, hq, hd),
             "wk": w["wk"].reshape(L, d, hkv, hd),
             "wv": w["wv"].reshape(L, d, hkv, hd),
             "wo": w["wo"].reshape(L, hq, hd, d)}
    if m["qk_norm"]:
        mixer["q_norm"] = w["q_norm"]
        mixer["k_norm"] = w["k_norm"]
    block = {"norm1": w["norm1"], "mixer": mixer, "norm2": w["norm2"],
             "mlp": {"w_up": w["w_up"], "w_down": w["w_down"],
                     "w_gate": w["w_gate"]}}
    embed = {"embedding": rows(w["embed"])}
    if not m["tied"]:
        embed["lm_head"] = rows(w["lm_head"])
    return {"embed": embed, "groups": ((block,),),
            "final_norm": w["final_norm"]}


def make_canonical(spec: dict, seed: int) -> dict:
    return jax.jit(lambda k: canonical(spec, k))(seed_key(seed))


def make_program_params(cfg, spec: dict, seed: int, template) -> dict:
    """The program's parameters from ``seed``, checked leaf by leaf
    against ``template`` (the program's own abstract parameter tree)."""
    fn = jax.jit(lambda k: to_program(cfg, spec, canonical(spec, k)))
    want = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), template)
    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)),
                       jax.eval_shape(fn, seed_key(seed)))
    if want != got:
        raise ValueError(f"weight layout differs from the program's:\n"
                         f"program {want}\nbenchmark {got}")
    return fn(seed_key(seed))


"""The benchmark's plain fp32 reference against the program's own
whole-prompt forward (``transformer.prefill``) at a small size, for both
families it serves: GQA with qk-norm (qwen3) and MHA without (deepseek).
Both read the same seeded weights; the program runs in fp32 here."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for _p in (BENCH, BENCH.parent / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import dataclasses  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from cb import bench, reference, weights  # noqa: E402

SMALL = dict(hidden_size=128, num_attention_heads=4, head_dim=32,
             intermediate_size=256, vocab_size=512, num_hidden_layers=2)


def small(name, **kw):
    spec = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    spec.update(SMALL, **kw)
    return spec


CASES = {"qwen3-gqa-qknorm": small("qwen3-8b.d16", num_key_value_heads=2),
         "deepseek-mha": small("deepseek-7b.d8", num_key_value_heads=4)}


def program_logits(spec, w, tokens):
    from repro.models import transformer as tfm
    cfg = dataclasses.replace(bench.program_config(spec), dtype="float32",
                              param_dtype="float32")
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          weights.to_program(cfg, spec, w))
    logits, _ = tfm.prefill(cfg, params,
                            {"tokens": jnp.asarray([tokens], jnp.int32)})
    return np.asarray(logits)[0, :spec["vocab_size"]]


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_matches_program_prefill(case):
    spec = CASES[case]
    w = weights.make_canonical(spec, 123)
    tokens = list(np.random.default_rng(0).integers(2, 512, 45))
    want = program_logits(spec, w, tokens)
    with jax.default_matmul_precision("highest"):
        got = reference.logits_at(spec, w, tokens, [len(tokens) - 1])[0]
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale, case


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_sees_the_mechanisms(case):
    """Turning qk-norm over, or pairing query heads with other KV heads,
    moves the reference's logits far beyond the agreement above."""
    spec = CASES[case]
    w = weights.make_canonical(spec, 5)
    tokens = list(np.random.default_rng(1).integers(2, 512, 30))
    base = reference.logits_at(spec, w, tokens, [29])[0]
    scale = np.abs(base).max()
    if spec["qk_norm"]:
        w2 = dict(w, q_norm=w["q_norm"] + 1.0)
        other = reference.logits_at(spec, w2, tokens, [29])[0]
        assert np.abs(other - base).max() > 1e-2 * scale
    else:
        L, d, _ = w["wk"].shape
        hd = spec["head_dim"]
        perm = w["wk"].reshape(L, d, -1, hd)[:, :, ::-1].reshape(L, d, -1)
        other = reference.logits_at(spec, dict(w, wk=perm), tokens, [29])[0]
        assert np.abs(other - base).max() > 1e-2 * scale


def test_fp8_control_is_further_from_the_reference_than_bf16_rounding():
    spec = CASES["qwen3-gqa-qknorm"]
    w = weights.make_canonical(spec, 11)
    tokens = list(np.random.default_rng(2).integers(2, 512, 64))
    at = list(range(32, 64))
    ref = reference.logits_at(spec, w, tokens, at)
    ctl = reference.logits_at(spec, w, tokens, at, control=True)
    assert np.abs(ctl - ref).max() > 1e-2 * np.abs(ref).max()

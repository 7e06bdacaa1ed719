"""Prefix compute reuse across mixer families (DESIGN.md §8).

The radix tree shares KV *pages* identically for every architecture; these
tests pin down the harder guarantee — that a prefix hit skips prefill
*compute* — for each resumption kind:

- attention (deepseek-7b) and MLA (deepseek-v2-lite-16b): *positional* —
  the shared KV/latent pages are position-addressed, so a borrower resumes
  at any boundary of them;
- SSM (mamba2-2.7b) and hybrid (hymba-1.5b): *point* — the recurrent
  state integrates the whole prefix, so a borrower resumes only at a page
  boundary, from the state page holding the state there.

Everything runs fp32: the extend/resumed paths are mathematically identical
to the cold prefill, and point stacks chunk on the position-space page
grid so the recurrent state's accumulation order is identical too — greedy
decode must match bit-for-bit.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.memclass import HBM3E, MRM_RRAM
from repro.core.simulator import MemorySystem
from repro.models import init_params
from repro.models.transformer import snapshot_kind, supports_extend
from repro.serving import ClusterFrontend, EngineConfig, ServeEngine

ARCHS = ["deepseek-7b", "mamba2-2.7b", "deepseek-v2-lite-16b", "hymba-1.5b"]
EXPECTED_KIND = {
    "deepseek-7b": "positional",
    "deepseek-v2-lite-16b": "positional",
    "mamba2-2.7b": "point",
    "hymba-1.5b": "point",
}


@pytest.fixture(scope="module", params=ARCHS)
def arch_setup(request):
    full = get_config(request.param)
    cfg = reduced(full, dtype="float32", param_dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    return request.param, full, cfg, params


def _mk_engine(full, cfg, params, **kw):
    mem = MemorySystem({"mrm": (MRM_RRAM, 64 << 30), "hbm": (HBM3E, 16 << 30)})
    ecfg = dict(max_slots=2, max_cache_len=96, weight_tier="hbm",
                kv_tier="mrm", eos_token=-1, chunk_tokens=16, page_tokens=16)
    ecfg.update(kw)
    return ServeEngine(cfg, params, mem, EngineConfig(**ecfg), account_cfg=full)


def _outputs(eng):
    return {k: list(v) for k, v in eng.outputs.items()}


def test_snapshot_kind_per_family(arch_setup):
    arch, full, cfg, params = arch_setup
    assert snapshot_kind(cfg) == EXPECTED_KIND[arch]
    assert snapshot_kind(full) == EXPECTED_KIND[arch]
    assert supports_extend(cfg)  # every family extends now


def test_extend_matches_whole_prompt_logits(arch_setup):
    """Model-level: prefilling a prompt's head and ``extend``-ing the tail
    is the same computation as whole-prompt prefill, for every mixer
    family — last-position logits and a subsequent decode step agree to
    fp32 reassociation tolerance (the two modes reduce in different
    orders; exact bitwise equality is only guaranteed when two runs cut
    the prompt identically, which the engine-level tests pin down)."""
    import jax.numpy as jnp

    from repro.models import transformer as tfm

    arch, full, cfg, params = arch_setup
    if cfg.n_experts:
        # MoE top-k routing flips on fp32 reassociation noise (~1e-6 at a
        # router input becomes a different expert), which is chaos, not an
        # extend bug — test the mixer path with dense MLPs instead. The
        # MoE config's extend path is held to the *stronger* bit-equality
        # bar in the engine-level tests below (identical partitions).
        cfg = reduced(full, dtype="float32", param_dtype="float32",
                      n_experts=0, n_shared_experts=0, moe_top_k=0,
                      expert_d_ff=0, first_dense_layers=0)
        params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(5)
    L, split = 40, 24
    toks = rng.integers(2, 400, (1, L)).astype(np.int32)
    plen = cfg.n_meta_tokens

    logits_whole, caches_whole = tfm.prefill(
        cfg, params, {"tokens": jnp.asarray(toks)}, max_cache_len=96)
    logits_head, caches = tfm.prefill(
        cfg, params, {"tokens": jnp.asarray(toks[:, :split])}, max_cache_len=96)
    logits_ext, caches_ext = tfm.extend(
        cfg, params, caches, jnp.asarray(toks[:, split:]), plen + split)
    np.testing.assert_allclose(np.asarray(logits_ext),
                               np.asarray(logits_whole),
                               atol=2e-4, rtol=2e-4)
    # decode one step from both cache states with the same forced token
    tok = np.asarray(jnp.argmax(logits_whole, -1)).astype(np.int32)[:, None]
    d_whole, _ = tfm.decode(cfg, params, caches_whole, jnp.asarray(tok),
                            plen + L)
    d_ext, _ = tfm.decode(cfg, params, caches_ext, jnp.asarray(tok), plen + L)
    np.testing.assert_allclose(np.asarray(d_ext), np.asarray(d_whole),
                               atol=2e-4, rtol=2e-4)


def test_chunked_engine_bit_equal_to_whole_prompt_engine(arch_setup):
    """Engine-level: a chunk_tokens=16 engine decodes exactly what a
    whole-prompt engine decodes. Bitwise equality requires both engines
    to cut prompts identically — guaranteed for point stacks, which chunk
    on the position-space page grid in every mode (DESIGN.md §8).
    Positional stacks reassociate the softmax between modes (covered at
    logits level above; the attention token-level form lives in
    tests/test_serving.py::test_chunked_prefill_token_equivalence)."""
    arch, full, cfg, params = arch_setup
    if EXPECTED_KIND[arch] != "point":
        pytest.skip("partition differs between modes for positional "
                    "stacks; see logits-level test above")
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(2, 400, n)) for n in (41, 70, 23)]
    chunked = _mk_engine(full, cfg, params, chunk_tokens=16)
    whole = _mk_engine(full, cfg, params, chunk_tokens=None)
    for eng in (chunked, whole):
        for p in prompts:
            eng.submit(list(p), 6)
        rep = eng.run_until_idle()
        assert rep["finished"] == len(prompts)
    assert _outputs(chunked) == _outputs(whole)


def test_prefix_hit_decodes_identically_to_cold_start(arch_setup):
    """Shared-prefix traffic served with the radix tree on decodes exactly
    what a prefix_caching=False engine decodes, and compute was actually
    skipped. Point stacks (SSM/hybrid) resume on the state page at the
    last page boundary the match covers (DESIGN.md §8)."""
    arch, full, cfg, params = arch_setup
    rng = np.random.default_rng(21)
    shared = list(rng.integers(2, 400, 48))
    prompts = [shared + list(rng.integers(2, 400, 8)) for _ in range(4)]

    warm = _mk_engine(full, cfg, params)
    for p in prompts:  # sequential: each later prompt can hit
        warm.submit(list(p), 6)
        warm.run_until_idle()
    assert warm.kv.prefix_hits >= 2          # pages shared either way
    assert warm.prefill_tokens_skipped > 0   # compute shortened overall
    assert warm.prefix_compute_hits >= 1

    cold = _mk_engine(full, cfg, params, prefix_caching=False)
    for p in prompts:
        cold.submit(list(p), 6)
        cold.run_until_idle()
    assert cold.prefill_tokens_skipped == 0
    assert _outputs(warm) == _outputs(cold)


def test_tail_hit_bit_equal_to_cold_start(arch_setup):
    """Sub-page tail reuse (DESIGN.md §9): a shared head that straddles a
    page boundary decodes bit-identically (fp32) to a cold start for
    every mixer family. Positional stacks (attention/MLA) actually copy
    the tail and resume extend from the exact token boundary; point
    stacks (SSM/hybrid) have no mid-page capture, so the tail degrades
    gracefully to the page-aligned behavior — same outputs, no copy."""
    arch, full, cfg, params = arch_setup
    kind = EXPECTED_KIND[arch]
    rng = np.random.default_rng(41)
    head = list(rng.integers(2, 400, 55))    # page 16: straddles a boundary
    prompts = [head + list(rng.integers(2, 400, 9)) for _ in range(4)]

    warm = _mk_engine(full, cfg, params, tail_copy=True)
    for p in prompts:   # sequential: each later prompt can hit
        warm.submit(list(p), 6)
        warm.run_until_idle()
    page_aligned = _mk_engine(full, cfg, params, tail_copy=False)
    for p in prompts:
        page_aligned.submit(list(p), 6)
        page_aligned.run_until_idle()
    cold = _mk_engine(full, cfg, params, prefix_caching=False)
    for p in prompts:
        cold.submit(list(p), 6)
        cold.run_until_idle()

    assert _outputs(warm) == _outputs(page_aligned) == _outputs(cold)
    if kind == "positional":
        # the tail was really copied (metered) and really skipped
        assert warm.kv.tail_hits > 0
        assert warm.kv.tail_tokens_copied > 0
        if full.kv_bytes_per_token() > 0:
            assert warm.kv.tail_copy_bytes > 0
        assert warm.prefill_tokens_computed \
            < page_aligned.prefill_tokens_computed
    else:
        # point stacks: the flag is on but no mid-page state exists,
        # so no copy may happen (a copy without compute reuse would
        # waste bus bytes and double-account the boundary)
        assert warm.kv.tail_hits == 0
        assert warm.prefill_tokens_computed \
            == page_aligned.prefill_tokens_computed


def test_decode_audit_all_families_interleaved(arch_setup):
    """Regression guard for the PR 4 clobbering class: with the padded
    whole-prompt path deleted, chunked prefill interleaves with decode on
    every path — the engine's decode-masking audit verifies per step that
    no compute page (KV, MLA latents, conv/SSD state) of a session outside
    the round is written. The audit raising would fail this test."""
    arch, full, cfg, params = arch_setup
    eng = _mk_engine(full, cfg, params, max_prefills_per_step=1,
                     audit_decode_masking=True)
    eng.submit(list(np.arange(2, 14)), 20)    # short: decoding quickly
    eng.submit(list(np.arange(2, 80)), 4)     # long: chunks interleave
    saw_interleave = False
    while not eng.sched.idle and eng.steps < 200:
        out = eng.step()
        if out["prefill_tokens"] > 0 and out["decode_tokens"] > 0:
            saw_interleave = True
    assert eng.sched.stats.finished == 2
    assert saw_interleave                     # the audit actually ran hot


NON_ATTENTION = ["mamba2-2.7b", "deepseek-v2-lite-16b", "hymba-1.5b"]


@pytest.fixture(scope="module", params=NON_ATTENTION)
def non_attn_setup(request):
    full = get_config(request.param)
    cfg = reduced(full, dtype="float32", param_dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    return request.param, full, cfg, params


def test_migrated_hit_decodes_identically_non_attention(non_attn_setup):
    """Cross-replica migration of the non-attention payloads (compressed
    latent pages / recurrent state / hybrid union): a request served off a
    grafted prefix on another replica decodes exactly what a cold engine
    decodes. (The attention case is covered in test_cluster_directory.)"""
    arch, full, cfg, params = non_attn_setup
    rng = np.random.default_rng(17)
    shared = list(rng.integers(2, 400, 48))
    # the seed prompt IS the shared head: its last page boundary then
    # sits exactly where the fan-out matches
    prompts = [list(shared)] + \
        [shared + list(rng.integers(2, 400, 8)) for _ in range(3)]

    fe = ClusterFrontend([_mk_engine(full, cfg, params) for _ in range(2)],
                         migrate_prefixes=True, migrate_load_gap=-1)
    rids = []
    for i, p in enumerate(prompts):
        rids.append(fe.submit(list(p), 6, session_key=f"u{i}"))
        fe.run_until_idle()
    # the fan-out crossed replicas and arrived as real hits there
    replicas = {fe.replica_of(r) for r in rids}
    assert len(replicas) == 2
    assert sum(e.kv.prefix_hits_migrated for e in fe.engines) >= 1
    # compute donation crossed the wire too: some replica that was not the
    # seed's home skipped prefill tokens
    home = fe.replica_of(rids[0])
    assert fe.engines[1 - home].prefill_tokens_skipped > 0

    cold = _mk_engine(full, cfg, params, prefix_caching=False)
    for p in prompts:
        cold.submit(list(p), 6)
        cold.run_until_idle()
    assert [fe.output(r) for r in rids] == \
        [cold.outputs[i] for i in range(len(prompts))]

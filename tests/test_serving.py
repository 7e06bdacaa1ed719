"""Serving stack: paged KV manager invariants, scheduler conservation
(hypothesis), end-to-end engine runs with paper-claim validation."""
import jax
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import get_config, reduced
from repro.core.memclass import HBM3E, MRM_RRAM
from repro.core.simulator import MemorySystem
from repro.models import init_params
from repro.serving import (ClusterFrontend, ContinuousBatchScheduler,
                           EngineConfig, PagedKVManager, Request, ServeEngine)


def _mem(gb=8):
    return MemorySystem({"mrm": (MRM_RRAM, gb << 30), "hbm": (HBM3E, gb << 30)})


# ---------------------------------------------------------------------------
# Paged KV manager
# ---------------------------------------------------------------------------


@given(st.lists(st.integers(min_value=1, max_value=300), min_size=1, max_size=20))
@settings(max_examples=30, deadline=None)
def test_paged_kv_token_accounting(appends):
    cfg = get_config("qwen3-8b")
    kv = PagedKVManager(cfg, _mem(), "mrm", page_tokens=128)
    kv.open_session(0)
    total = 0
    for n in appends:
        kv.append_tokens(0, n)
        total += n
    s = kv.sessions[0]
    assert s.tokens == total
    assert sum(p.n_tokens for p in s.pages) == total
    # every page except possibly the last is sealed exactly at page_tokens
    for p in s.pages[:-1]:
        assert p.sealed and p.n_tokens == 128
    assert s.pages[-1].n_tokens <= 128
    kv.close_session(0)
    assert kv.live_pages() == 0


def test_paged_kv_read_all_bytes():
    cfg = get_config("qwen3-8b")
    kv = PagedKVManager(cfg, _mem(), "mrm", page_tokens=64)
    kv.open_session(1)
    kv.append_tokens(1, 100)
    got = kv.read_all(1)
    assert got == 100 * cfg.kv_bytes_per_token()


# ---------------------------------------------------------------------------
# Scheduler
# ---------------------------------------------------------------------------


@given(st.integers(1, 8), st.integers(1, 30), st.integers(1, 4))
@settings(max_examples=30, deadline=None)
def test_scheduler_conservation(slots, n_requests, max_prefills):
    """Every submitted request is eventually admitted exactly once and
    finished exactly once; slots never over-subscribe."""
    sched = ContinuousBatchScheduler(slots, max_prefills)
    for i in range(n_requests):
        sched.submit(Request(i, [1, 2, 3], 4, 0.0))
    seen = set()
    for step in range(500):
        for slot, req in sched.admissions():
            assert req.request_id not in seen
            seen.add(req.request_id)
        assert len(sched.active) <= slots
        for slot in list(sched.decode_slots()):
            req = sched.active[slot]
            req.generated += 1
            if req.generated >= req.max_new_tokens:
                sched.finish(slot, float(step))
        if sched.idle:
            break
    assert sched.idle
    assert len(seen) == n_requests
    assert sched.stats.finished == n_requests


# ---------------------------------------------------------------------------
# Engine end-to-end
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_engine_setup():
    full = get_config("deepseek-7b")
    cfg = reduced(full)
    params = init_params(cfg, jax.random.key(0))
    return full, cfg, params


def test_engine_end_to_end_and_paper_claims(small_engine_setup):
    full, cfg, params = small_engine_setup
    mem = MemorySystem({"mrm": (MRM_RRAM, 64 << 30), "hbm": (HBM3E, 16 << 30)})
    eng = ServeEngine(cfg, params, mem,
                      EngineConfig(max_slots=3, max_cache_len=96,
                                   weight_tier="mrm", kv_tier="mrm",
                                   expected_session_s=5.0, eos_token=-1),
                      account_cfg=full)
    rng = np.random.default_rng(0)
    for _ in range(5):
        eng.submit(list(rng.integers(2, 400, rng.integers(6, 30))), 8)
    rep = eng.run_until_idle()
    assert rep["finished"] == 5
    assert rep["tokens_generated"] >= 5 * 8
    # paper §2.2: decode-dominated read:write >> 1000:1, sequential
    assert rep["steady_rw_ratio"] > 1000
    assert rep["memory"]["tiers"]["mrm"]["seq_fraction"] > 0.99
    assert rep["kv_live_pages"] == 0  # soft state dropped at session end


def test_engine_deterministic(small_engine_setup):
    full, cfg, params = small_engine_setup
    outs = []
    for _ in range(2):
        mem = MemorySystem({"mrm": (MRM_RRAM, 64 << 30), "hbm": (HBM3E, 16 << 30)})
        eng = ServeEngine(cfg, params, mem,
                          EngineConfig(max_slots=2, max_cache_len=64,
                                       weight_tier="mrm", kv_tier="mrm"),
                          account_cfg=full)
        rng = np.random.default_rng(7)
        for _ in range(3):
            eng.submit(list(rng.integers(2, 400, 12)), 6)
        eng.run_until_idle()
        outs.append({k: list(v) for k, v in eng.outputs.items()})
    assert outs[0] == outs[1]


def test_engine_refresh_fires_during_long_sessions(small_engine_setup):
    """KV pages written with short DCM retention must get refreshed while
    their session is still live."""
    full, cfg, params = small_engine_setup
    mem = MemorySystem({"mrm": (MRM_RRAM, 64 << 30), "hbm": (HBM3E, 16 << 30)})
    eng = ServeEngine(cfg, params, mem,
                      EngineConfig(max_slots=1, max_cache_len=96,
                                   weight_tier="hbm", kv_tier="mrm",
                                   expected_session_s=0.02),
                      account_cfg=full)
    # 80 decode steps x ~11.5 ms (weights stream from HBM at its own
    # bandwidth under the per-tier step-latency model) comfortably crosses
    # the DCM-floored 0.5 s refresh deadline
    eng.submit(list(np.arange(2, 34)), 80)
    rep = eng.run_until_idle()
    assert rep["memory"]["refresh_stats"]["refresh"] >= 1


# ---------------------------------------------------------------------------
# Beyond-paper features: prefix caching [53], weight redeploy wear (Fig. 1)
# ---------------------------------------------------------------------------


def test_prefix_caching_shares_pages():
    cfg = get_config("qwen3-8b")
    mem = _mem(32)
    kv = PagedKVManager(cfg, mem, "mrm", page_tokens=64)
    prompt = list(range(100, 300))        # 200 tokens
    w0 = mem.devices["mrm"].stats.write_bytes
    kv.open_session(0, match=kv.match_prefix(prompt))
    kv.append_tokens(0, 200)              # 3 sealed 64-token pages + 8 open
    kv.register_prefix(0, prompt)
    w_first = mem.devices["mrm"].stats.write_bytes - w0
    s1 = kv.open_session(1, match=kv.match_prefix(prompt))
    assert s1.shared_prefix_pages == 3 and s1.tokens == 192
    kv.append_tokens(1, 200 - s1.tokens)  # only the tail is written
    w_second = mem.devices["mrm"].stats.write_bytes - w0 - w_first
    assert w_second < w_first * 0.2
    assert kv.prefix_hits == 1 and kv.prefix_tokens_reused == 192
    # a *partial* prefix (radix, not whole-key) also matches, page-aligned
    s2 = kv.open_session(2, match=kv.match_prefix(prompt[:150]))
    assert s2.tokens == 128 and s2.shared_prefix_pages == 2
    # shared pages survive the first session's close, die with eviction
    kv.close_session(0)
    assert kv.read_all(1) == 200 * cfg.kv_bytes_per_token()
    kv.close_session(1)
    kv.close_session(2)
    assert kv.evict_prefixes() > 0        # leaf-LRU-evict the whole tree
    assert kv.live_pages() == 0 and kv.radix.n_nodes() == 0


def test_engine_prefix_caching_end_to_end(small_engine_setup):
    full, cfg, params = small_engine_setup
    mem = MemorySystem({"mrm": (MRM_RRAM, 64 << 30), "hbm": (HBM3E, 16 << 30)})
    eng = ServeEngine(cfg, params, mem,
                      EngineConfig(max_slots=2, max_cache_len=96,
                                   weight_tier="mrm", kv_tier="mrm",
                                   eos_token=-1, prefix_caching=True),
                      account_cfg=full)
    prompt = list(range(2, 70))  # 68 tokens, unpadded under prefix caching
    for _ in range(4):
        eng.submit(list(prompt), 4)
    rep = eng.run_until_idle()
    assert rep["finished"] == 4
    # the first two admissions share a step (both cold); the rest hit
    assert rep["prefix_hits"] >= 2
    assert rep["prefix_tokens_reused"] > 0
    # the hit is real in the compute plane: prefill tokens were skipped
    assert rep["prefill_tokens_skipped"] > 0
    assert rep["prefix"]["compute_hits"] >= 2
    # identical prompts must still produce identical outputs
    outs = [tuple(v) for v in eng.outputs.values()]
    assert len(set(outs)) == 1


def test_weight_redeploy_wear_accounting(small_engine_setup):
    """Fig. 1's weight-update endurance bars, measured from the system:
    each redeploy rewrites the weight region once."""
    full, cfg, params = small_engine_setup
    mem = MemorySystem({"mrm": (MRM_RRAM, 64 << 30), "hbm": (HBM3E, 16 << 30)})
    eng = ServeEngine(cfg, params, mem,
                      EngineConfig(max_slots=1, max_cache_len=64,
                                   weight_tier="mrm", kv_tier="hbm"),
                      account_cfg=full)
    w0 = mem.devices["mrm"].stats.write_bytes
    for _ in range(5):
        eng.redeploy_weights()
    # 5 full weight-region rewrites hit the device...
    assert mem.devices["mrm"].stats.write_bytes - w0 >= 5 * eng.weight_bytes
    # ...and the software wear-leveller spreads them (max/mean stays small)
    assert mem.devices["mrm"].wear.wear_ratio < 3.0
    # lifetime projection at an hourly update cadence stays > 5 years for MRM
    rate = eng.weight_bytes / 3600.0
    proj = mem.devices["mrm"].wear.project_lifetime_s(rate, 0.0)
    from repro.core.memclass import YEAR
    assert proj > 5 * YEAR


# ---------------------------------------------------------------------------
# Modality coverage: multi-codebook audio + VLM serving paths
# ---------------------------------------------------------------------------


def test_engine_multicodebook_audio():
    """musicgen-family serving: (B, 1, K) tokens, K LM heads, greedy per
    codebook."""
    full = get_config("musicgen-large")
    cfg = reduced(full)
    params = init_params(cfg, jax.random.key(0))
    mem = _mem(32)
    eng = ServeEngine(cfg, params, mem,
                      EngineConfig(max_slots=2, max_cache_len=64,
                                   weight_tier="mrm", kv_tier="mrm",
                                   eos_token=-1, prefix_caching=False),
                      account_cfg=full)
    rng = np.random.default_rng(0)
    for _ in range(3):
        prompt = [list(rng.integers(0, cfg.vocab_size, cfg.n_codebooks))
                  for _ in range(12)]
        eng.submit(prompt, max_new_tokens=5)
    rep = eng.run_until_idle()
    assert rep["finished"] == 3
    assert rep["tokens_generated"] >= 15
    assert eng.last_tokens.shape[-1] == cfg.n_codebooks


# ---------------------------------------------------------------------------
# Chunked prefill: equivalence vs one-maximal-chunk prompts, and prompts
# beyond max_cache_len
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def f32_engine_setup():
    """fp32 compute keeps the (mathematically equivalent) extend path's
    greedy argmax bit-stable vs whole-prompt prefill — bf16's residual
    rounding can amplify fp32-accumulation-order differences."""
    full = get_config("deepseek-7b")
    cfg = reduced(full, dtype="float32", param_dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    return full, cfg, params


def _run_engine(full, cfg, params, chunk_tokens, prompts, max_new=8, **ecfg_kw):
    mem = MemorySystem({"mrm": (MRM_RRAM, 64 << 30), "hbm": (HBM3E, 16 << 30)})
    kw = dict(max_slots=3, max_cache_len=96, weight_tier="mrm", kv_tier="mrm",
              eos_token=-1, chunk_tokens=chunk_tokens)
    kw.update(ecfg_kw)
    eng = ServeEngine(cfg, params, mem, EngineConfig(**kw), account_cfg=full)
    for p in prompts:
        eng.submit(list(p), max_new)
    rep = eng.run_until_idle()
    return eng, rep


def test_chunked_prefill_token_equivalence(f32_engine_setup):
    """A long prompt split across steps produces exactly the tokens the
    whole-prompt prefill produces."""
    full, cfg, params = f32_engine_setup
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, 400, n) for n in (41, 70, 23, 55)]
    eng_a, rep_a = _run_engine(full, cfg, params, None, prompts)
    eng_b, rep_b = _run_engine(full, cfg, params, 16, prompts)
    assert rep_a["finished"] == rep_b["finished"] == 4
    assert {k: list(v) for k, v in eng_a.outputs.items()} == \
           {k: list(v) for k, v in eng_b.outputs.items()}
    # chunking actually happened, and interleaved with decode rounds
    assert rep_b["prefill_chunks"] > rep_a["prefill_chunks"] == 4
    assert rep_b["steps"] > rep_a["steps"]


def test_chunked_prefill_admits_prompt_beyond_cache_len(small_engine_setup):
    """Prompts >> max_cache_len are admitted via chunked prefill (ring
    caches keep the attention tail)."""
    full, cfg, params = small_engine_setup
    rng = np.random.default_rng(4)
    long_prompt = rng.integers(2, 400, 210)  # max_cache_len is 96
    eng, rep = _run_engine(full, cfg, params, 32, [long_prompt], max_new=6)
    assert rep["finished"] == 1
    assert rep["tokens_generated"] >= 6
    assert rep["prefill_chunks"] >= 7
    assert rep["kv_live_pages"] == 0


def test_chunked_prefill_interleaves_decode(small_engine_setup):
    """While one request's prompt is still being chunked in, resident
    sessions keep decoding (bounded inter-token latency)."""
    full, cfg, params = small_engine_setup
    mem = MemorySystem({"mrm": (MRM_RRAM, 64 << 30), "hbm": (HBM3E, 16 << 30)})
    eng = ServeEngine(cfg, params, mem,
                      EngineConfig(max_slots=2, max_cache_len=96,
                                   weight_tier="mrm", kv_tier="mrm",
                                   eos_token=-1, chunk_tokens=16,
                                   max_prefills_per_step=1),
                      account_cfg=full)
    eng.submit(list(np.arange(2, 14)), 24)     # short: decoding quickly
    eng.submit(list(np.arange(2, 90)), 4)      # long: ~6 chunks
    saw_interleave = False
    while not eng.sched.idle and eng.steps < 200:
        out = eng.step()
        if out["prefill_tokens"] > 0 and out["decode_tokens"] > 0:
            saw_interleave = True
    assert saw_interleave
    assert eng.sched.stats.finished == 2


def test_prefix_hit_decodes_identically_to_cold_start(f32_engine_setup):
    """Acceptance: a radix prefix hit (slot caches seeded from the donor
    snapshot, prefill extended from the match boundary) must decode the
    exact tokens a cold start decodes — on the same engine (hit vs its own
    cold donor) and vs a fresh engine that never saw the prefix."""
    full, cfg, params = f32_engine_setup
    rng = np.random.default_rng(21)
    shared = list(rng.integers(2, 400, 40))
    prompts = [shared + list(rng.integers(2, 400, 8)) for _ in range(3)]

    eng, rep = _run_engine(full, cfg, params, 16, [], max_new=8,
                           page_tokens=8)
    for p in prompts:          # sequential: each later prompt hits
        eng.submit(list(p), 8)
        eng.run_until_idle()
    assert eng.kv.prefix_hits >= 2
    assert eng.prefill_tokens_skipped > 0   # compute actually shortened

    # cold baseline: same engine config, but the tree is drained between
    # requests, so every prompt prefills from scratch
    cold, _ = _run_engine(full, cfg, params, 16, [], max_new=8,
                          page_tokens=8)
    for p in prompts:
        cold.submit(list(p), 8)
        cold.run_until_idle()
        cold.kv.evict_prefixes()
        assert cold.kv.radix.n_nodes() == 0
    assert cold.kv.prefix_hits == 0
    assert {k: list(v) for k, v in eng.outputs.items()} == \
           {k: list(v) for k, v in cold.outputs.items()}


def test_wrapped_donor_never_donates_compute(f32_engine_setup):
    """A donor prompt that overflowed the smallest ring wrapped it — its
    snapshot lost the early positions a shorter borrower needs, so it must
    publish pages only (memory reuse), never a compute snapshot. The
    borrower prefills in full and decodes exactly like a cold start."""
    full, cfg, params = f32_engine_setup
    rng = np.random.default_rng(23)
    head = list(rng.integers(2, 400, 32))
    long_donor = head + list(rng.integers(2, 400, 108))  # 140 > ring (96)
    borrower = head + list(rng.integers(2, 400, 16))     # 48, shares 32

    eng, _ = _run_engine(full, cfg, params, 16, [], max_new=6, page_tokens=16)
    eng.submit(list(long_donor), 6)
    eng.run_until_idle()
    assert eng.kv.radix.n_nodes() > 0       # pages published...
    eng.submit(list(borrower), 6)
    eng.run_until_idle()
    assert eng.kv.prefix_hits >= 1          # ...and memory reuse happened
    assert eng.prefill_tokens_skipped == 0  # ...but no compute donation

    cold, _ = _run_engine(full, cfg, params, 16,
                          [long_donor, borrower], max_new=6,
                          page_tokens=16, prefix_caching=False)
    assert {k: list(v) for k, v in eng.outputs.items()} == \
           {k: list(v) for k, v in cold.outputs.items()}


def test_radix_reuse_cuts_prefill_and_kv_writes(f32_engine_setup):
    """Shared-prefix traffic: radix reuse must cut both the prefill tokens
    computed and the KV-tier write bytes at equal output tokens."""
    full, cfg, params = f32_engine_setup
    rng = np.random.default_rng(22)
    shared = list(rng.integers(2, 400, 48))
    prompts = [shared + list(rng.integers(2, 400, 16)) for _ in range(6)]
    kw = dict(page_tokens=16, weight_tier="hbm")
    eng_on, rep_on = _run_engine(full, cfg, params, 16, prompts, max_new=6, **kw)
    eng_off, rep_off = _run_engine(full, cfg, params, 16, prompts, max_new=6,
                                   prefix_caching=False, **kw)
    assert rep_on["tokens_generated"] == rep_off["tokens_generated"]
    assert {k: list(v) for k, v in eng_on.outputs.items()} == \
           {k: list(v) for k, v in eng_off.outputs.items()}
    # >= 30% fewer prefill tokens through the model...
    assert rep_on["prefill_tokens_computed"] <= \
        0.7 * rep_off["prefill_tokens_computed"]
    # ...and >= 30% fewer KV write bytes on the KV tier (weights in hbm)
    w_on = rep_on["memory"]["tiers"]["mrm"]["write_gb"]
    w_off = rep_off["memory"]["tiers"]["mrm"]["write_gb"]
    assert w_on <= 0.7 * w_off


def test_radix_hot_promotion_programs_retention(small_engine_setup):
    """Observed reuse programs retention: a node hit `hot_threshold` times
    is promoted (reprogram write metered, refresh deadline extended)."""
    full, cfg, params = small_engine_setup
    mem = MemorySystem({"mrm": (MRM_RRAM, 64 << 30), "hbm": (HBM3E, 16 << 30)})
    eng = ServeEngine(cfg, params, mem,
                      EngineConfig(max_slots=2, max_cache_len=96,
                                   weight_tier="hbm", kv_tier="mrm",
                                   eos_token=-1, page_tokens=16,
                                   chunk_tokens=16,
                                   radix_hot_threshold=2,
                                   radix_hot_retention_s=7200.0),
                      account_cfg=full)
    prompt = list(range(2, 50))
    for _ in range(5):
        eng.submit(list(prompt), 4)
        eng.run_until_idle()
    rep = eng.report()
    assert rep["prefix"]["retention_promotions"] >= 1
    assert rep["prefix"]["promoted_pages"] >= 1
    # reprogram writes are metered as refresh traffic, not steady writes
    assert rep["memory"]["tiers"]["mrm"]["refresh_gb"] > 0


def test_radix_auto_hot_tier_solves_placement(small_engine_setup):
    """radix_hot_tier='auto' runs the §4 placement solver over the
    engine's tiers and promotion migrates hot prefix pages there."""
    full, cfg, params = small_engine_setup
    mem = MemorySystem({"mrm": (MRM_RRAM, 4 << 30), "hbm": (HBM3E, 8 << 30),
                        "mrm_cold": (MRM_RRAM, 64 << 30)})
    eng = ServeEngine(cfg, params, mem,
                      EngineConfig(max_slots=2, max_cache_len=96,
                                   weight_tier="hbm", kv_tier="mrm",
                                   eos_token=-1, page_tokens=16,
                                   radix_hot_threshold=2,
                                   radix_hot_tier="auto"),
                      account_cfg=full)
    assert eng.memplane.hot_tier in mem.devices
    prompt = list(range(2, 50))
    for _ in range(4):
        eng.submit(list(prompt), 4)
        eng.run_until_idle()
    rep = eng.report()
    assert rep["prefix"]["retention_promotions"] >= 1
    if eng.memplane.hot_tier != "mrm":
        assert rep["prefix"]["migrated_pages"] >= 1


def test_radix_cold_leaves_decay(small_engine_setup):
    """Unlocked leaves idle past cold_ttl_s decay out of the tree (soft
    state: an identical future prompt recomputes)."""
    full, cfg, params = small_engine_setup
    mem = MemorySystem({"mrm": (MRM_RRAM, 64 << 30), "hbm": (HBM3E, 16 << 30)})
    eng = ServeEngine(cfg, params, mem,
                      EngineConfig(max_slots=2, max_cache_len=96,
                                   weight_tier="hbm", kv_tier="mrm",
                                   eos_token=-1, page_tokens=16,
                                   radix_cold_ttl_s=0.5),
                      account_cfg=full)
    eng.submit(list(range(2, 50)), 4)
    eng.run_until_idle()
    assert eng.kv.radix.n_nodes() > 0
    # let simulated time pass the TTL; maintenance runs on advance
    eng.mem.advance(1.0)
    eng.kv.maintain()
    assert eng.kv.radix.n_nodes() == 0
    assert eng.kv.radix_stats.cold_decays >= 1


def test_whole_prompt_is_one_maximal_chunk(f32_engine_setup):
    """Single-path invariant (DESIGN.md §5): with ``chunk_tokens=None`` a
    ring-fitting prompt runs as exactly one chunk of the same unpadded
    chunked path, and decodes bit-identically (fp32) whether prefix
    caching is on or off — there is no separate padded whole-prompt mode
    left to diverge from."""
    full, cfg, params = f32_engine_setup
    rng = np.random.default_rng(31)
    prompts = [rng.integers(2, 400, n) for n in (11, 27, 40)]
    eng_on, rep_on = _run_engine(full, cfg, params, None, prompts, max_new=6)
    eng_off, rep_off = _run_engine(full, cfg, params, None, prompts, max_new=6,
                                   prefix_caching=False)
    # one chunk per prompt: the maximal first chunk IS the whole prompt
    assert rep_on["prefill_chunks"] == rep_off["prefill_chunks"] == 3
    assert {k: list(v) for k, v in eng_on.outputs.items()} == \
           {k: list(v) for k, v in eng_off.outputs.items()}


def test_unchunked_long_prompt_admitted_via_ring_chunks(small_engine_setup):
    """A prompt beyond max_cache_len no longer needs an explicit
    chunk_tokens (the legacy padded mode rejected it at submit): the one
    chunked path splits it into ring-bounded pieces — the ring caches
    keep the attention window's tail, exactly as decode does."""
    full, cfg, params = small_engine_setup
    mem = MemorySystem({"mrm": (MRM_RRAM, 64 << 30), "hbm": (HBM3E, 16 << 30)})
    eng = ServeEngine(cfg, params, mem,
                      EngineConfig(max_slots=2, max_cache_len=64,
                                   weight_tier="mrm", kv_tier="mrm",
                                   eos_token=-1),
                      account_cfg=full)
    eng.submit(list(range(2, 102)), 4)      # 100 tokens > 64-token rings
    rep = eng.run_until_idle()
    assert rep["finished"] == 1
    assert rep["prefill_chunks"] > 1        # really split, not padded
    assert rep["kv_live_pages"] == 0


def test_chunked_prefill_windowed_config_clamps_chunk():
    """Sliding-window layers have per-layer rings smaller than
    max_cache_len; a requested chunk larger than the smallest ring must be
    clamped (an oversized chunk would collide ring slots intra-chunk)."""
    full = get_config("gemma2-27b")   # alternating local(64)/global reduced
    cfg = reduced(full)
    params = init_params(cfg, jax.random.key(0))
    mem = MemorySystem({"mrm": (MRM_RRAM, 64 << 30), "hbm": (HBM3E, 16 << 30)})
    eng = ServeEngine(cfg, params, mem,
                      EngineConfig(max_slots=2, max_cache_len=128,
                                   weight_tier="mrm", kv_tier="mrm",
                                   eos_token=-1, chunk_tokens=128),
                      account_cfg=full)
    rng = np.random.default_rng(11)
    for _ in range(2):
        eng.submit(list(rng.integers(2, 400, 100)), 4)
    rep = eng.run_until_idle()
    assert rep["finished"] == 2
    assert rep["tokens_generated"] >= 8
    # the 128-token request was actually split (min ring is 64)
    assert rep["prefill_chunks"] > 2


# ---------------------------------------------------------------------------
# Capacity pressure: explicit eviction/spill/recompute, never silent drops
# ---------------------------------------------------------------------------


def _tiny_mem(kv_bytes=1 << 26):
    return MemorySystem({"mrm": (MRM_RRAM, kv_bytes), "hbm": (HBM3E, 16 << 30)})


def test_pressure_prefix_lru_eviction_no_silent_drops(small_engine_setup):
    """A capacity-constrained KV tier forces evictions; every failed
    allocation is resolved by an explicit decision and the ledger balances."""
    full, cfg, params = small_engine_setup
    eng = ServeEngine(cfg, params, _tiny_mem(),
                      EngineConfig(max_slots=3, max_cache_len=64,
                                   weight_tier="hbm", kv_tier="mrm",
                                   eos_token=-1, page_tokens=16,
                                   kv_pressure_policy="evict-lru",
                                   kv_high_watermark=0.9),
                      account_cfg=full)
    rng = np.random.default_rng(5)
    for _ in range(10):
        eng.submit(list(rng.integers(2, 400, 64)), 8)
    rep = eng.run_until_idle()
    p = rep["pressure"]
    assert rep["finished"] == 10
    assert p["events"] > 0, "tier was supposed to be capacity-constrained"
    assert p["events"] == (p["resolved_evict"] + p["resolved_spill"] +
                           p["resolved_recompute"] + p["unresolved"])
    assert p["unresolved"] == 0 and rep["dropped_allocs"] == 0


def test_pressure_spill_tier(small_engine_setup):
    """'spill' policy migrates overflow pages to the colder tier: the spill
    device sees KV write traffic it never sees in the uncontended run.
    (The KV tier is sized below the workload's true footprint — which
    shrank when prompt padding was deleted, since pad tokens no longer
    enter the paged KV.)"""
    full, cfg, params = small_engine_setup
    mem = MemorySystem({"mrm": (MRM_RRAM, 1 << 25), "hbm": (HBM3E, 16 << 30),
                        "ddr": (MRM_RRAM, 64 << 30)})
    eng = ServeEngine(cfg, params, mem,
                      EngineConfig(max_slots=3, max_cache_len=64,
                                   weight_tier="hbm", kv_tier="mrm",
                                   eos_token=-1, prefix_caching=False,
                                   kv_pressure_policy="spill",
                                   kv_spill_tier="ddr"),
                      account_cfg=full)
    rng = np.random.default_rng(6)
    for _ in range(10):
        eng.submit(list(rng.integers(2, 400, 40)), 8)
    rep = eng.run_until_idle()
    p = rep["pressure"]
    assert p["events"] > 0 and p["resolved_spill"] > 0
    assert rep["dropped_allocs"] == 0
    assert mem.devices["ddr"].stats.write_bytes > 0


def test_pressure_recompute_policy_meters_recompute(small_engine_setup):
    """'recompute' drops soft state and re-materializes it on read, metered
    as recompute tokens (the paper's drop-and-recompute arm)."""
    full, cfg, params = small_engine_setup
    eng = ServeEngine(cfg, params, _tiny_mem(1 << 25),
                      EngineConfig(max_slots=3, max_cache_len=64,
                                   weight_tier="hbm", kv_tier="mrm",
                                   eos_token=-1, prefix_caching=False,
                                   kv_pressure_policy="recompute"),
                      account_cfg=full)
    rng = np.random.default_rng(7)
    for _ in range(8):
        eng.submit(list(rng.integers(2, 400, 40)), 8)
    rep = eng.run_until_idle()
    p = rep["pressure"]
    assert rep["finished"] == 8
    assert p["resolved_recompute"] > 0
    assert p["recompute_tokens"] > 0
    assert rep["dropped_allocs"] == 0


def test_kv_manager_legacy_none_policy_counts_drops():
    cfg = get_config("qwen3-8b")
    mem = MemorySystem({"mrm": (MRM_RRAM, 1 << 22), "hbm": (HBM3E, 1 << 30)})
    kv = PagedKVManager(cfg, mem, "mrm", page_tokens=64, policy="none")
    kv.open_session(0)
    kv.append_tokens(0, 64 * 50)
    assert kv.dropped_allocs > 0  # legacy silent counting is opt-in only
    assert kv.pressure.unresolved == kv.dropped_allocs


# ---------------------------------------------------------------------------
# Cluster frontend: N replicas, affinity routing, conserving fleet report
# ---------------------------------------------------------------------------


def _mk_engine(full, cfg, params, **kw):
    mem = MemorySystem({"mrm": (MRM_RRAM, 64 << 30), "hbm": (HBM3E, 16 << 30)})
    ecfg = dict(max_slots=2, max_cache_len=64, weight_tier="mrm",
                kv_tier="mrm", eos_token=-1, page_tokens=16)
    ecfg.update(kw)
    return ServeEngine(cfg, params, mem, EngineConfig(**ecfg), account_cfg=full)


def test_cluster_frontend_conserves_tokens_and_bytes(small_engine_setup):
    full, cfg, params = small_engine_setup
    fe = ClusterFrontend([_mk_engine(full, cfg, params) for _ in range(3)])
    rng = np.random.default_rng(8)
    n = 9
    for i in range(n):
        fe.submit(list(rng.integers(2, 400, 12)), 5)
    rep = fe.run_until_idle()
    assert rep["replicas"] == 3
    assert rep["finished"] == n
    assert rep["tokens_generated"] == n * 5
    # conservation: fleet aggregates == sum of replica reports
    assert rep["tokens_generated"] == sum(
        r["tokens_generated"] for r in rep["per_replica"])
    for tier in ("mrm", "hbm"):
        assert rep["tiers"][tier]["read_gb"] == pytest.approx(sum(
            r["memory"]["tiers"][tier]["read_gb"] for r in rep["per_replica"]))
        assert rep["tiers"][tier]["write_gb"] == pytest.approx(sum(
            r["memory"]["tiers"][tier]["write_gb"] for r in rep["per_replica"]))
    # shared simulated clock: all replicas ended at the fleet time
    assert all(abs(e.mem.now - rep["sim_time_s"]) < 1e-9 for e in fe.engines)
    # least-loaded routing spread work across every replica
    assert all(r["tokens_generated"] > 0 for r in rep["per_replica"])


def test_cluster_session_affinity_routes_sticky(small_engine_setup):
    full, cfg, params = small_engine_setup
    fe = ClusterFrontend([_mk_engine(full, cfg, params) for _ in range(3)])
    rng = np.random.default_rng(9)
    prompt = list(rng.integers(2, 400, 16))
    rids = {k: [fe.submit(list(prompt), 4, session_key=k) for _ in range(3)]
            for k in ("alice", "bob")}
    fe.run_until_idle()
    for k, ids in rids.items():
        assert len({fe.replica_of(r) for r in ids}) == 1
    # affinity means the repeated prompt hit the same replica's prefix index
    assert sum(e.kv.prefix_hits for e in fe.engines) >= 2


def test_cluster_radix_affinity_beats_key_hash(small_engine_setup):
    """A request sharing a served prompt's prefix must be routed to the
    replica holding it — whatever its session key hashes to — and arrive
    as a real prefix hit."""
    full, cfg, params = small_engine_setup
    fe = ClusterFrontend([_mk_engine(full, cfg, params, page_tokens=8)
                          for _ in range(3)])
    rng = np.random.default_rng(10)
    prompt = list(rng.integers(2, 400, 24))
    r0 = fe.submit(list(prompt), 4, session_key="alice")
    fe.run_until_idle()
    home = fe.replica_of(r0)
    assert fe.engines[home].kv.radix.n_nodes() > 0
    # different users, shared prefix (e.g. a common system prompt)
    rids = [fe.submit(list(prompt) + [500 + i], 4, session_key=f"user-{i}")
            for i in range(4)]
    fe.run_until_idle()
    assert all(fe.replica_of(r) == home for r in rids)
    assert fe.radix_routed >= 4
    assert fe.engines[home].kv.prefix_hits >= 4


def test_cluster_least_loaded_includes_kv_pressure(small_engine_setup):
    """A replica with a saturated KV tier must lose least-loaded ties to
    an equally-queued replica with free KV capacity."""
    full, cfg, params = small_engine_setup
    busy = _mk_engine(full, cfg, params, prefix_caching=False)
    idle = _mk_engine(full, cfg, params, prefix_caching=False)
    fe = ClusterFrontend([busy, idle])
    # occupy replica 0's KV with a live session (equal queue lengths)
    busy.kv.open_session(999)
    busy.kv.append_tokens(999, 512)
    assert fe.route() == 1  # tie on load -> KV pressure breaks it
    busy.kv.close_session(999)
    assert fe.route() == 0  # pressure gone -> index order


def test_ttft_itl_percentiles_reported(small_engine_setup):
    full, cfg, params = small_engine_setup
    mem = MemorySystem({"mrm": (MRM_RRAM, 64 << 30), "hbm": (HBM3E, 16 << 30)})
    eng = ServeEngine(cfg, params, mem,
                      EngineConfig(max_slots=2, max_cache_len=64,
                                   weight_tier="mrm", kv_tier="mrm",
                                   eos_token=-1),
                      account_cfg=full)
    rng = np.random.default_rng(12)
    for _ in range(4):
        eng.submit(list(rng.integers(2, 400, 12)), 6)
    rep = eng.run_until_idle()
    lat = rep["latency"]
    assert lat["n"] == 4
    assert lat["ttft_p50"] is not None and lat["ttft_p50"] > 0
    assert lat["itl_p50"] is not None and lat["itl_p50"] > 0
    assert lat["ttft_p95"] >= lat["ttft_p50"]
    # every finished request recorded a first-token time
    for r in eng.sched.latency:
        assert r["ttft"] is not None and r["ttft"] >= 0
    # the cluster fleet report pools the same records
    fe = ClusterFrontend([eng])
    assert fe.report()["latency"]["n"] == 4


# ---------------------------------------------------------------------------
# Per-tier step-latency model + O(1) region lookup
# ---------------------------------------------------------------------------


def test_step_latency_is_per_tier():
    """Traffic on a slow tier must not be charged at the fast tier's
    bandwidth: the slowest tier bounds the step."""
    from repro.core.memclass import get_technology
    mrm = get_technology("mrm_rram")
    mem = MemorySystem({"mrm": (mrm, 16 << 30), "hbm": (HBM3E, 16 << 30)})
    snap = mem.snapshot()
    rid = mem.write_region("mrm", "x", 6e9, expected_lifetime_s=10.0)
    step_s, per_tier = mem.step_latency_since(snap)
    expect = 6e9 / (mrm.write_bw_gbps * 1e9)
    assert step_s == pytest.approx(expect, rel=1e-6)
    assert per_tier["mrm"]["write_bytes"] == pytest.approx(6e9)
    assert per_tier["hbm"]["latency_s"] == 0.0
    # reads charged at read bandwidth, on the region's own tier, O(1) lookup
    snap = mem.snapshot()
    mem.read_region(rid, 8e9)
    step_s, per_tier = mem.step_latency_since(snap)
    assert step_s == pytest.approx(8e9 / (mrm.read_bw_gbps * 1e9), rel=1e-6)
    assert mem.region(rid).tier == "mrm"


def test_engine_vlm_frontend_stub():
    """internvl2-family serving: patch embeddings prepended by the stub
    frontend; positions account for the prefix."""
    full = get_config("internvl2-76b")
    cfg = reduced(full)
    params = init_params(cfg, jax.random.key(0))
    mem = _mem(32)
    eng = ServeEngine(cfg, params, mem,
                      EngineConfig(max_slots=2, max_cache_len=96,
                                   weight_tier="mrm", kv_tier="mrm",
                                   eos_token=-1, prefix_caching=False),
                      account_cfg=full)
    rng = np.random.default_rng(1)
    for _ in range(2):
        eng.submit(list(rng.integers(2, cfg.vocab_size, 20)), max_new_tokens=4)
    rep = eng.run_until_idle()
    assert rep["finished"] == 2
    # KV accounting includes the frontend prefix tokens
    assert eng.kv.prefix_tokens_reused == 0


def test_sampler_never_emits_vocab_padding():
    """The LM head is padded past the vocabulary (vocab_pad_to); a padding
    id is not a token, so greedy sampling ignores those columns even when
    random weights make one the largest logit."""
    from repro.serving.engine import ComputeBackend
    cfg = reduced(get_config("qwen3-8b"), vocab_size=500, vocab_pad_to=64)
    assert cfg.padded_vocab == 512
    backend = ComputeBackend(cfg, None, EngineConfig(max_slots=2))
    logits = np.zeros((2, cfg.padded_vocab), np.float32)
    logits[:, 3] = 1.0
    logits[:, 510] = 9.0                        # a padding column wins
    assert np.asarray(backend.sample(logits)).tolist() == [3, 3]


def test_compile_cache_dir_prefers_the_environment(monkeypatch):
    """The persistent compile cache lives where JAX_COMPILATION_CACHE_DIR
    says, and only without it at the fixed <repo>/.jax_cache."""
    from repro.launch.serve import REPO_ROOT, compile_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
    assert compile_cache_dir() == "/cache/from/env"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache_dir() == str(REPO_ROOT / ".jax_cache")
    assert (REPO_ROOT / "src" / "repro" / "launch" / "serve.py").is_file()


def test_replicas_sharing_a_device_share_one_weight_copy(small_engine_setup):
    """serve.replica_params commits replica i's weights to device
    i % devices; replicas that land on one device share one copy, and the
    engine keeps its page pool where its weights are."""
    from repro.launch.serve import replica_params
    full, cfg, params = small_engine_setup
    n = len(jax.devices()) + 1
    placed = replica_params(params, n)
    assert len(placed) == n and placed[-1] is placed[0]
    for i, p in enumerate(placed):
        dev = jax.devices()[i % len(jax.devices())]
        assert {d for x in jax.tree.leaves(p) for d in x.devices()} == {dev}
    eng = ServeEngine(cfg, placed[0], _mem(),
                      EngineConfig(max_slots=2, paged_kernel=True),
                      account_cfg=full)
    assert {d for x in jax.tree.leaves(eng.backend.paged_caches)
            for d in x.devices()} == {jax.devices()[0]}

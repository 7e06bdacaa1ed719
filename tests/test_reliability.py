"""Reliability plane (DESIGN.md §11): ECC numerics in isolation —
RBER-vs-age model, binomial tail bound, code selection — plus margin
derates, split-codeword selection, fault-injection determinism, the
simulator's ECC/scrub metering invariants, and the engine injection
path."""
import math

import numpy as np
import pytest

from repro.core import (FaultInjector, MemorySystem, SplitCode, TierEcc,
                        cell_cost_factor, data_class_of, derated_rber_at_age,
                        design_code, design_split_code, margin_derate,
                        rber_at_age, uncorrectable_log10)
from repro.core.ecc import (ECC_PROFILES, MARGIN_RBER_CAP,
                            STATE_RETENTION_FRAC, _log_binom_tail)
from repro.core.faults import CRIT_BIT_RANGE, flip_bits
from repro.core.memclass import DAY, HBM3E, MRM_MRAM, MRM_PCM, MRM_RRAM

MANAGED = [MRM_PCM, MRM_RRAM, MRM_MRAM]


# ---------------------------------------------------------------------------
# rber_at_age: monotonicity and clamps
# ---------------------------------------------------------------------------


def test_rber_monotone_in_age():
    ages = [0.0, DAY / 8, DAY / 4, DAY / 2, DAY, 2 * DAY]
    rbers = [rber_at_age(MRM_RRAM, a, DAY) for a in ages]
    assert all(b > a for a, b in zip(rbers, rbers[1:]))


def test_rber_anchors():
    # at write: rber0; at the programmed deadline: rber_at_retention
    assert rber_at_age(MRM_RRAM, 0.0, DAY) == pytest.approx(1e-9)
    assert rber_at_age(MRM_RRAM, DAY, DAY) == pytest.approx(1e-4)
    assert rber_at_age(MRM_RRAM, 0.0, DAY, rber0=1e-7) == pytest.approx(1e-7)


def test_rber_clamps():
    # age/retention saturates at 4x, and the rate itself at the 0.5 ceiling
    assert rber_at_age(MRM_RRAM, 4 * DAY, DAY) == \
        rber_at_age(MRM_RRAM, 400 * DAY, DAY)
    assert rber_at_age(MRM_RRAM, 400 * DAY, DAY,
                       rber_at_retention=1e-1) == 0.5
    # negative age is treated as fresh, zero retention does not divide
    assert rber_at_age(MRM_RRAM, -5.0, DAY) == pytest.approx(1e-9)
    assert rber_at_age(MRM_RRAM, 1.0, 0.0) <= 0.5


# ---------------------------------------------------------------------------
# _log_binom_tail vs exact binomial sums (small n)
# ---------------------------------------------------------------------------


def _exact_log_tail(n: int, t: int, p: float) -> float:
    s = sum(math.comb(n, k) * p ** k * (1 - p) ** (n - k)
            for k in range(t + 1, n + 1))
    return math.log10(s) if s > 0 else -300.0


@pytest.mark.parametrize("n", [10, 20, 30])
@pytest.mark.parametrize("t", [1, 2, 3, 5])
@pytest.mark.parametrize("p", [1e-4, 1e-3, 1e-2])
def test_log_binom_tail_vs_exact(n, t, p):
    if t < n * p:
        return  # below-mode regime covered by test_log_binom_tail_mode_guard
    approx = _log_binom_tail(n, t, p)
    exact = _exact_log_tail(n, t, p)
    # the dominant term is a lower bound of the tail, and within a tenth
    # of a decade of exact in every regime design_code operates in
    assert approx <= exact + 1e-12
    assert exact - approx < 0.1


def test_log_binom_tail_mode_guard():
    # t below the mode (n*p): the mass sits far above t, so the tail is
    # ~certain — without the guard the dominant term at t+1 underestimates
    # it catastrophically and design_code would return t=1 codes at RBERs
    # where every block fails
    assert _log_binom_tail(10_000, 5, 0.01) == math.log10(0.5)
    assert _log_binom_tail(100, 0, 0.5) == 0.0     # certain-failure regime
    assert _log_binom_tail(100, 3, 0.0) == -300.0  # no errors possible


# ---------------------------------------------------------------------------
# design_code boundary cases
# ---------------------------------------------------------------------------


def test_design_code_t_grows_with_rber():
    ts = [design_code(4096, r).correctable for r in (1e-7, 1e-5, 1e-4, 1e-3)]
    assert all(b >= a for a, b in zip(ts, ts[1:]))
    assert ts[-1] > ts[0]


def test_design_code_infeasible_rber_raises():
    with pytest.raises(ValueError):
        design_code(4096, 0.5)


def test_design_code_stricter_target_costs_more():
    loose = design_code(4096, 1e-4, uber_target=1e-9)
    strict = design_code(4096, 1e-4, uber_target=1e-21)
    assert strict.correctable > loose.correctable
    assert strict.overhead > loose.overhead


# ---------------------------------------------------------------------------
# margin derate / cell cost — the density lever's two sides
# ---------------------------------------------------------------------------


def test_margin_derate_identity_at_nominal_and_growth_below():
    assert margin_derate(MRM_RRAM, MRM_RRAM.retention_s) == pytest.approx(1.0)
    d600 = margin_derate(MRM_RRAM, 600.0)
    d75 = margin_derate(MRM_RRAM, 75.0)
    assert 1.0 < d600 < d75
    # sub-second retentions clamp to 1 s instead of diverging
    assert margin_derate(MRM_RRAM, 1e-6) == margin_derate(MRM_RRAM, 1.0)


def test_derated_rber_capped_and_bounded():
    # the derate multiplies the anchors but never past the designable cap
    r = derated_rber_at_age(MRM_RRAM, 300.0, 600.0)
    assert 0.0 < r <= MARGIN_RBER_CAP
    assert derated_rber_at_age(MRM_RRAM, 100 * DAY, 600.0) <= 0.5


def test_cell_cost_factor_discount():
    assert cell_cost_factor(MRM_RRAM, MRM_RRAM.retention_s) == pytest.approx(1.0)
    c = cell_cost_factor(MRM_RRAM, 600.0)
    assert 0.65 <= c < 1.0
    assert cell_cost_factor(MRM_RRAM, 1.0) == 0.65  # floor


# ---------------------------------------------------------------------------
# split codeword: exponent-protected / mantissa-relaxed
# ---------------------------------------------------------------------------


def test_split_code_structure():
    sc = design_split_code(4096, 1e-4)
    assert isinstance(sc, SplitCode)
    assert sc.data_bits == 4096 * 8
    assert sc.parity_bits == sc.crit.parity_bits + sc.bulk.parity_bits
    assert sc.n_bits == sc.data_bits + sc.parity_bits
    assert sc.correctable == sc.crit.correctable
    assert sc.bulk.correctable == 1


def test_split_code_beats_uniform_at_derated_rber():
    rber = 1e-4  # where the density lever operates
    assert design_split_code(4096, rber).overhead < \
        design_code(4096, rber).overhead


def test_split_code_crossover_at_low_rber():
    # at nominal-margin RBER both designs carry the minimum t; the split
    # code pays its extra fixed bulk code, so TierEcc must prefer uniform
    rber = 1e-7
    assert design_split_code(4096, rber).overhead >= \
        design_code(4096, rber).overhead


def test_uncorrectable_log10_matches_tail():
    code = design_code(4096, 1e-5)
    assert uncorrectable_log10(code, 1e-5) == \
        _log_binom_tail(code.n_bits, code.correctable, 1e-5)
    assert uncorrectable_log10(code, 1e-5) < -15 < \
        uncorrectable_log10(code, 0.4)


# ---------------------------------------------------------------------------
# TierEcc code selection
# ---------------------------------------------------------------------------


def test_tier_ecc_off_meters_nothing():
    ecc = TierEcc(MRM_RRAM, "off")
    assert ecc.code_for("kv", 600.0) is None
    assert ecc.overhead_for("kv", 600.0) == 0.0
    assert ecc.summary() == {"profile": "off"}


def test_tier_ecc_rejects_unknown_profile():
    with pytest.raises(ValueError):
        TierEcc(MRM_RRAM, "strong")
    assert set(ECC_PROFILES) == {"off", "uniform", "domain"}


def test_tier_ecc_weights_always_uniform_strict():
    ecc = TierEcc(MRM_RRAM, "domain")
    for frac in STATE_RETENTION_FRAC.values():
        code = ecc.code_for("weights", MRM_RRAM.retention_s * frac)
        assert not isinstance(code, SplitCode)


def test_tier_ecc_domain_never_worse_and_wins_when_derated():
    dom = TierEcc(MRM_RRAM, "domain")
    uni = TierEcc(MRM_RRAM, "uniform")
    for state, frac in STATE_RETENTION_FRAC.items():
        r = MRM_RRAM.retention_s * frac
        od, ou = dom.overhead_for("kv", r), uni.overhead_for("kv", r)
        assert 0.0 < od <= ou
        if state != "hot":  # the density gate: derated states must shrink
            assert od < ou
    # shorter retention -> leakier cells -> more parity, both profiles
    for ecc in (dom, uni):
        ovs = [ecc.overhead_for("kv", MRM_RRAM.retention_s * f)
               for f in STATE_RETENTION_FRAC.values()]
        assert all(b >= a for a, b in zip(ovs, ovs[1:]))


def test_tier_ecc_cache_buckets():
    ecc = TierEcc(MRM_RRAM, "domain")
    # same eighth-decade bucket -> the designed code object is reused
    assert ecc.code_for("kv", 600.0) is ecc.code_for("kv", 601.0)


def test_tier_ecc_volatile_tier_does_not_crash():
    # HBM's sub-second retention clamps to the 1 s floor: a finite code
    # (its 32-byte blocks amortize parity poorly), not a crash, when a
    # volatile tier is configured with ECC on
    ecc = TierEcc(HBM3E, "domain")
    assert 0.0 < ecc.overhead_for("kv", HBM3E.retention_s) < 0.5


def test_data_class_of_owner_names():
    assert data_class_of("weights:llama") == "weights"
    assert data_class_of("kv:req-1") == "kv"
    assert data_class_of("prefix:hot") == "kv"


# ---------------------------------------------------------------------------
# fault injection
# ---------------------------------------------------------------------------


def test_flip_bits_deterministic_and_band_limited():
    rng1, rng2 = np.random.default_rng(7), np.random.default_rng(7)
    arr = np.zeros(256, np.float32)
    a = flip_bits(arr, 5, 9, rng1)
    b = flip_bits(arr, 5, 9, rng2)
    assert np.array_equal(a.view(np.uint32), b.view(np.uint32))
    assert a.shape == arr.shape and a.dtype == arr.dtype
    # crit flips stay in the sign/exponent band, bulk in the mantissa band
    lo, _ = CRIT_BIT_RANGE["float32"]
    crit_only = flip_bits(arr, 8, 0, np.random.default_rng(1))
    assert not np.any(crit_only.view(np.uint32) & ((1 << lo) - 1))
    bulk_only = flip_bits(arr, 0, 8, np.random.default_rng(2))
    assert not np.any(bulk_only.view(np.uint32) >> lo)


def test_flip_bits_zero_flips_is_identity():
    arr = np.arange(16, dtype=np.float32)
    assert flip_bits(arr, 0, 0, np.random.default_rng(0)) is arr


def _mem_with_region(ecc_profile="domain", **kw):
    mem = MemorySystem({"mrm": (MRM_RRAM, 1 << 30)},
                       ecc_profile=ecc_profile, **kw)
    rid = mem.write_region("mrm", "kv:test", 1 << 20,
                           expected_lifetime_s=600.0)
    return mem, rid


def test_injector_rber_tracks_age():
    mem, rid = _mem_with_region()
    inj = FaultInjector(mem, 1e-3, seed=0)
    region = mem.region(rid)
    fresh = inj.page_rber(region)
    mem.now += 0.9 * region.retention_s
    aged = inj.page_rber(region)
    assert 0.0 < fresh < aged <= 0.5
    assert aged == pytest.approx(
        rber_at_age(MRM_RRAM, mem.now - region.written_at,
                    region.retention_s, rber0=1e-8, rber_at_retention=1e-3))


def test_injector_scrub_threshold():
    mem, rid = _mem_with_region()
    inj = FaultInjector(mem, 1e-3, seed=0)
    region = mem.region(rid)
    interval = region.retention_s / mem.tracker.margin
    mem.now = region.written_at + 0.5 * interval
    assert not inj.wants_scrub(region)
    mem.now = region.written_at + 0.8 * interval
    assert inj.wants_scrub(region)


def test_injector_fresh_protected_page_is_clean():
    mem, rid = _mem_with_region()
    inj = FaultInjector(mem, 1e-3, seed=0)
    arr = np.ones((64, 64), np.float32)
    out, n_bad = inj.corrupt(arr, mem.region(rid), protected=True)
    assert out is None and n_bad == 0
    assert inj.stats.uncorrectable_blocks == 0


def test_injector_overaged_page_corrupts_past_protection():
    mem, rid = _mem_with_region()
    inj = FaultInjector(mem, 1e-3, seed=0)
    region = mem.region(rid)
    mem.now = region.written_at + 10 * region.retention_s  # RBER clamps to 0.5
    arr = np.ones((64, 64), np.float32)
    out, n_bad = inj.corrupt(arr, region, protected=True)
    assert out is not None and n_bad > 0
    assert not np.array_equal(out, arr)
    assert inj.stats.crit_flips > 0 and inj.stats.uncorrectable_blocks > 0


def test_injector_unprotected_flips_land_directly():
    mem, rid = _mem_with_region(ecc_profile="off")
    inj = FaultInjector(mem, 1e-2, seed=3)
    region = mem.region(rid)
    mem.now = region.written_at + region.retention_s
    arr = np.zeros((64, 64), np.float32)
    out, n_bad = inj.corrupt(arr, region, protected=False)
    assert out is not None and n_bad == 0  # no accounting-scale sampling
    assert np.any(out != 0.0)


def test_injector_skips_unfloatable_dtypes():
    mem, rid = _mem_with_region()
    inj = FaultInjector(mem, 1e-3, seed=0)
    out, n_bad = inj.corrupt(np.zeros(8, np.int8), mem.region(rid), False)
    assert out is None and n_bad == 0


# ---------------------------------------------------------------------------
# simulator metering invariants
# ---------------------------------------------------------------------------


def test_ecc_off_is_byte_identical():
    mem, rid = _mem_with_region(ecc_profile="off")
    mem.read_region(rid, 1 << 20)
    d = mem.devices["mrm"]
    assert d.stats.ecc_read_bytes == d.stats.ecc_write_bytes == 0
    assert d.stats.scrub_read_bytes == 0 and d.stats.n_scrubs == 0


def test_ecc_bytes_metered_separately():
    """The ECC-bytes-balance invariant: check bits never pollute
    read_bytes/write_bytes (the §10 smoke identity survives), but do
    enter snapshot()/step-latency totals."""
    mem, rid = _mem_with_region(ecc_profile="domain")
    base = mem.devices["mrm"].stats.write_bytes
    mem.read_region(rid, 1 << 20)
    d = mem.devices["mrm"]
    ov = d.ecc.overhead_for("kv", mem.region(rid).retention_s)
    assert d.stats.ecc_write_bytes == pytest.approx((1 << 20) * ov)
    assert d.stats.ecc_read_bytes == pytest.approx((1 << 20) * ov)
    assert d.stats.write_bytes == base  # data counters untouched by ECC
    assert d.stats.read_bytes == 1 << 20
    reads, writes = mem.snapshot()["mrm"]
    assert reads == d.stats.read_bytes + d.stats.ecc_read_bytes + \
        d.stats.scrub_read_bytes
    assert writes == d.stats.write_bytes + d.stats.refresh_bytes + \
        d.stats.ecc_write_bytes


def test_ecc_capacity_ledger_tenant():
    mem, _ = _mem_with_region(ecc_profile="domain")
    d = mem.devices["mrm"]
    n = 10 << 20
    assert d.blocks_for_stored(n, "kv", 600.0) > d.blocks_for(n)
    # weights pay the strict uniform code's (larger) overhead
    assert d.blocks_for_stored(n, "weights", 600.0) >= \
        d.blocks_for_stored(n, "kv", 600.0)


def test_scrub_charged_as_refresh():
    mem, rid = _mem_with_region(ecc_profile="domain")
    d = mem.devices["mrm"]
    region = mem.region(rid)
    mem.advance(0.8 * region.retention_s / mem.tracker.margin)
    wear_before = d.wear.scrub_rewrites
    assert mem.scrub_region(rid)
    ov = d.ecc.overhead_for("kv", region.retention_s)
    assert d.stats.n_scrubs == 1
    assert d.stats.scrub_read_bytes == pytest.approx((1 << 20) * (1 + ov))
    assert d.stats.refresh_bytes >= 1 << 20       # rewrite charged as refresh
    assert d.wear.scrub_rewrites > wear_before    # in-place wear recorded
    assert region.written_at == mem.now           # retention clock re-armed
    assert not mem.scrub_region(10 ** 9)          # unknown region: no-op


def test_service_refresh_disabled_pages_age_out():
    mem, rid = _mem_with_region(ecc_profile="domain", service_refresh=False)
    region = mem.region(rid)
    written = region.written_at
    assert mem.advance(4 * region.retention_s) == []
    assert region.written_at == written           # never refreshed
    assert mem.devices["mrm"].stats.refresh_bytes == 0


# ---------------------------------------------------------------------------
# engine injection path
# ---------------------------------------------------------------------------


def test_engine_reports_reliability_and_injects():
    import jax

    from repro.configs import get_config, reduced
    from repro.models import init_params
    from repro.serving import EngineConfig, ServeEngine

    full = get_config("gemma-2b")
    cfg = reduced(full)
    params = init_params(cfg, jax.random.key(0))
    mem = MemorySystem({"mrm": (MRM_RRAM, 1 << 30), "hbm": (HBM3E, 1 << 28)},
                       ecc_profile="domain")
    eng = ServeEngine(
        cfg, params, mem,
        EngineConfig(max_slots=1, max_cache_len=64, weight_tier="hbm",
                     kv_tier="mrm", eos_token=-1, page_tokens=16,
                     chunk_tokens=16, inject_rber=1e-3, inject_seed=0),
        account_cfg=full)
    rng = np.random.default_rng(0)
    eng.submit(list(rng.integers(2, cfg.vocab_size, 24)), max_new_tokens=4)
    rep = eng.run_until_idle()
    rel = rep["reliability"]
    assert rel["ecc_profile"] == "domain"
    assert rel["inject_rber"] == pytest.approx(1e-3)
    assert rel["injection"]["pages_visited"] > 0
    # fresh pages under protection: injection observed but nothing lands
    assert rel["injection"]["uncorrectable_blocks"] == 0
    mrm = rel["tiers"]["mrm"]
    assert mrm["ecc_write_bytes"] > 0 and mrm["ecc_read_bytes"] > 0

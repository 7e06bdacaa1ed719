"""The trace reduction on three engine steps recorded on a TPU v5e
(fixtures/v5e_sysprompt_steps.textproto), checked against a brute-force
reading of the same events, and on small hand-made cases."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for _p in (BENCH, BENCH.parent / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.profiler import ProfileData  # noqa: E402

from cb import trace as T  # noqa: E402

FIXTURE = Path(__file__).parent / "fixtures" / "v5e_sysprompt_steps.textproto"


@pytest.fixture(scope="module")
def recorded():
    text = "\n".join(line for line in FIXTURE.read_text().splitlines()
                     if not line.startswith("#"))
    return T.from_profile(ProfileData.from_text_proto(text))


def brute_busy(tr, w0, w1, step_ns=100.0):
    t = np.arange(w0 + step_ns / 2, w1, step_ns)
    busy = np.zeros(t.shape, bool)
    for _, a, b in tr.devices[0].ops:
        busy |= (t >= a) & (t < b)
    return t, busy


def brute_label(tr, t):
    inside = [(b - a, n) for n, a, b in tr.spans if a <= t <= b]
    return min(inside)[1].split(":")[0] if inside else "outside any span"


def test_recorded_trace_is_read(recorded):
    assert len(recorded.devices) == 1
    names = {n.split(":")[0] for n, _, _ in recorded.spans}
    assert {"cb.step", "cb.run_decode", "cb.run_prefill_chunk",
            "cb.traced"} <= names


def test_busy_union_and_idle_share(recorded):
    r = T.reduce(recorded)
    (w0, w1), = [(a, b) for n, a, b in recorded.spans if n == T.MARKER]
    assert r["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    _, busy = brute_busy(recorded, w0, w1)
    assert r["busy_s"] == pytest.approx(busy.mean() * (w1 - w0) * 1e-9,
                                        rel=1e-3)
    # the chip was busy most, not all, of these steps
    assert 0.6 < r["busy_s"] / r["window_s"] < 0.95


def test_idle_gaps_named_by_the_innermost_span(recorded):
    r = T.reduce(recorded)
    (w0, w1), = [(a, b) for n, a, b in recorded.spans if n == T.MARKER]
    t, busy = brute_busy(recorded, w0, w1, step_ns=1000.0)
    want = {}
    for x in t[~busy]:
        k = brute_label(recorded, x)
        want[k] = want.get(k, 0.0) + 1000e-9
    got = dict(r["idle_gaps"])
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=0.02, abs=2e-5), k
    assert sum(got.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_launches_pair_with_their_programs(recorded):
    r = T.reduce(recorded)
    kinds = [n for n, _ in r["launches"]]
    assert "cb.run_decode" in kinds and "cb.run_prefill_chunk:extend" in kinds
    dec = [s for n, s in r["launches"] if n == "cb.run_decode"]
    ext = [s for n, s in r["launches"] if n != "cb.run_decode"]
    # one decode program per round, all alike; extends take longer
    assert max(dec) - min(dec) < 0.05 * min(dec)
    assert min(ext) > max(dec)


def trace_of(ops, modules, spans):
    return T.Trace([T.Device("/device:TPU:0", ops, modules)], spans)


def test_nested_ops_keep_their_self_time_and_kind():
    ops = [("%while.1 = ...", 100.0, 200.0), ("%fusion.2 = ...", 110.0, 150.0),
           ("%copy.3 = ...", 300.0, 320.0)]
    mods = [("jit__lambda(1)", 100.0, 200.0), ("jit_copy(2)", 300.0, 320.0)]
    spans = [("cb.traced", 50.0, 400.0), ("cb.step", 60.0, 350.0),
             ("cb.run_decode", 90.0, 210.0), ("cb.idle", 360.0, 400.0)]
    r = T.reduce(trace_of(ops, mods, spans))
    got = dict(r["device_ops"])
    assert got["decode:while"] == pytest.approx(60e-9)
    assert got["decode:fusion"] == pytest.approx(40e-9)
    assert got["other:copy"] == pytest.approx(20e-9)
    assert r["busy_s"] == pytest.approx(120e-9)
    gaps = dict(r["idle_gaps"])
    # 60-90, 210-300 and 320-350 in the step's own host code, 90-100 and
    # 200-210 in the decode call, 50-60 and 350-360 in the marker alone,
    # 360-400 in the idle sleep
    assert gaps["cb.step"] == pytest.approx((30 + 90 + 30) * 1e-9)
    assert gaps["cb.run_decode"] == pytest.approx(20e-9)
    assert gaps["cb.idle"] == pytest.approx(40e-9)
    assert gaps["cb.traced"] == pytest.approx(20e-9)


def test_device_clock_ahead_of_the_host_is_shifted_back():
    # the program launched at host 100 shows on the device at 90
    ops = [("%fusion.1 = ...", 90.0, 140.0)]
    mods = [("jit__lambda(1)", 90.0, 140.0)]
    spans = [("cb.traced", 0.0, 300.0), ("cb.run_decode", 100.0, 160.0)]
    r = T.reduce(trace_of(ops, mods, spans))
    assert r["launches"] == [("cb.run_decode", pytest.approx(50e-9))]
    gaps = dict(r["idle_gaps"])
    assert gaps["cb.traced"] == pytest.approx(240e-9)       # 0-100, 160-300
    assert gaps["cb.run_decode"] == pytest.approx(10e-9)    # 150-160


def test_no_device_no_reading():
    assert T.reduce(T.Trace([], [("cb.traced", 0.0, 1.0)])) is None

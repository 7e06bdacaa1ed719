"""The readers of the program's own spans (source ``program_span``): a
known value on a hand-built recorder, nothing on an empty window, on a
window the ring lost records from, or on a program without the recorder,
and values that fit the harness's own timings on the tiny CPU cell."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for _p in (BENCH, BENCH.parent / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import time  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import pytest  # noqa: E402

from cb import bench  # noqa: E402
from repro.serving import telemetry  # noqa: E402
from test_chipbench_harness import checkout  # noqa: E402,F401

READERS = ("admit_wait_p50_ms", "plan_ms_per_step", "memplane_ms_per_step",
           "decode_launch_ms")
MS = 1e-3


def _read(name, run):
    return bench.metric_reader(name)(run)


def _hand_built(capacity=1 << 10):
    """Two engine steps between t=10 and t=10.3, each span a known length;
    one request submitted before the window and one after its close."""
    rec = telemetry.Recorder(capacity)
    put = rec.record
    put("request.submit", 9.9, 9.9, rid=9)
    put("request.queued", 9.9, 10.01, rid=9)
    for rid, t in ((0, 10.0), (1, 10.1), (2, 10.2)):
        put("request.submit", t, t, rid=rid)
    put("request.queued", 10.0, 10.004, rid=0)     # 4 ms
    put("request.queued", 10.1, 10.110, rid=1)     # 10 ms; rid 2: 100 ms
    put("engine.step", 10.01, 10.05)
    put("engine.step", 10.06, 10.09)
    put("engine.plan", 10.01, 10.01 + 1 * MS)
    put("engine.plan", 10.06, 10.06 + 3 * MS)
    put("kv.account", 10.02, 10.02 + 1 * MS)
    put("mrm.meter", 10.03, 10.03 + 2 * MS)
    put("mrm.finish", 10.04, 10.04 + 1 * MS)
    for t0, inputs, launch in ((10.02, 1, 2), (10.07, 0.5, 0.5),
                               (10.08, 1.5, 0.5)):
        rec.record("backend.decode", t0, t0 + 0.008)
        rid = rec._next_id
        rec._stack.append(rid)
        put("backend.inputs", t0, t0 + inputs * MS)
        put("backend.launch", t0 + 0.003, t0 + 0.003 + launch * MS)
        put("backend.fetch", t0 + 0.005, t0 + 0.007)
        rec._stack.pop()
    put("backend.inputs", 10.085, 10.095)           # a prefill's, not read
    put("request.submit", 10.35, 10.35, rid=3)
    return rec


WINDOW = SimpleNamespace(t_open=10.0, t_close=10.3)
KNOWN = {"admit_wait_p50_ms": 10.0, "plan_ms_per_step": 2.0,
         "memplane_ms_per_step": 2.0, "decode_launch_ms": 2.0}


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_the_known_value(monkeypatch, name):
    monkeypatch.setattr(telemetry, "_RECORDER", _hand_built())
    assert _read(name, WINDOW) == pytest.approx(KNOWN[name], abs=1e-6)


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_nothing_on_an_empty_window(monkeypatch, name):
    monkeypatch.setattr(telemetry, "_RECORDER", _hand_built())
    assert _read(name, SimpleNamespace(t_open=20.0, t_close=21.0)) is None
    monkeypatch.setattr(telemetry, "_RECORDER", telemetry.Recorder())
    assert _read(name, WINDOW) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_nothing_when_the_ring_lost_window_records(
        monkeypatch, name):
    rec = _hand_built(capacity=12)
    assert rec.dropped > 0
    monkeypatch.setattr(telemetry, "_RECORDER", rec)
    assert _read(name, WINDOW) is None
    # a later window, after every lost record, is whole
    rec.record("engine.step", 11.0, 11.01)
    rec.record("engine.plan", 11.0, 11.0 + MS)
    late = SimpleNamespace(t_open=10.5, t_close=12.0)
    assert rec.complete_since(late.t_open)
    if name == "plan_ms_per_step":
        assert _read(name, late) == pytest.approx(1.0)


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_nothing_without_the_programs_recorder(monkeypatch,
                                                           name):
    """A program older than its telemetry has no module to import."""
    monkeypatch.setitem(sys.modules, "repro.serving.telemetry", None)
    assert _read(name, WINDOW) is None


def test_readers_fit_the_harness_timings_on_the_tiny_cell(checkout):  # noqa: F811
    b = bench.load_benchmark(checkout)
    out = bench.run_cell(b, "tiny.chat", 2**31 + 11, 3.0, False,
                         time.perf_counter(), root=checkout, allow_cpu=True)
    assert out["correct"], out["checks"]
    run = out["_run"]
    got = {n: _read(n, run) for n in READERS + ("host_ms_per_step",
                                                "decode_round_ms")}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert (got["plan_ms_per_step"] + got["memplane_ms_per_step"]
            <= got["host_ms_per_step"])
    assert got["decode_launch_ms"] <= got["decode_round_ms"]

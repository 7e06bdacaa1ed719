"""In-program telemetry (DESIGN.md §3, "Telemetry"): the recorder's
nesting, window and ring; the spans a paged engine step records, where
and how many; the build and pool-growth counters; and the spans' place
on the profiler's host plane."""
import time
from collections import Counter

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.memclass import HBM3E, MRM_RRAM
from repro.core.simulator import MemorySystem
from repro.models import init_params
from repro.serving import EngineConfig, ServeEngine
from repro.serving.telemetry import Recorder, recorder

# span name -> the names of the spans it may sit directly under
NESTING = {
    "engine.step": {None},
    "engine.plan": {"engine.step"},
    "request.queued": {"engine.plan"},
    "backend.copy_rows": {"engine.plan"},
    "engine.prefill": {"engine.step"},
    "engine.decode": {"engine.step"},
    "kv.account": {"engine.prefill", "engine.decode"},
    "backend.prefill": {"engine.prefill"},
    "backend.decode": {"engine.decode"},
    "backend.inputs": {"backend.prefill", "backend.decode"},
    "backend.launch": {"backend.prefill", "backend.decode"},
    "backend.fetch": {"backend.prefill", "backend.decode"},
    "backend.build": {"backend.launch"},
    "mrm.meter": {"engine.prefill", "engine.decode"},
    "engine.emit": {"engine.decode"},
    "engine.register": {"engine.prefill"},
    "mrm.finish": {"engine.step"},
    "request.submit": {None},
}
BUILD = {"backend.build", "jax.compile"}


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------


def test_spans_nest_by_parent_id_and_take_attrs_in_the_body():
    rec = Recorder()
    with rec.span("a", k=1) as a:
        with rec.span("b") as b:
            rec.mark("m", rid=7)
        a.attrs["late"] = 2
    with rec.span("c"):
        pass
    by = {r.name: r for r in rec.spans()}
    assert by["a"].parent == 0 and by["c"].parent == 0
    assert by["b"].parent == a.id and by["m"].parent == b.id
    assert by["a"].attrs == {"k": 1, "late": 2}
    assert by["m"].t0 == by["m"].t1 and by["m"].attrs == {"rid": 7}
    assert by["a"].t0 <= by["b"].t0 <= by["m"].t0 <= by["b"].t1 <= by["a"].t1
    # records enter the ring as they close
    assert [r.name for r in rec.spans()] == ["m", "b", "a", "c"]


def test_a_span_that_raises_is_recorded_and_unwinds():
    rec = Recorder()
    with pytest.raises(ValueError):
        with rec.span("outer"):
            with rec.span("inner"):
                raise ValueError
    with rec.span("after") as s:
        pass
    assert s.parent == 0
    assert [r.name for r in rec.spans()] == ["inner", "outer", "after"]


def test_window_filter_keeps_records_inside_lo_hi():
    rec = Recorder()
    for name, t0, t1 in (("x", 1.0, 2.0), ("x", 2.5, 3.5), ("y", 3.0, 3.2),
                         ("x", 3.9, 4.1)):
        rec.record(name, t0, t1)
    assert [(r.t0, r.t1) for r in rec.spans("x", lo=1.0, hi=4.0)] == [
        (1.0, 2.0), (2.5, 3.5)]
    assert [r.name for r in rec.spans(lo=2.0, hi=4.0)] == ["x", "y"]
    assert [r.t0 for r in rec.spans("x", lo=3.0)] == [3.9]
    assert rec.spans("z") == []


def test_ring_keeps_the_newest_and_counts_what_it_dropped():
    rec = Recorder(capacity=4)
    for i in range(6):
        rec.record("s", float(i), float(i) + 0.5, i=i)
    kept = rec.spans()
    assert [r.attrs["i"] for r in kept] == [2, 3, 4, 5]
    assert rec.dropped == 2 and rec.totals()["spans_dropped"] == 2
    # the newest lost record closed at 1.5
    assert not rec.complete_since(1.5) and rec.complete_since(1.6)
    assert Recorder(capacity=4).complete_since(0.0)


def test_the_process_recorder_counts_compiles_under_the_open_span():
    rec = recorder()
    n0, s0 = rec.jax_compiles, rec.jax_compile_s
    f = jax.jit(lambda x: x * 3 + 1)
    with rec.span("outer") as outer:
        f(np.arange(5, dtype=np.float32) + 0.25).block_until_ready()
    assert rec.jax_compiles >= n0 + 1 and rec.jax_compile_s > s0
    mine = [r for r in rec.spans("jax.compile", lo=outer.t0)
            if r.parent == outer.id]
    assert mine and all(r.attrs["fun"] for r in mine)


# ---------------------------------------------------------------------------
# a tiny paged engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    full = get_config("qwen3-8b")
    cfg = reduced(full)
    return full, cfg, init_params(cfg, jax.random.key(0))


def _engine(setup, **kw):
    full, cfg, params = setup
    mem = MemorySystem({"mrm": (MRM_RRAM, 8 << 30), "hbm": (HBM3E, 8 << 30)})
    ecfg = dict(max_slots=4, page_tokens=8,
                max_cache_len=64, chunk_tokens=16, eos_token=-1)
    ecfg.update(kw)
    return ServeEngine(cfg, params, mem, EngineConfig(**ecfg),
                       account_cfg=full)


def _prompts(seed, n, head=(), length=20):
    rng = np.random.default_rng(seed)
    return [list(head) + [int(t) for t in rng.integers(2, 400, length)]
            for _ in range(n)]


def _drain(eng, prompts, max_new):
    """Submit and step to idle; returns this run's span records and
    engine.step reports."""
    t0 = time.perf_counter()
    rids = [eng.submit(p, max_new) for p in prompts]
    reports = []
    while not eng.sched.idle:
        reports.append(eng.step())
    return rids, recorder().spans(lo=t0, hi=time.perf_counter()), reports


def _children(spans):
    kids = {}
    for s in spans:
        kids.setdefault(s.parent, []).append(s)
    return kids


def test_engine_step_spans_have_the_documented_names_and_nesting(setup):
    eng = _engine(setup)
    head = _prompts(1, 1, length=12)[0]      # a shared head: radix hits
    _drain(eng, [head + [5, 6, 7]], 3)
    tails0 = eng.kv.tail_hits
    rids, spans, reports = _drain(eng, _prompts(2, 3, head=head), 5)
    by_id = {s.id: s for s in spans}
    names = Counter(s.name for s in spans)
    assert set(names) - {"jax.compile"} <= set(NESTING), names
    assert not any(n.startswith("cb.") for n in names)
    for s in spans:
        if s.name == "jax.compile":
            continue
        parent = by_id.get(s.parent)
        assert (parent.name if parent else None) in NESTING[s.name], s
    steps = [s for s in spans if s.name == "engine.step"]
    assert len(steps) == len(reports)
    # every sub-page tail hit copied its rows under the plan
    assert names["backend.copy_rows"] == eng.kv.tail_hits - tails0 > 0
    # one request.queued per admitted request, opened at its submission
    submits = {s.attrs["rid"]: s.t0 for s in spans
               if s.name == "request.submit"}
    queued = {s.attrs["rid"]: s for s in spans if s.name == "request.queued"}
    assert set(submits) == set(queued) == set(rids)
    assert all(queued[r].t0 == submits[r] <= queued[r].t1 for r in rids)


def test_one_prefill_span_per_chunk_and_one_decode_span_per_round(setup):
    eng = _engine(setup)
    chunks0 = eng.prefill_chunks_run
    rids, spans, reports = _drain(eng, _prompts(3, 3, length=37), 4)
    pre = [s for s in spans if s.name == "engine.prefill"]
    assert len(pre) == eng.prefill_chunks_run - chunks0
    assert sum(s.attrs["tokens"] for s in pre) == sum(
        r["prefill_tokens"] for r in reports)
    assert {s.attrs["rid"] for s in pre} == set(rids)
    for rid in rids:   # 37 tokens in chunks of 16: 0, 16, 32
        mine = [s for s in pre if s.attrs["rid"] == rid]
        assert [s.attrs["offset"] for s in mine] == [0, 16, 32]
        assert [s.attrs["first"] for s in mine] == [True, False, False]
    dec = [s for s in spans if s.name == "engine.decode"]
    assert len(dec) == sum(1 for r in reports if r["decode_tokens"])
    assert [s.attrs["rows"] for s in dec] == [
        r["decode_tokens"] for r in reports if r["decode_tokens"]]
    kids = _children(spans)
    for s in dec:
        (be,) = [k for k in kids[s.id] if k.name == "backend.decode"]
        assert [k.name for k in kids[be.id] if k.name not in BUILD] == [
            "backend.inputs", "backend.launch", "backend.fetch"]
        assert [k.name for k in kids[s.id]] == [
            "kv.account", "backend.decode", "mrm.meter", "engine.emit"]


def _decode_only_span_counts(setup, n_rows):
    eng = _engine(setup, max_slots=n_rows)
    _drain(eng, _prompts(4, n_rows), 6)      # builds every program
    _, spans, _ = _drain(eng, _prompts(5, n_rows), 6)
    steps = {s.id: s for s in spans if s.name == "engine.step"}
    count = Counter()

    def top(s):
        while s.parent not in steps and s.parent in by_id:
            s = by_id[s.parent]
        return s.parent

    by_id = {s.id: s for s in spans}
    for s in spans:
        if s.id not in steps and s.name not in BUILD:
            count[top(s)] += 1
    return [count[i] + 1 for i, s in steps.items()
            if s.attrs["chunks"] == 0 and s.attrs["rows"] == n_rows]


def test_spans_per_step_do_not_grow_with_decode_rows(setup):
    one = _decode_only_span_counts(setup, 1)
    four = _decode_only_span_counts(setup, 4)
    assert one and four
    assert max(four) == max(one) and min(four) == min(one)
    assert max(four) <= 30


def test_builds_count_each_new_key_once(setup):
    eng = _engine(setup, prefix_caching=False)
    prompts = _prompts(6, 2, length=20)
    _, spans, _ = _drain(eng, prompts, 4)
    builds = [s for s in spans if s.name == "backend.build"]
    keys = [(s.attrs["kind"], s.attrs["length"], s.attrs["width"])
            for s in builds]
    assert keys and len(set(keys)) == len(keys)
    tel = eng.report()["telemetry"]
    for kind in ("first", "extend", "decode"):
        mine = [s for s in builds if s.attrs["kind"] == kind]
        assert tel["builds"][kind]["count"] == len(mine) >= 1
        assert tel["builds"][kind]["seconds"] >= sum(
            s.t1 - s.t0 for s in mine) * 0.99
    # the same work again builds nothing
    _, spans, _ = _drain(eng, prompts, 4)
    assert not [s for s in spans if s.name == "backend.build"]
    assert eng.report()["telemetry"]["builds"] == tel["builds"]


def test_a_pool_growth_is_counted_and_every_program_builds_again(setup):
    eng = _engine(setup, prefix_caching=False)
    prompts = _prompts(7, 2, length=20)
    _drain(eng, prompts, 4)
    tel = eng.report()["telemetry"]
    assert tel["pool_grows"] == 0
    pages = tel["pool_pages"]
    eng.backend._grow_pool()
    tel = eng.report()["telemetry"]
    assert tel["pool_grows"] == 1 and tel["pool_pages"] == 2 * pages
    _, spans, _ = _drain(eng, prompts, 4)
    kinds = {s.attrs["kind"] for s in spans if s.name == "backend.build"}
    assert kinds == {"first", "extend", "decode"}


def test_report_carries_the_telemetry_counters(setup):
    eng = _engine(setup)
    _drain(eng, _prompts(8, 1), 2)
    tel = eng.report()["telemetry"]
    assert set(tel) == {"builds", "pool_grows", "pool_pages", "pool_copies",
                        "kv_pages_gathered", "kv_pages_attended",
                        "attention_kernel_launches", "spans_dropped",
                        "jax_compiles", "jax_compile_s"}
    assert tel["spans_dropped"] == recorder().dropped
    assert tel["jax_compiles"] == recorder().jax_compiles


def test_kv_page_counts_match_the_hand_reckoning():
    """A multi-head engine (Hq = Hkv, 4 attention layers, 8-token pages)
    runs a 20-token prompt as a first chunk of 16 and an extend of 4, then
    one decode round. The attention gathers rows × table width × layers
    pages; the keys occupy sum(ceil(keys / 8)) × layers. Each launch's
    span carries its own two counts."""
    full = get_config("deepseek-7b")
    cfg = reduced(full)
    assert cfg.n_heads == cfg.n_kv_heads and not cfg.qk_norm
    eng = _engine((full, cfg, init_params(cfg, jax.random.key(1))))
    L = cfg.num_layers
    t0 = time.perf_counter()
    eng.submit(_prompts(10, 1, length=20)[0], 8)
    while eng.backend.kv_pages_gathered["decode"] == 0:
        eng.step()
    tel = eng.report()["telemetry"]
    # first: 16 rows, table of 2 pages; extend: 4 rows at offset 16, 3
    # pages of keys in a table of 4; decode: B = 4 rows over a table of
    # 4, one row of 21 keys (positions 0..20)
    assert tel["kv_pages_gathered"] == {"first": 16 * 2 * L,
                                        "extend": 4 * 4 * L,
                                        "decode": 4 * 4 * L}
    assert tel["kv_pages_attended"] == {"first": 2 * L, "extend": 3 * L,
                                        "decode": 3 * L}
    spans = recorder().spans(lo=t0)
    for name, kinds in (("backend.prefill", ("first", "extend")),
                        ("backend.decode", ("decode",))):
        mine = [s.attrs for s in spans if s.name == name]
        for key in ("kv_pages_gathered", "kv_pages_attended"):
            assert sum(a[key] for a in mine) == sum(tel[key][k]
                                                    for k in kinds)
        assert {a["path"] for a in mine} == {"rows"}
    assert tel["attention_kernel_launches"] == {"first": 0, "extend": 0,
                                                "decode": 0}


def test_kv_page_counts_on_the_kernel_path():
    """The same engine and requests with the paged steps attending
    through the ragged kernel (interpret mode here), as they do on the
    chip: each launch counts the grid's page DMAs
    (``kernel.pages_gathered``), so with every table slot live up to the
    keys and one query tile per chunk the gathered pages equal the
    attended ones; the idle decode rows gather nothing. One kernel call
    per attention layer per launch; the tokens are the rows form's."""
    from repro.kernels.paged_attention.kernel import pages_gathered

    full = get_config("deepseek-7b")
    cfg = reduced(full)
    params = init_params(cfg, jax.random.key(1))
    L = cfg.num_layers
    outs = []
    for kernel in (True, False):
        eng = _engine((full, cfg, params))
        eng.backend.attn_kernel = kernel
        t0 = time.perf_counter()
        rid = eng.submit(_prompts(10, 1, length=20)[0], 8)
        while eng.backend.kv_pages_gathered["decode"] == 0:
            eng.step()
        if kernel:
            tel = eng.report()["telemetry"]
            spans = recorder().spans(lo=t0)
        while not eng.sched.idle:
            eng.step()
        outs.append(list(eng.outputs[rid]))
    assert tel["kv_pages_gathered"] == {"first": 2 * L, "extend": 3 * L,
                                        "decode": 3 * L}
    assert tel["kv_pages_gathered"] == tel["kv_pages_attended"]
    assert tel["attention_kernel_launches"] == {"first": L, "extend": L,
                                                "decode": L}
    # the decode round by hand: the one live row's 3 pages of 21 keys in
    # a 4-slot table, three idle rows
    tbl = np.zeros((4, 4), np.int32)
    tbl[0, :3] = [1, 2, 3]
    assert pages_gathered(tbl, np.arange(5), [21, 0, 0, 0], page_size=8,
                          max_q_len=1) == 3
    assert {s.attrs["path"] for s in spans
            if s.name in ("backend.prefill", "backend.decode")} == {"kernel"}
    assert outs[0] == outs[1] and len(outs[0]) == 8


# ---------------------------------------------------------------------------
# the profiler's host plane
# ---------------------------------------------------------------------------


def test_spans_lie_on_the_profilers_host_plane(setup, tmp_path):
    """Under a profile, each span of two engine steps is an event of the
    same name on the host plane, as long as its in-memory record."""
    from jax.profiler import ProfileData

    eng = _engine(setup, prefix_caching=False)
    prompts = _prompts(9, 2)
    _drain(eng, prompts, 4)                  # build outside the trace
    for p in prompts:
        eng.submit(p, 4)
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
        eng.step()
    finally:
        jax.profiler.stop_trace()
    mine = [s for s in recorder().spans(lo=t0)
            if s.t1 > s.t0 and s.name in NESTING
            and s.name != "request.queued"]
    assert {s.name for s in mine} >= {"engine.step", "engine.plan",
                                      "engine.prefill", "backend.launch"}
    (path,) = list(tmp_path.rglob("*.xplane.pb"))
    host = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in NESTING:
                        host.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.duration_ns * 1e-9))
    for name in {s.name for s in mine}:
        recs = sorted(s.t1 - s.t0 for s in mine if s.name == name)
        evs = sorted(d for _, d in host.get(name, []))
        assert len(evs) == len(recs), name
        for d_mem, d_trace in zip(recs, evs):
            assert abs(d_trace - d_mem) <= 1e-3 + 0.1 * d_mem, name


@pytest.mark.parametrize("kernel,builds", [(True, 1), (False, 2)],
                         ids=["kernel", "rows"])
def test_kernel_path_shares_one_program_across_table_widths(kernel, builds):
    """Decode rounds over page tables 4 and 8 slots wide: on the kernel
    path both run one program (tables reach it padded to the kernel's
    width quantum, and the kernel reads no slot past a sequence's keys);
    the rows form reads every slot, so each width builds its own."""
    full = get_config("deepseek-7b")
    cfg = reduced(full)
    eng = _engine((full, cfg, init_params(cfg, jax.random.key(1))))
    eng.backend.attn_kernel = kernel
    for width in (4, 8):
        eng.backend.run_decode([], page_tables=np.zeros((4, width),
                                                        np.int32))
    assert eng.report()["telemetry"]["builds"]["decode"]["count"] == builds

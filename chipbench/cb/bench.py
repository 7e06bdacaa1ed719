"""One run of one cell: set-up, the measured window, the check, the result.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name: ``BENCHMARK.json`` names the cell's configuration and mix; the
configuration is ``chipbench/configs/<config>.json``; the mix is
``chipbench/traffic/<mix>.json``; each metric is read by
``chipbench/metrics/<metric>.py``. Adding any of them adds files only.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

import numpy as np

from cb import openloop, traffic
from cb.peaks import peaks_for
from cb.work import Work

BENCH_DIR = Path(__file__).resolve().parents[1]       # chipbench/
ROOT = BENCH_DIR.parent
TRACE_TAIL_S = 3.0     # the traced slice: the last seconds of the window


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def config_spec(bench: dict, name: str, root: Path = ROOT) -> dict:
    entry = find(bench["configs"], name, "configuration")
    return json.loads((root / entry["file"]).read_text())


def mix_path(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "traffic" / f"{name}.json"


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "cb_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metric entries this cell reports in a run of this kind."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
# compile counting
# ---------------------------------------------------------------------------


class CompileCounter:
    """Programs JAX traces (each new jitted function or shape, whether its
    executable is then compiled or loaded from the persistent cache)."""

    _instance = None

    def __init__(self):
        import jax
        self.traced = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.endswith("jaxpr_trace_duration"):
            self.traced += 1
        elif event.endswith("backend_compile_duration"):
            self.compile_s += duration

    @classmethod
    def get(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance


# ---------------------------------------------------------------------------
# what the metric readers see
# ---------------------------------------------------------------------------


@dataclass
class Run:
    setup_s: float
    requests: list                 # traffic.Request, due on the host clock
    t_open: float
    t_close: float
    log: openloop.Log
    counters: dict                 # program counters: change over window
    window_compiles: int
    work: Work
    peaks: dict
    chips: int = 1
    trace: Optional[dict] = None   # cb.trace.reduce() of the traced slice
    traced: Optional[tuple] = None  # the traced slice on the host clock

    def launches(self, kind=None, lo=None, hi=None):
        lo = self.t_open if lo is None else lo
        hi = self.t_close if hi is None else hi
        return [x for x in self.log.launches
                if lo <= x.t0 and x.t1 <= hi
                and (kind is None or x.kind in kind)]

    def traced_launches(self):
        """Launches of the traced slice paired with their device seconds
        (from the end, as the trace reduction pairs them), or None when
        the two lists disagree on the kind of any launch."""
        if not self.trace or not self.traced:
            return None
        mine = self.launches(lo=self.traced[0], hi=self.traced[1])
        dev = self.trace["launches"]
        n = min(len(mine), len(dev))
        if n == 0:
            return None
        pairs = list(zip(mine[len(mine) - n:], dev[len(dev) - n:]))
        for launch, (name, _) in pairs:
            kind = name.split(":", 1)[-1] if ":" in name else "decode"
            if kind != launch.kind:
                return None
        return [(launch, sec) for launch, (_, sec) in pairs]


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def program_fields(spec: dict) -> dict:
    """The configuration file's sizes under the program's field names."""
    return {"num_layers": spec["num_hidden_layers"],
            "d_model": spec["hidden_size"],
            "n_heads": spec["num_attention_heads"],
            "n_kv_heads": spec["num_key_value_heads"],
            "head_dim": spec["head_dim"],
            "d_ff": spec["intermediate_size"],
            "vocab_size": spec["vocab_size"],
            "qk_norm": bool(spec.get("qk_norm", False)),
            "tie_embeddings": bool(spec["tie_word_embeddings"]),
            "rope_theta": float(spec["rope_theta"]),
            "norm_eps": float(spec["rms_norm_eps"])}


def program_config(spec: dict):
    """The program's registered ``spec["arch"]`` with the configuration
    file's sizes: the file is what runs."""
    import dataclasses

    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(spec["arch"]), **program_fields(spec))
    cfg.validate()
    return cfg


def build_engine(cfg, spec: dict, params):
    from repro.core.memclass import get_technology
    from repro.core.simulator import MemorySystem
    from repro.serving import EngineConfig, ServeEngine

    serve = spec["serve"]
    ecfg = EngineConfig(**serve["engine"])
    kv_bytes_token = (2 * cfg.n_kv_heads * cfg.resolved_head_dim * 2
                      * cfg.num_layers)
    tiers = {}
    for name, t in serve["tiers"].items():
        if "kv_pages" in t:
            # room for kv_pages full pages below the eviction watermark
            cap = (t["kv_pages"] * ecfg.page_tokens * kv_bytes_token
                   / ecfg.kv_high_watermark)
        else:
            cap = t["gib"] * 2**30
        tiers[name] = (get_technology(t["tech"]), int(cap))
    mem = MemorySystem(tiers, ecc_profile=serve.get("ecc_profile", "off"))
    eng = ServeEngine(cfg, params, mem, ecfg, account_cfg=cfg)
    if not eng.paged:
        raise ValueError("the engine is not on the paged compute plane")
    return eng


def pool_pages(eng) -> int:
    import jax
    return int(jax.tree.leaves(eng.backend.paged_caches)[0].shape[1])


def prefill_shapes(eng, sched: traffic.Schedule) -> set:
    """(kind, chunk length, page-table width) of every prefill program the
    schedule can launch. A prompt behind a shared prefix resumes at the
    prefix's end (sub-page tail copied) or, when no registered prompt holds
    the prefix's last partial page, at its last page boundary; a prompt
    with no prefix starts cold. Chunk sizes are the engine's own plan."""
    pt, be = eng.ecfg.page_tokens, eng.backend
    shapes = set()
    for r in sched.requests:
        L = len(r.tokens)
        _, chunk = eng._chunk_plan(np.asarray(r.tokens, np.int32))
        starts = {0}
        if r.prefix is not None:
            p = len(sched.prefixes[r.prefix])
            starts = {p, p // pt * pt}
        for done in starts:
            while done < L:
                n = min(chunk, L - done)
                shapes.add(("first" if done == 0 else "extend", n,
                            be.table_width(-(-(done + n) // pt))))
                done += n
    return shapes


def warm_up(eng, sched: traffic.Schedule) -> None:
    """Compile, before the window, every program the window can use:
    each prefill shape of ``prefill_shapes`` and each decode table width
    from the shortest context to the longest. Each runs once on null page
    tables (page 0, which attention masks everywhere) as a chunk that
    does not end its prompt, so no request's state moves."""
    from repro.serving.engine import PrefillChunk

    be, pt = eng.backend, eng.ecfg.page_tokens
    for kind, n, w in sorted(prefill_shapes(eng, sched)):
        ck = PrefillChunk(slot=0, request_id=-1,
                          tokens=np.full(n, 2, np.int32),
                          offset=0 if kind == "first" else pt,
                          first=kind == "first", last=False)
        be.run_prefill_chunk(ck, page_table=np.zeros((w,), np.int32))
        block(be.paged_caches)      # one pool copy in flight at a time
    lo = min(len(r.tokens) for r in sched.requests) + 1
    hi = max(len(r.tokens) + r.max_new for r in sched.requests)
    w, widths = be.table_width(-(-lo // pt)), []
    while w <= be.table_width(-(-hi // pt)):
        widths.append(w)
        w *= 2
    for w in widths:
        openloop.warm_decode_widths(eng, [w])
        block(be.paged_caches)


def block(tree) -> None:
    import jax
    jax.block_until_ready(tree)


def set_up(cfg, spec, sched, seed: int):
    """Weights, engine, the mix's shared prefixes in the radix tree, and
    every program compiled. Returns the engine and the seconds each part
    took."""
    from repro.models import transformer as tfm

    from cb import weights

    marks = [("start", time.perf_counter())]
    params = weights.make_program_params(cfg, spec, seed,
                                         tfm.abstract_params(cfg))
    block(params)
    marks.append(("weights", time.perf_counter()))
    eng = build_engine(cfg, spec, params)
    marks.append(("engine", time.perf_counter()))
    # the mix's shared prefixes, then each prefix with a probe after it
    # (registers the page holding the prefix's partial last page), then
    # one hit on each (runs the sub-page tail copy once)
    rng = np.random.default_rng([seed % 2**64, 1])
    hits = [p + [int(t) for t in rng.integers(2, cfg.vocab_size,
                                              eng.ecfg.page_tokens)]
            for p in sched.prefixes] if sched.tail_probes else []
    # and one short request of its own, so the first-token and decode
    # sampling run before the window in every mix
    own = [[int(t) for t in rng.integers(2, cfg.vocab_size,
                                         eng.ecfg.page_tokens)]]
    for group in (sched.prefixes, sched.tail_probes, hits, own):
        for toks in group:
            eng.submit(toks, max_new_tokens=2)
        eng.run_until_idle(max_steps=100000)
    marks.append(("prefixes", time.perf_counter()))
    warm_up(eng, sched)
    marks.append(("warm_up", time.perf_counter()))
    return eng, {b[0]: round(b[1] - a[1], 3)
                 for a, b in zip(marks, marks[1:])}


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------


def sample_finished(requests, t_close: float, eng, want_tokens: int,
                    max_requests: int, seed: int) -> list:
    """(prompt, served tokens) of a seeded sample of the requests finished
    by the close: the one with the most served tokens, then others in an
    order drawn from the seed until ``want_tokens`` are covered."""
    done = [r for r in requests if r.rid is not None
            and len(r.token_times) >= r.max_new
            and r.token_times[-1] <= t_close]
    if not done:
        return []
    rng = np.random.default_rng([seed % 2**64, 2])
    longest = max(done, key=lambda r: (r.max_new, len(r.tokens)))
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    out, n = [], 0
    for r in [longest] + rest:
        if len(out) >= max_requests or n >= want_tokens:
            break
        served = list(eng.outputs[r.rid][:r.max_new])
        out.append((list(r.tokens), served))
        n += len(served)
    return out


def logit_gaps(spec: dict, seed: int, samples, control: bool = False):
    """At every served position of each sample, the gap by which the
    served token's reference logit lies below the reference's best logit
    there: {"program": [gaps of sample 0, ...]}. With ``control`` also the
    gaps of the tokens the fp8 control forward puts first at the same
    positions ({"control": [...]}): the tokens the control would serve in
    the program's place."""
    from cb import reference, weights

    w = weights.make_canonical(spec, seed)
    out = {"program": [], "control": []}
    for prompt, served in samples:
        seq = prompt + served[:-1]
        at = list(range(len(prompt) - 1, len(seq)))
        ref = reference.logits_at(spec, w, seq, at)
        best, rows = ref.max(axis=-1), np.arange(len(at))
        out["program"].append(best - ref[rows, np.asarray(served)])
        if control:
            first = np.argmax(reference.logits_at(spec, w, seq, at,
                                                  control=True), axis=-1)
            out["control"].append(best - ref[rows, first])
    del w
    return {k: v for k, v in out.items() if v}


def gap_summary(per_sample) -> dict:
    g = np.concatenate(per_sample)
    return {"max": float(np.max(g)), "mean": float(np.mean(g)),
            "share_not_first": float(np.mean(g > 0)), "n": int(len(g))}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def devices_for(chips: int, allow_cpu: bool = False):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no device: {e}") from e
    if devs[0].platform == "cpu" and not allow_cpu:
        raise NoChip(f"no accelerator: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"{chips} chips asked for, JAX finds {len(devs)}")
    return devs


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, root: Path = ROOT,
             allow_cpu: bool = False, fault: Optional[str] = None,
             rate: Optional[float] = None, control: bool = False) -> dict:
    """One run. ``root`` is the checkout holding BENCHMARK.json and
    chipbench/. The rest serve calibration and the harness's own tests,
    never a benchmark run: ``allow_cpu`` (a CPU run), ``fault`` (one of
    ``FAULTS``, planted under the timed path), ``rate`` (another arrival
    rate, for the knee sweep) and ``control`` (the fp8 control in the
    program's place: its first tokens at the served positions are what
    the check judges; the program's gaps are still read, for the
    diagnostics line)."""
    bench_dir = root / "chipbench"
    cell = find(bench["workloads"], cell_name, "workload")
    devs = devices_for(cell["chips"], allow_cpu)
    kind = devs[0].device_kind
    peaks = peaks_for(kind) if not allow_cpu else {"bf16_flops": 1.0,
                                                   "hbm_bytes_per_s": 1.0}
    spec = config_spec(bench, cell["config"], root)
    cfg = program_config(spec)
    mix = traffic.load_mix(mix_path(cell["traffic"], bench_dir))
    if rate is not None:
        mix["arrivals"]["rate_per_s"] = rate
    sched = traffic.build(mix, seed, seconds, cfg.vocab_size)
    counter = CompileCounter.get()

    eng, parts = set_up(cfg, spec, sched, seed)
    diag = {"setup_parts_s": parts,
            "compile_s_in_setup": round(counter.compile_s, 3)}
    if fault:
        FAULTS[fault](eng)
    log = openloop.Log()
    openloop.instrument(eng, log)
    before = _counters(eng)
    pages0 = pool_pages(eng)
    traced0 = counter.traced
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="cb_trace_") if trace else None
    t_open, t_close, traced = openloop.run_window(
        eng, sched.requests, seconds, log,
        trace_tail_s=min(TRACE_TAIL_S, seconds / 2), trace_dir=trace_dir)
    window_compiles = counter.traced - traced0
    after = _counters(eng)
    pages1 = pool_pages(eng)
    for r in sched.requests:
        r.due = t_open + r.due
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:cell["chips"]])

    reduced = None
    if trace:
        from cb import trace as cbtrace
        files = list(Path(trace_dir).rglob("*.xplane.pb"))
        t_red = time.perf_counter()
        if files:
            reduced = cbtrace.reduce(cbtrace.load(str(files[0])))
        diag["trace_reduce_s"] = round(time.perf_counter() - t_red, 3)
        shutil.rmtree(trace_dir, ignore_errors=True)
    counters = {k: after[k] - before[k] for k in after}
    diag.update(window_compiles=window_compiles, pool_pages=[pages0, pages1],
                counters=counters,
                launches=len(log.launches), steps=len(log.steps),
                window_split_s=window_split(log, t_open, t_close))

    check = spec["check"]
    samples = sample_finished(sched.requests, t_close, eng,
                              check["sample_tokens"], check["max_requests"],
                              seed)
    attempted = sum(1 for r in sched.requests if r.rid is not None)
    run = Run(setup_s=setup_s, requests=sched.requests, t_open=t_open,
              t_close=t_close, log=log, counters=counters,
              window_compiles=window_compiles, work=Work(spec), peaks=peaks,
              chips=cell["chips"], trace=reduced, traced=traced)

    # free the program's state before the reference runs on the chip
    del eng
    gc.collect()
    t_ref = time.perf_counter()
    vocab_ok = all(0 <= t < cfg.vocab_size for _, s in samples for t in s)
    gaps = (logit_gaps(spec, seed, samples, control=control)
            if samples and vocab_ok else {})
    diag["reference_s"] = round(time.perf_counter() - t_ref, 3)
    for k, g in gaps.items():
        diag[k] = gap_summary(g)
    print("diag " + json.dumps(diag), file=sys.stderr, flush=True)
    limit = check["max_logit_gap"]
    judged = gaps.get("control" if control else "program", [])
    widest = [float(np.max(g)) for g in judged]
    failed = (sum(1 for x in widest if not x <= limit)
              + (0 if vocab_ok else 1))
    correct = bool(samples) and vocab_ok and failed == 0

    metrics = {}
    for m in cell_metrics(bench, cell_name, trace):
        value = metric_reader(m["name"], bench_dir)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace and reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = {
        "logit_gap_max": {"value": max(widest, default=None), "limit": limit},
        "requests_compared": {"value": len(samples), "limit": 1},
        "tokens_compared": {"value": sum(len(s) for _, s in samples),
                            "limit": 1},
        "tokens_outside_vocab": {"value": 0 if vocab_ok else 1, "limit": 0},
    }
    out["_run"] = run
    out["_diag"] = diag
    return out


def window_split(log: openloop.Log, t_open: float, t_close: float) -> dict:
    """Where the window's wall time went on the host: inside engine steps,
    inside the ComputeBackend calls of those steps (decode rounds apart),
    and the rest of the steps (engine host code). What is left of the
    window is the client's: submitting, stamping tokens, idle sleep."""
    steps = [s for s in log.steps if t_open <= s.t0 and s.t1 <= t_close]
    calls = [x for x in log.launches if t_open <= x.t0 and x.t1 <= t_close]
    step_s = sum(s.t1 - s.t0 for s in steps)
    backend_s = sum(x.t1 - x.t0 for x in calls)
    decode_s = sum(x.t1 - x.t0 for x in calls if x.kind == "decode")
    return {"steps": len(steps), "step_s": round(step_s, 3),
            "decode_s": round(decode_s, 3),
            "prefill_s": round(backend_s - decode_s, 3),
            "engine_host_s": round(step_s - backend_s, 3),
            "client_s": round(t_close - t_open - step_s, 3)}


def _counters(eng) -> dict:
    return {"prefill_tokens_skipped": eng.prefill_tokens_skipped,
            "prefill_tokens_computed": eng.prefill_tokens_computed,
            "prefix_hits": eng.kv.prefix_hits,
            "dropped_allocs": eng.kv.dropped_allocs,
            "tail_hits": eng.kv.tail_hits}


def _alter_tokens(eng) -> None:
    """Every token the engine samples (first tokens and decode rounds) is
    altered where it is produced."""
    be = eng.backend
    sample = be.sample

    def altered(logits):
        return (sample(logits) + 1) % eng.cfg.vocab_size
    be.sample = altered


def _keep_state(eng) -> None:
    """Every decode round returns the KV page pool unchanged: the keys and
    values of decoded tokens are never written."""
    be = eng.backend
    decode = be.run_decode

    def unchanged(*args, **kw):
        pool = be.paged_caches
        out = decode(*args, **kw)
        be.paged_caches = pool
        return out
    be.run_decode = unchanged


# faults the check's own tests plant under the timed path
FAULTS = {"token_altered": _alter_tokens, "state_unchanged": _keep_state}


# ---------------------------------------------------------------------------


def configure_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where JAX_COMPILATION_CACHE_DIR points), every program cached."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT /
                                                               ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def print_result(out: dict) -> None:
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    line = {k: v for k, v in out.items() if not k.startswith("_")}
    print(json.dumps(line), flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_start: float) -> int:
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chipbench: the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        bench = load_benchmark()
        configure_cache()
        out = run_cell(bench, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    except Exception:  # noqa: BLE001 - any failure ends the run without a result
        traceback.print_exc()
        return 1
    print_result(out)
    return 0

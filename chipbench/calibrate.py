#!/usr/bin/env python3
"""Calibration runs of one cell, several in one process (not a benchmark
run; its output sets the cell's rate and its check's limit).

    # the knee: the cell's traffic at each rate, one window each
    python chipbench/calibrate.py --workload W --seconds 20 --rates 4 6 8
    # the check's readings: the fp8 control in the program's place
    # (``correct`` is the control's), with the program's own gaps over
    # the same served tokens beside it, one window per seed
    python chipbench/calibrate.py --workload W --seconds 20 --seeds 1 2 3

Each window prints one JSON line; everything runs in this one process.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from cb import bench  # noqa: E402
from cb.stats import (itl_samples, percentile, tpot_samples,  # noqa: E402
                      ttft_samples)


def summary(out: dict) -> dict:
    run = out["_run"]
    reqs = run.requests
    due = [r for r in reqs if r.due < run.t_close]
    done = [r for r in due if len(r.token_times) >= r.max_new]
    ttft = ttft_samples(reqs, run.t_close)
    itl = itl_samples(reqs, run.t_open, run.t_close)
    tpot = tpot_samples(reqs, run.t_open, run.t_close)
    toks = sum(1 for r in reqs for t in r.token_times
               if run.t_open <= t <= run.t_close)
    return {"due": len(due), "finished": len(done),
            "waiting_at_close": sum(1 for r in due if not r.token_times),
            "ttft_p50_ms": percentile(ttft, 50) * 1e3 if ttft else None,
            "ttft_p95_ms": percentile(ttft, 95) * 1e3 if ttft else None,
            "tpot_p50_ms": percentile(tpot, 50) * 1e3 if tpot else None,
            "itl_p50_ms": percentile(itl, 50) * 1e3 if itl else None,
            "itl_p90_ms": percentile(itl, 90) * 1e3 if itl else None,
            "itl_p95_ms": percentile(itl, 95) * 1e3 if itl else None,
            "itl_p99_ms": percentile(itl, 99) * 1e3 if itl else None,
            "output_tokens_per_s": toks / (run.t_close - run.t_open),
            "setup_s": run.setup_s}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--control", action="store_true",
                    help="put the fp8 control in the program's place in "
                         "every window")
    a = ap.parse_args()
    sys.path.insert(0, str(bench.ROOT / "src"))
    bench.configure_cache()
    b = bench.load_benchmark()
    runs = ([dict(rate=r, seed=a.seed) for r in a.rates]
            + [dict(rate=None, seed=s) for s in a.seeds])
    for kw in runs:
        out = bench.run_cell(b, a.workload, kw["seed"], a.seconds, False,
                             time.perf_counter(), rate=kw["rate"],
                             control=a.control or not a.rates)
        line = {"workload": a.workload, **kw, "correct": out["correct"],
                "limit": out["checks"]["logit_gap_max"]["limit"],
                **summary(out), "program_gaps": out["_diag"].get("program"),
                "control_gaps": out["_diag"].get("control"),
                "window_compiles": out["_run"].window_compiles,
                "counters": out["_run"].counters,
                "pool_pages": out["_diag"]["pool_pages"],
                "window_split_s": out["_diag"]["window_split_s"],
                "memory_peak_bytes": out["device"]["memory_peak_bytes"]}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The harness end to end on the CPU, at a tiny size, with the look for a
chip skipped: a cell, a traffic mix and a metric that exist only in a
temporary checkout are found by name; a sound run is correct and
compiles nothing in its window; a run with a fault planted under the
timed path, or with the fp8 control in the program's place, is not."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for _p in (BENCH, BENCH.parent / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import json  # noqa: E402
import shutil  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402

from cb import bench  # noqa: E402

TINY = dict(name="tiny", hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=128,
            vocab_size=4096, num_hidden_layers=2)
MIX = {"arrivals": {"process": "poisson", "rate_per_s": 4.0},
       "prefixes": {"count": 2, "tokens": {"dist": "const", "value": 20},
                    "zipf_s": 1.0, "tail_probe_tokens": 8},
       "body": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 8,
                "max": 24, "round_to": 8},
       "answer": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 4,
                  "max": 10}}
METRIC = '''"""Requests that received every token inside the window."""


def read(run):
    return float(sum(1 for r in run.requests
                     if len(r.token_times) >= r.max_new
                     and r.token_times[-1] <= run.t_close))
'''


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout whose cell, mix, configuration and one metric are new
    files beside copies of the existing ones, none of which is edited."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((BENCH / "configs" / "qwen3-8b.d16.json").read_text())
    spec.update(TINY)
    spec["serve"]["engine"].update(max_slots=4, page_tokens=8,
                                   max_cache_len=128, chunk_tokens=16)
    spec["serve"]["tiers"]["mrm"]["kv_pages"] = 40
    spec["check"] = {"max_logit_gap": 0.02,
                     "sample_tokens": 32, "max_requests": 3}
    (root / "chipbench/configs/tiny.json").write_text(json.dumps(spec))
    (root / "chipbench/traffic/tiny-chat.json").write_text(json.dumps(MIX))
    (root / "chipbench/metrics/requests_done.py").write_text(METRIC)
    b = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny", "source": "test",
                         "file": "chipbench/configs/tiny.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "tiny.chat", "config": "tiny",
                           "traffic": "tiny-chat", "chips": 1,
                           "why": "test"})
    b["end_to_end"].append({"name": "requests_done", "unit": "count",
                            "better": "higher", "bound": 0.25,
                            "source": "host_clock",
                            "workloads": ["tiny.chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


def run(root, seed, **kw):
    b = bench.load_benchmark(root)
    return bench.run_cell(b, "tiny.chat", seed, 3.0, False,
                          time.perf_counter(), root=root, allow_cpu=True,
                          **kw)


def test_new_files_are_found_by_name(checkout):
    b = bench.load_benchmark(checkout)
    spec = bench.config_spec(b, "tiny", checkout)
    assert spec["hidden_size"] == 64
    assert bench.mix_path("tiny-chat", checkout / "chipbench").is_file()
    names = [m["name"] for m in bench.cell_metrics(b, "tiny.chat", False)]
    assert "requests_done" in names and "ttft_p90_ms" not in names
    assert "requests_done" not in [
        m["name"] for m in bench.cell_metrics(b, "qwen3-8b.sysprompt-chat",
                                              False)]
    assert callable(bench.metric_reader("requests_done",
                                        checkout / "chipbench"))


def test_sound_run_is_correct_and_compiles_nothing_in_its_window(checkout):
    out = run(checkout, 2**31 + 5)
    assert out["correct"], out["checks"]
    assert out["_run"].window_compiles == 0
    assert out["_run"].counters["tail_hits"] > 0
    m = out["metrics"]
    assert m["requests_done"]["value"] >= 1
    # the end-to-end metrics with no workloads key, and its own
    b = bench.load_benchmark(checkout)
    assert set(m) == {e["name"] for e in b["end_to_end"]
                      if "workloads" not in e} | {"requests_done"}
    assert list(out)[-3:] == ["checks", "_run", "_diag"]


@pytest.mark.parametrize("fault", sorted(bench.FAULTS))
def test_planted_fault_is_not_correct(checkout, fault):
    out = run(checkout, 7, fault=fault)
    assert not out["correct"]
    c = out["checks"]["logit_gap_max"]
    assert c["value"] > c["limit"] and out["failed"] >= 1


def test_fp8_control_in_the_programs_place_is_not_correct(checkout):
    """The control serves, at each position of the same prompts and
    tokens, the token the fp8 reference puts first. The check judges
    those tokens, and finds them not correct, where the program's own
    tokens of the same run are within the limit."""
    out = run(checkout, 2**31 + 9, control=True)
    c = out["checks"]["logit_gap_max"]
    assert not out["correct"]
    assert c["value"] > c["limit"] and out["failed"] >= 1
    assert c["value"] == out["_diag"]["control"]["max"]
    assert out["_diag"]["program"]["max"] <= c["limit"]


def test_no_chip_means_no_run(checkout):
    b = bench.load_benchmark(checkout)
    with pytest.raises(bench.NoChip):
        bench.run_cell(b, "tiny.chat", 1, 1.0, False, time.perf_counter(),
                       root=checkout)


def test_a_cells_chips_are_checked_against_the_devices():
    """On 4 virtual CPU devices a cell asking for 4 chips finds them and
    one asking for 8 does not (a process of its own: the device count is
    fixed when JAX starts)."""
    import os
    import subprocess

    code = ("import sys; sys.path[:0] = sys.argv[1:]\n"
            "from cb import bench\n"
            "assert len(bench.devices_for(4, allow_cpu=True)) == 4\n"
            "try:\n"
            "    bench.devices_for(8, allow_cpu=True)\n"
            "except bench.NoChip:\n"
            "    print('ok')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code, str(BENCH),
                        str(BENCH.parent / "src")], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.stdout.strip() == "ok", p.stderr


def test_benchmark_alone_prints_no_result(tmp_path):
    """In a directory holding only BENCHMARK.json and chipbench/ (no
    system under test) the command fails and prints nothing."""
    import subprocess

    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "qwen3-8b.sysprompt-chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def test_cpu_only_exits_before_measuring():
    import os
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "qwen3-8b.sysprompt-chat", "--seed", "0",
                        "--seconds", "10", "--trace", "0"],
                       cwd=BENCH.parent, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
    assert "no accelerator" in p.stderr

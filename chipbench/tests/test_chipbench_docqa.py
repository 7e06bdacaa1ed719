"""The served path of both attention families against ``cb/reference.py``
on the CPU, under a mini document Q&A mix: grouped heads with qk-norm
(qwen3, Hq = 2 Hkv) and plain multi-head attention (deepseek, Hq = Hkv,
no qk-norm), each at a tiny size with 8-token pages. Two shared
documents end mid-page, so every question extends after a sub-page tail
copy, and every answer decodes 16 tokens or more through the paged
cache. A sound run is correct and compiles nothing in its window; a
planted fault, or the fp8 control in the program's place, is not.

The configurations and the cells live only in a temporary checkout,
beside copies of the committed files, none of which is edited. The
readers of the KV gather counts read the program's spans of a recorded
window, and nothing where the program has no such counts."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for _p in (BENCH, BENCH.parent / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import json  # noqa: E402
import shutil  # noqa: E402
import time  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import pytest  # noqa: E402

from cb import bench  # noqa: E402
from repro.serving import telemetry  # noqa: E402

TINY = dict(hidden_size=64, head_dim=16, intermediate_size=128,
            vocab_size=4096, num_hidden_layers=2)
# family -> (committed configuration, query and KV heads)
FAMILIES = {"qwen3": ("qwen3-8b.d16.json", 4, 2),
            "deepseek": ("deepseek-7b.d8.json", 4, 4)}
MIX = {"arrivals": {"process": "poisson", "rate_per_s": 4.0},
       "prefixes": {"count": 2,
                    "tokens": {"dist": "lognormal", "median": 28,
                               "sigma": 0.3, "min": 20, "max": 40,
                               "round_to": 8, "offset": 4},
                    "zipf_s": 0.8, "tail_probe_tokens": 8},
       "body": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 4,
                "max": 12, "round_to": 4},
       "answer": {"dist": "lognormal", "median": 20, "sigma": 0.3,
                  "min": 16, "max": 28}}
RATIOS = ("decode_kv_gather_ratio", "extend_kv_gather_ratio")


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "chipbench/traffic/tiny-docqa.json").write_text(json.dumps(MIX))
    b = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for fam, (file, hq, hkv) in FAMILIES.items():
        spec = json.loads((BENCH / "configs" / file).read_text())
        spec.update(TINY, name=f"tiny-{fam}", num_attention_heads=hq,
                    num_key_value_heads=hkv)
        spec["serve"]["engine"].update(max_slots=4, page_tokens=8,
                                       max_cache_len=128, chunk_tokens=16)
        spec["serve"]["tiers"]["mrm"]["kv_pages"] = 40
        spec["check"] = {"max_logit_gap": 0.02,
                         "sample_tokens": 48, "max_requests": 3}
        (root / f"chipbench/configs/tiny-{fam}.json").write_text(
            json.dumps(spec))
        b["configs"].append({"name": f"tiny-{fam}", "source": "test",
                             "file": f"chipbench/configs/tiny-{fam}.json",
                             "reduced": [], "why": "test"})
        b["workloads"].append({"name": f"tiny-{fam}.docqa",
                               "config": f"tiny-{fam}",
                               "traffic": "tiny-docqa", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


def run(root, family, seed, **kw):
    b = bench.load_benchmark(root)
    return bench.run_cell(b, f"tiny-{family}.docqa", seed, 3.0, False,
                          time.perf_counter(), root=root, allow_cpu=True,
                          **kw)


def test_the_tiny_configurations_keep_their_families(checkout):
    b = bench.load_benchmark(checkout)
    qwen = bench.program_config(bench.config_spec(b, "tiny-qwen3", checkout))
    mha = bench.program_config(bench.config_spec(b, "tiny-deepseek",
                                                 checkout))
    assert qwen.n_heads == 2 * qwen.n_kv_heads and qwen.qk_norm
    assert mha.n_heads == mha.n_kv_heads and not mha.qk_norm
    assert mha.rope_theta == 1e4 and mha.resolved_head_dim == 16


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_sound_run_is_correct_and_compiles_nothing_in_its_window(
        checkout, family):
    out = run(checkout, family, 2**31 + 21)
    assert out["correct"], out["checks"]
    r = out["_run"]
    assert r.window_compiles == 0
    assert r.counters["tail_hits"] > 0 and r.counters["prefix_hits"] > 0
    assert out["checks"]["tokens_compared"]["value"] >= 16
    # every served answer decoded through the paged cache
    assert all(len(x.token_times) >= 16 for x in r.requests
               if x.rid is not None and len(x.token_times) >= x.max_new)
    ratios = {n: bench.metric_reader(n)(r) for n in RATIOS}
    assert all(v is not None and v >= 1.0 for v in ratios.values()), ratios


@pytest.mark.parametrize("fault", sorted(bench.FAULTS))
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_planted_fault_is_not_correct(checkout, family, fault):
    out = run(checkout, family, 17, fault=fault)
    assert not out["correct"]
    c = out["checks"]["logit_gap_max"]
    assert c["value"] > c["limit"] and out["failed"] >= 1


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_fp8_control_in_the_programs_place_is_not_correct(checkout, family):
    out = run(checkout, family, 2**31 + 29, control=True)
    c = out["checks"]["logit_gap_max"]
    assert not out["correct"]
    assert c["value"] > c["limit"] and out["failed"] >= 1
    assert out["_diag"]["program"]["max"] <= c["limit"]


# ---------------------------------------------------------------------------
# the KV gather ratios on a recorded window
# ---------------------------------------------------------------------------


def _recorded(counts: bool):
    """A window from t=10 to t=11 with two decode rounds and two prefill
    chunks inside it and one of each outside; with ``counts`` each span
    carries the program's KV page counts (gathered, attended)."""
    rec = telemetry.Recorder()
    for name, t, gathered, attended in (
            ("backend.decode", 9.5, 1000, 1),
            ("backend.decode", 10.1, 64 * 16, 400),
            ("backend.decode", 10.2, 64 * 16, 240),
            ("backend.prefill", 10.3, 64 * 32, 50),
            ("backend.prefill", 10.4, 128 * 96, 70),
            ("backend.prefill", 11.5, 1000, 1)):
        attrs = dict(kv_pages_gathered=gathered,
                     kv_pages_attended=attended) if counts else {}
        rec.record(name, t, t + 0.01, width=64, rows=4, **attrs)
    return rec


WINDOW = SimpleNamespace(t_open=10.0, t_close=11.0)
KNOWN = {"decode_kv_gather_ratio": 2 * 64 * 16 / 640,
         "extend_kv_gather_ratio": (64 * 32 + 128 * 96) / 120}


@pytest.mark.parametrize("name", RATIOS)
def test_ratio_reads_the_recorded_counts(monkeypatch, name):
    monkeypatch.setattr(telemetry, "_RECORDER", _recorded(True))
    assert bench.metric_reader(name)(WINDOW) == pytest.approx(KNOWN[name])


@pytest.mark.parametrize("name", RATIOS)
def test_ratio_is_none_without_the_counts(monkeypatch, name):
    """The spans of a program that counts no KV pages, and a program
    without the recorder, give nothing."""
    monkeypatch.setattr(telemetry, "_RECORDER", _recorded(False))
    assert bench.metric_reader(name)(WINDOW) is None
    monkeypatch.setattr(telemetry, "_RECORDER", _recorded(True))
    assert bench.metric_reader(name)(
        SimpleNamespace(t_open=20.0, t_close=21.0)) is None
    monkeypatch.setitem(sys.modules, "repro.serving.telemetry", None)
    assert bench.metric_reader(name)(WINDOW) is None

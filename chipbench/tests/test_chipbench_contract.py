"""BENCHMARK.json keeps the benchmark's format: its keys, names, units and
limits, every name it uses has its file, and every configuration file
states the program's published widths with only what ``reduced`` lists
cut."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for _p in (BENCH, BENCH.parent / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import json  # noqa: E402
import re  # noqa: E402

import pytest  # noqa: E402

ROOT = BENCH.parent
B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= len(B["command"]) <= 32 and all(map(line_ok, B["command"]))
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    for p in B["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert ".." not in p.split("/") and not p.startswith("/")
    for word in B["command"][1:]:
        assert any(word.startswith(p + "/") for p in B["paths"])


def test_configs():
    names = [c["name"] for c in B["configs"]]
    assert 1 <= len(names) <= 24 and len(set(names)) == len(names)
    files = [c["file"] for c in B["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in B["workloads"]}
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line_ok(c["source"]) and line_ok(c["why"])
        assert any(c["file"].startswith(p + "/") for p in B["paths"])
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k)
            assert not re.search(r"(_dim|_rank)$|hidden_size|intermediate|"
                                 r"latent|state_size|proj|expan|per_tok",
                                 k), k
        spec = json.loads((ROOT / c["file"]).read_text())
        assert spec["reduced"] == c["reduced"]
        assert spec["source"] == c["source"]


@pytest.mark.parametrize("path", sorted((BENCH / "configs").glob("*.json")),
                         ids=lambda p: p.stem)
def test_config_states_the_published_widths(path):
    """Every configuration file, in a cell or kept for a later one."""
    from repro.configs import get_config

    from cb import bench

    spec = json.loads(path.read_text())
    full = get_config(spec["arch"])
    mine = bench.program_fields(spec)
    for key, value in mine.items():
        theirs = getattr(full, key)
        if key == "num_layers":
            assert spec["published"]["num_hidden_layers"] == theirs
            assert value < theirs and "num_hidden_layers" in spec["reduced"]
        else:
            assert value == theirs, key


def test_workloads():
    names = [w["name"] for w in B["workloads"]]
    assert 1 <= len(names) <= 24 and len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert four <= max(1, len(names) // 2)
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line_ok(w["why"])
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()


def test_metrics():
    e2e, layer = B["end_to_end"], B["per_layer"]
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    cells = {w["name"] for w in B["workloads"]}
    e2e_cells = {m["name"]: set(m.get("workloads", cells)) for m in e2e}
    for m in e2e:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in e2e_cells and e2e_cells["setup_s"] == cells
    for m in layer:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and line_ok(m["layer"])
        assert m["moves"] in e2e_cells
        assert set(m.get("workloads", cells)) <= e2e_cells[m["moves"]]
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for cell in cells:
        mine = [n for n, c in e2e_cells.items() if cell in c]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(cell in m.get("workloads", cells) for m in layer)

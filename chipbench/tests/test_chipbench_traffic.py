"""The one traffic generator: a seed fixes the schedule, every seed gets
the same work, and lengths and rates follow the stated distributions."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for _p in (BENCH, BENCH.parent / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import json
from statistics import median

import numpy as np
import pytest

from cb import traffic

MIXES = BENCH / "traffic"
VOCAB = 151936


def mix(name):
    return traffic.load_mix(MIXES / f"{name}.json")


@pytest.mark.parametrize("name", ["sysprompt-chat", "unique-chat",
                                  "docqa-decode"])
def test_same_seed_same_schedule(name):
    a = traffic.build(mix(name), 2**31 + 17, 30, VOCAB)
    b = traffic.build(mix(name), 2**31 + 17, 30, VOCAB)
    assert [(r.due, r.tokens, r.max_new) for r in a.requests] == \
        [(r.due, r.tokens, r.max_new) for r in b.requests]
    assert a.prefixes == b.prefixes and a.tail_probes == b.tail_probes


@pytest.mark.parametrize("name", ["sysprompt-chat", "unique-chat",
                                  "docqa-decode"])
def test_every_seed_gets_the_same_work_in_the_same_order(name):
    a = traffic.build(mix(name), 1, 30, VOCAB)
    b = traffic.build(mix(name), 987654321, 30, VOCAB)
    assert [r.tokens for r in a.requests] != [r.tokens for r in b.requests]
    assert [(r.due, len(r.tokens), r.max_new, r.prefix)
            for r in a.requests] == [(r.due, len(r.tokens), r.max_new,
                                      r.prefix) for r in b.requests]
    assert list(map(len, a.prefixes)) == list(map(len, b.prefixes))


def test_lengths_follow_the_stated_lognormal():
    spec = {"dist": "lognormal", "median": 96, "sigma": 0.7, "min": 32,
            "max": 512, "round_to": 32}
    x = traffic.lengths(spec, 1001)
    assert x.min() >= 32 and x.max() <= 512 and (x % 32 == 0).all()
    # the median quantile is rounded up to the grid
    assert median(x) == 96
    raw = dict(spec, round_to=1, min=0, max=10**9)
    y = traffic.lengths(raw, 1000)
    z = np.log(y / 96.0) / 0.7
    assert abs(np.std(z) - 1.0) < 0.02 and abs(np.mean(z)) < 0.01


def test_offset_grid_and_add():
    x = traffic.lengths({"dist": "lognormal", "median": 2048, "sigma": 0.3,
                         "min": 1024, "max": 3072, "round_to": 32,
                         "offset": 16}, 8)
    assert ((x - 16) % 32 == 0).all() and x.min() >= 1024
    y = traffic.lengths({"dist": "const", "value": 5, "add": 10}, 3)
    assert y.tolist() == [5, 5, 5]
    z = traffic.lengths({"dist": "lognormal", "median": 96, "sigma": 0.7,
                         "min": 32, "max": 512, "round_to": 32,
                         "add": 1024}, 5)
    assert z.min() >= 1056 and z.max() <= 1536


def test_arrival_rate_and_window():
    m = mix("sysprompt-chat")
    s = traffic.build(m, 3, 40, VOCAB)
    rate = m["arrivals"]["rate_per_s"]
    assert len(s.requests) == int(np.ceil(rate * 40))
    due = [r.due for r in s.requests]
    assert due == sorted(due)
    # stratified exponential gaps: their mean is 1/rate up to the tail
    # quantiles the stratification leaves out
    assert abs(due[-1] / len(due) - 1 / rate) < 0.05 / rate


def test_onoff_bursts_leave_the_off_periods_empty():
    m = {"arrivals": {"process": "onoff", "rate_per_s": 10, "on_s": 2,
                      "off_s": 3},
         "prefixes": None, "body": {"dist": "const", "value": 8},
         "answer": {"dist": "const", "value": 4}}
    s = traffic.build(m, 5, 50, 1000)
    phase = np.array([r.due for r in s.requests]) % 5.0
    assert (phase < 2.0 + 1e-9).all()
    # 10/s while on for 2 s of every 5: 4/s over the 50 s window
    assert len(s.requests) == 200


def test_zipf_counts_and_prefix_use():
    c = traffic.zipf_counts(8, 1.0, 300)
    assert c.sum() == 300 and list(c) == sorted(c, reverse=True)
    assert abs(c[0] / c[1] - 2.0) < 0.1
    s = traffic.build(mix("sysprompt-chat"), 9, 40, VOCAB)
    used = np.bincount([r.prefix for r in s.requests], minlength=8)
    assert sorted(used.tolist(), reverse=True) == \
        traffic.zipf_counts(8, 1.0, len(s.requests)).tolist()
    for r in s.requests:
        head = s.prefixes[r.prefix]
        assert r.tokens[:len(head)] == head
        assert all(2 <= t < VOCAB for t in r.tokens)
    assert all(len(p) == 912 for p in s.prefixes)
    assert all(q[:len(p)] == p and len(q) == len(p) + 32
               for p, q in zip(s.prefixes, s.tail_probes))


def test_mixes_are_plain_data():
    for f in MIXES.glob("*.json"):
        m = json.loads(f.read_text())
        assert {"arrivals", "body", "answer"} <= set(m), f.name

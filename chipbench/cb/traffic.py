"""The one traffic generator. A traffic mix is a JSON file of parameters
(``chipbench/traffic/<mix>.json``); this module turns it, a seed and a
window length into a schedule of requests.

Every seed gets the same work in the same order: lengths and
inter-arrival gaps are the stratified quantiles of their distributions
(the i-th of n values sits at quantile (i + 0.5) / n), shared prefixes
go to requests in fixed Zipf counts, and one fixed permutation (not the
seed) orders them. The seed draws the token ids only. So runs with
different seeds differ in what the tokens are, never in how much work
arrives when: a spread between runs is the system's, not the
schedule's.

Mix parameters::

    arrivals   {"process": "poisson", "rate_per_s": r}
               {"process": "onoff", "rate_per_s": r, "on_s": a, "off_s": b}
               (rate while on; the off periods carry no arrivals)
    prefixes   null, or {"count": k, "tokens": LEN, "zipf_s": s,
                         "tail_probe_tokens": t}
               k shared prefixes (system prompts, documents) prefilled in
               set-up; prefix j (of fixed length) is used with Zipf(s)
               frequency by rank j.
               tail_probe_tokens > 0 registers, in set-up, each prefix
               followed by t further tokens, so the page that holds the
               prefix's last partial page is in the radix tree before the
               window (every hit then resumes mid-page via the tail copy)
    body       LEN: the request's own prompt tokens after its prefix
    answer     LEN: output tokens (max_new_tokens; eos cannot end early)

    LEN = {"dist": "lognormal", "median": m, "sigma": s, "min": a,
           "max": b, "round_to": g, "offset": o, "add": c}
        | {"dist": "const", "value": v}
    A lognormal draw is clipped to [min, max], then rounded up to
    o + a multiple of g (default g = 1, o = 0), then ``add`` is added.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass, field
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional

import numpy as np

_N01 = NormalDist()
ORDER_SEED = 0          # orders every schedule; the run's seed does not


@dataclass
class Request:
    """One scheduled request and what the client observed of it."""
    index: int
    due: float                 # seconds after the window opens
    prefix: Optional[int]      # index into Schedule.prefixes, or None
    tokens: List[int]          # the whole prompt
    max_new: int
    submitted: Optional[float] = None   # absolute host time
    rid: Optional[int] = None
    token_times: List[float] = field(default_factory=list)  # absolute


@dataclass
class Schedule:
    prefixes: List[List[int]]          # prefilled in set-up
    tail_probes: List[List[int]]       # prefix + probe tokens, set-up
    requests: List[Request]            # ordered by due time
    params: dict


def load_mix(path) -> dict:
    return json.loads(Path(path).read_text())


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """n stratified lengths of ``spec`` (LEN above), in ascending order."""
    if spec["dist"] == "const":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([_N01.inv_cdf(q) for q in quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(x, spec["min"], spec["max"])
    g, o = int(spec.get("round_to", 1)), int(spec.get("offset", 0))
    x = o + np.ceil((x - o) / g) * g
    return (x + int(spec.get("add", 0))).astype(np.int64)


def gaps(arrivals: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n inter-arrival gaps (seconds): stratified exponential quantiles at
    the stated rate, in an order drawn from ``rng``."""
    rate = float(arrivals["rate_per_s"])
    g = -np.log1p(-quantiles(n)) / rate
    return rng.permutation(g)


def zipf_counts(k: int, s: float, n: int) -> np.ndarray:
    """How many of n requests use each of k prefixes: the Zipf(s)
    expectation, rounded by largest remainder so the counts sum to n."""
    w = 1.0 / np.arange(1, k + 1) ** s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    short = n - counts.sum()
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[:short]] += 1
    return counts


def _due_times(arrivals: dict, n: int, rng) -> np.ndarray:
    """Due times of n requests: the first is due at its own gap."""
    on_time = np.cumsum(gaps(arrivals, n, rng))
    if arrivals["process"] == "poisson":
        return on_time
    if arrivals["process"] == "onoff":
        on_s, off_s = float(arrivals["on_s"]), float(arrivals["off_s"])
        return on_time + np.floor(on_time / on_s) * off_s
    raise ValueError(f"unknown arrival process {arrivals['process']!r}")


def request_count(arrivals: dict, seconds: float) -> int:
    """Requests whose stratified schedule spans ``seconds`` of wall time."""
    rate = float(arrivals["rate_per_s"])
    if arrivals["process"] == "onoff":
        on_s, off_s = float(arrivals["on_s"]), float(arrivals["off_s"])
        rate *= on_s / (on_s + off_s)
    return max(1, int(math.ceil(rate * seconds)))


def build(mix: dict, seed: int, seconds: float, vocab: int) -> Schedule:
    """The schedule of ``mix`` for ``seed`` over a ``seconds`` window.
    Token ids are drawn from [2, vocab) so they avoid the low ids that
    tokenizers reserve."""
    order = np.random.default_rng(ORDER_SEED)
    rng = np.random.default_rng(seed % 2**64)
    n = request_count(mix["arrivals"], seconds)
    due = _due_times(mix["arrivals"], n, order)
    body = order.permutation(lengths(mix["body"], n))
    answer = order.permutation(lengths(mix["answer"], n))

    def draw(k: int) -> List[int]:
        return [int(t) for t in rng.integers(2, vocab, int(k))]

    prefixes, probes, which = [], [], [None] * n
    pre = mix.get("prefixes")
    if pre:
        k = int(pre["count"])
        # prefix j's length is interleaved, so popularity does not
        # follow length
        plens = lengths(pre["tokens"], k)[order.permutation(k)]
        prefixes = [draw(m) for m in plens]
        counts = zipf_counts(k, float(pre["zipf_s"]), n)
        which = order.permutation(np.repeat(np.arange(k), counts)).tolist()
        t = int(pre.get("tail_probe_tokens", 0))
        probes = [p + draw(t) for p in prefixes] if t else []
    requests = []
    for i in range(n):
        head = prefixes[which[i]] if which[i] is not None else []
        requests.append(Request(index=i, due=float(due[i]),
                                prefix=which[i],
                                tokens=head + draw(body[i]),
                                max_new=int(answer[i])))
    return Schedule(prefixes, probes, requests, mix)

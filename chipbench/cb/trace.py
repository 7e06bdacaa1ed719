"""Reduction of a profiler trace (XSpace) to the numbers the per-layer
metrics read: device busy time and idle share, device time per program
launch, the top device operations, and idle gaps named by the harness
span the host was in.

Planes and lines read (as the JAX profiler writes them on a TPU host):

- ``/device:<KIND>:<n>`` planes: line ``XLA Ops`` (every operation that
  ran) and line ``XLA Modules`` (every program execution);
- ``/host:CPU``: the harness's own ``jax.profiler.TraceAnnotation``
  spans, whose names start with ``cb.`` (``cb.step``,
  ``cb.run_prefill_chunk:first``, ``cb.run_prefill_chunk:extend``,
  ``cb.run_decode``, ``cb.idle``, ``cb.submit``, ``cb.traced``).

The model programs of the served path are the device modules named
``jit__lambda(...)``; every ``cb.run_prefill_chunk:*`` and ``cb.run_decode``
span launches exactly one, and the device runs them in launch order, so
the k-th from the end of one list is the k-th from the end of the other.
Device and host clocks can disagree by a few milliseconds: the smallest
(device start - host launch) over those pairs, where negative, is taken
as the offset and removed from every device time.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

SPAN_PREFIX = "cb."
MARKER = "cb.traced"
LAUNCH_SPANS = ("cb.run_prefill_chunk", "cb.run_decode")
MODEL_MODULE = "jit__lambda"

Interval = Tuple[float, float]


@dataclass
class Device:
    name: str
    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[Device]
    spans: List[Tuple[str, float, float]]   # (name, start_ns, end_ns)


def from_profile(pd) -> Trace:
    """Collect the events this module reads from a ``ProfileData``."""
    devices, spans = [], set()
    for plane in pd.planes:
        if plane.name.startswith("/device:") and ":CUSTOM:" not in plane.name:
            dev = Device(plane.name)
            for line in plane.lines:
                target = {"XLA Ops": dev.ops,
                          "XLA Modules": dev.modules}.get(line.name)
                if target is None:
                    continue
                for ev in line.events:
                    target.append((ev.name, float(ev.start_ns),
                                   float(ev.start_ns) + float(ev.duration_ns)))
            if dev.ops or dev.modules:
                dev.ops.sort(key=lambda e: e[1])
                dev.modules.sort(key=lambda e: e[1])
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.add((ev.name, float(ev.start_ns),
                                   float(ev.start_ns) + float(ev.duration_ns)))
    return Trace(devices, sorted(spans, key=lambda s: (s[1], -s[2])))


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _launches(tr: Trace) -> List[Tuple[str, float]]:
    return [(n, a) for n, a, _ in tr.spans
            if n.split(":")[0] in LAUNCH_SPANS]


def pair_launches(tr: Trace, dev: Device):
    """[(span name, host start, device start, device end)] of the model
    programs, paired from the end."""
    host = _launches(tr)
    mods = [m for m in dev.modules if m[0].startswith(MODEL_MODULE)]
    n = min(len(host), len(mods))
    return [(h[0], h[1], m[1], m[2])
            for h, m in zip(host[len(host) - n:], mods[len(mods) - n:])]


def _op_label(name: str) -> str:
    """``%fusion.12.clone = bf16[...] fusion(...)`` -> ``fusion``."""
    return name.split(" = ")[0].lstrip("%").split(".")[0]


def _self_times(ops) -> List[Tuple[str, float, float, float]]:
    """(label, start, end, self seconds) of every op; an op whose interval
    holds later ops (a loop, a call) keeps only the time they leave."""
    out, stack = [], []
    for name, a, b in ops:
        while stack and stack[-1][2] <= a:
            stack.pop()
        entry = [_op_label(name), a, b, (b - a)]
        if stack and b <= stack[-1][2]:
            stack[-1][3] -= (b - a)
        out.append(entry)
        stack.append(entry)
    return [(e[0], e[1], e[2], max(e[3], 0.0) * 1e-9) for e in out]


def segments(spans) -> List[Tuple[float, float, str]]:
    """Cut the timeline at every span edge; label each piece with the
    innermost span over it (spans of one thread nest)."""
    edges = sorted({x for _, a, b in spans for x in (a, b)})
    out = []
    for a, b in zip(edges, edges[1:]):
        t, best = (a + b) / 2, None
        for name, s0, s1 in spans:
            if s0 > t:
                break
            if s1 >= t and (best is None or s1 - s0 < best[1]):
                best = (name, s1 - s0)
        if best:
            out.append((a, b, best[0].split(":")[0]))
    return out


def _split_gap(segs, starts, a: float, b: float, into: dict) -> None:
    """Add the gap [a, b] (ns) to ``into`` by the span over each part."""
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    covered = 0.0
    while i < len(segs) and segs[i][0] < b:
        lo, hi = max(a, segs[i][0]), min(b, segs[i][1])
        if hi > lo:
            into[segs[i][2]] += (hi - lo) * 1e-9
            covered += hi - lo
        i += 1
    if b - a > covered:
        into["outside any span"] += (b - a - covered) * 1e-9


def reduce(tr: Trace, top: int = 10) -> Optional[dict]:
    """Numbers of the traced window, or None when the trace holds no
    device activity."""
    if not tr.devices:
        return None
    marks = [(a, b) for n, a, b in tr.spans if n == MARKER]
    per_dev, ops_by, gaps_by = [], defaultdict(float), defaultdict(float)
    pairs_out = []
    segs = segments(tr.spans)
    seg_starts = [g[0] for g in segs]
    for dev in tr.devices:
        pairs = pair_launches(tr, dev)
        skew = min((d - h for _, h, d, _ in pairs), default=0.0)
        shift = -skew if skew < 0 else 0.0
        ops = [(n, a + shift, b + shift) for n, a, b in dev.ops]
        if marks:
            w0, w1 = marks[-1]
        else:
            w0 = min(a for _, a, _ in ops)
            w1 = max(b for _, _, b in ops)
        busy = clip(union([(a, b) for _, a, b in ops]), w0, w1)
        busy_ns = sum(b - a for a, b in busy)
        per_dev.append((busy_ns * 1e-9, (w1 - w0) * 1e-9))
        # launches inside the window, with their device seconds
        for name, h, d0, d1 in pairs:
            if w0 <= d0 + shift and d1 + shift <= w1:
                pairs_out.append((name, (d1 - d0) * 1e-9))
        kinds = [(d0 + shift, d1 + shift, name.split(":", 1)[-1]
                  if name.startswith("cb.run_prefill") else "decode")
                 for name, _, d0, d1 in pairs]
        k_starts = [k[0] for k in kinds]
        for label, a, b, s in _self_times(
                [o for o in ops if w0 <= o[1] and o[2] <= w1]):
            i = bisect.bisect_right(k_starts, a) - 1
            kind = kinds[i][2] if i >= 0 and a < kinds[i][1] else "other"
            ops_by[f"{kind}:{label}"] += s
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                _split_gap(segs, seg_starts, a, b, gaps_by)
    n = len(per_dev)
    busy_s = sum(b for b, _ in per_dev) / n
    window_s = sum(w for _, w in per_dev) / n
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "launches": pairs_out,
        "device_ops": sorted(([k, v] for k, v in ops_by.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in gaps_by.items()),
                            key=lambda kv: -kv[1])[:top],
    }

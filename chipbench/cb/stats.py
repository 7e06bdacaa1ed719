"""Order statistics used by the metric readers."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Linear-interpolation percentile (numpy's default method) of
    ``values``; None when there are none."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    k = (len(xs) - 1) * p / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def ttft_samples(requests, t_close: float) -> list:
    """Time to first token of every request due before ``t_close``, in
    seconds. A request whose first token had not come by then counts at
    its age at the close, so a backlog raises the percentiles instead of
    leaving the sample."""
    out = []
    for r in requests:
        if r.due >= t_close:
            continue
        if r.token_times and r.token_times[0] <= t_close:
            out.append(r.token_times[0] - r.due)
        else:
            out.append(t_close - r.due)
    return out


def itl_samples(requests, t_open: float, t_close: float) -> list:
    """Every gap between consecutive output tokens of any request whose
    later token came inside the window [t_open, t_close], in seconds."""
    out = []
    for r in requests:
        ts = r.token_times
        for a, b in zip(ts, ts[1:]):
            if t_open <= b <= t_close:
                out.append(b - a)
    return out


def tpot_samples(requests, t_open: float, t_close: float,
                 min_gaps: int = 8) -> list:
    """Time per output token of every request with more than ``min_gaps``
    tokens inside the window [t_open, t_close], in seconds: from its first
    to its last token there, over the gaps between them. A request still
    decoding at the close counts with the tokens it had by then. Each
    reading spans ``min_gaps`` token gaps or more, so the host clock's
    error is small beside it."""
    out = []
    for r in requests:
        ts = [t for t in r.token_times if t_open <= t <= t_close]
        if len(ts) > min_gaps:
            out.append((ts[-1] - ts[0]) / (len(ts) - 1))
    return out

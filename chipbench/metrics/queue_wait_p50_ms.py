"""Median (ms) from a request's due time to the start of its first
prefill chunk; a request not started by the close counts at its age."""
from cb.stats import percentile


def read(run):
    first = {}
    for x in run.log.launches:
        if x.kind != "decode" and x.rid not in first:
            first[x.rid] = x.t0
    waits = []
    for r in run.requests:
        if r.rid is None or r.due >= run.t_close:
            continue
        t = first.get(r.rid)
        waits.append((t if t is not None and t <= run.t_close
                      else run.t_close) - r.due)
    v = percentile(waits, 50)
    return None if v is None else v * 1e3

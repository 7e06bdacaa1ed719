"""Median wall milliseconds of a batched decode round (run_decode returns
host tokens, so the call waits for the device)."""
from cb.stats import percentile


def read(run):
    v = percentile([x.t1 - x.t0 for x in run.launches(kind=("decode",))], 50)
    return None if v is None else v * 1e3

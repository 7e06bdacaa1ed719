"""99th percentile (ms) of every gap between consecutive output tokens of
any request, for gaps that end inside the window."""
from cb.stats import itl_samples, percentile


def read(run):
    v = percentile(itl_samples(run.requests, run.t_open, run.t_close), 99)
    return None if v is None else v * 1e3

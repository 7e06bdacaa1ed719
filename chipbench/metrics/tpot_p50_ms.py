"""Median time per output token (ms) over the requests that streamed more
than 8 tokens inside the window: each request's first-to-last token time
there over its gaps. The pace a reader of a streamed answer sees."""
from cb.stats import percentile, tpot_samples


def read(run):
    v = percentile(tpot_samples(run.requests, run.t_open, run.t_close), 50)
    return None if v is None else v * 1e3

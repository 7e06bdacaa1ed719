"""Median time to first token (ms) over requests due in the window, from
the due time to the return of the step that produced the token; a
request still waiting at the close counts at its age then."""
from cb.stats import percentile, ttft_samples


def read(run):
    v = percentile(ttft_samples(run.requests, run.t_close), 50)
    return None if v is None else v * 1e3

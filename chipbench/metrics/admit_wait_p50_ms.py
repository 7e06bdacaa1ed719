"""Median milliseconds from a request's submission to its admission by
the scheduler (the program's ``request.queued`` span), over the requests
submitted in the window; one not admitted by the close counts at its age
then."""
from cb.program_spans import window_spans
from cb.stats import percentile


def read(run):
    spans = window_spans(run)
    if spans is None:
        return None
    admitted = {s.attrs["rid"]: s.t1 for s in spans
                if s.name == "request.queued"}
    waits = [admitted.get(s.attrs["rid"], run.t_close) - s.t0
             for s in spans if s.name == "request.submit"]
    v = percentile(waits, 50)
    return None if v is None else v * 1e3

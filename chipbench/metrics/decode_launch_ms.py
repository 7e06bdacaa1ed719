"""Median, over the window's decode rounds, of the host milliseconds
before the round is queued on the device: the program's
``backend.inputs`` (host arrays to the device) and ``backend.launch``
(the jit call) spans inside each ``backend.decode``."""
from collections import defaultdict

from cb.program_spans import window_spans
from cb.stats import percentile


def read(run):
    spans = window_spans(run)
    if spans is None:
        return None
    rounds = {s.id for s in spans if s.name == "backend.decode"}
    host = defaultdict(float)
    for s in spans:
        if s.parent in rounds and s.name in ("backend.inputs",
                                             "backend.launch"):
            host[s.parent] += s.t1 - s.t0
    v = percentile(list(host.values()), 50)
    return None if v is None else v * 1e3

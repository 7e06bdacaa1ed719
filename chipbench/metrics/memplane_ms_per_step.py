"""Mean milliseconds per engine step in the program's memory-plane
spans: ``kv.account`` (pages and page tables), ``mrm.meter`` (weight pass
and per-page read metering) and ``mrm.finish`` (step latency, clock
advance, retention maintenance)."""
from cb.program_spans import per_step_ms


def read(run):
    return per_step_ms(run, ("kv.account", "mrm.meter", "mrm.finish"))

"""Mean host milliseconds of an engine step outside its ComputeBackend
calls: scheduling, page tables, MRM metering, radix bookkeeping."""


def read(run):
    steps = [s for s in run.log.steps if run.t_open <= s.t0]
    if not steps:
        return None
    inside = sum(x.t1 - x.t0 for x in run.launches())
    wall = sum(s.t1 - s.t0 for s in steps)
    return 1e3 * (wall - inside) / len(steps)

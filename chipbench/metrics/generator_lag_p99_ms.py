"""99th percentile (ms) of how late the client submitted a request after
its due time: a starved generator shows here, not as a fast server."""
from cb.stats import percentile


def read(run):
    v = percentile(run.log.lag, 99)
    return None if v is None else v * 1e3

"""90th-percentile time to first token (ms), over the same requests as
ttft_p50_ms."""
from cb.stats import percentile, ttft_samples


def read(run):
    v = percentile(ttft_samples(run.requests, run.t_close), 90)
    return None if v is None else v * 1e3

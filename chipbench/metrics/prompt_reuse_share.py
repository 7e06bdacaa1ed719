"""Share (%) of prompt tokens that a radix prefix hit let the engine skip,
over the window: change in prefill_tokens_skipped over the change in
skipped plus computed."""


def read(run):
    skipped = run.counters["prefill_tokens_skipped"]
    total = skipped + run.counters["prefill_tokens_computed"]
    return None if total == 0 else 100.0 * skipped / total

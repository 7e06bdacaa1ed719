"""Device milliseconds of the prefill and extend programs per 1000 prompt
tokens they computed, in the traced slice."""


def read(run):
    pairs = run.traced_launches()
    if not pairs:
        return None
    pre = [(x, s) for x, s in pairs if x.kind != "decode"]
    tokens = sum(x.tokens for x, _ in pre)
    if tokens == 0:
        return None
    return 1e3 * sum(s for _, s in pre) / (tokens / 1000.0)

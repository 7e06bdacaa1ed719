"""Share (%) of the decode program's roofline: the least time the chip
needs for the traced decode rounds' work (the larger of FLOPs over peak
and bytes over bandwidth: weights once per round, the K/V of the keys
each active row attends) over their device time."""


def read(run):
    pairs = run.traced_launches()
    if not pairs:
        return None
    dec = [(x, s) for x, s in pairs if x.kind == "decode" and x.contexts]
    dev = sum(s for _, s in dec)
    if dev <= 0:
        return None
    least = sum(run.work.least_time(*run.work.decode_round(x.contexts),
                                    run.peaks) for x, _ in dec)
    return 100.0 * least / dev

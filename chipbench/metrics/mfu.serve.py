"""Model FLOPs of the engine steps in the window (every prompt token
computed and every output token, attention at each token's context) over
the wall seconds of those steps times the chip's bf16 peak, in percent:
the whole step's share of the peak. Below the knee the window's work is
fixed by the traffic, so the steps' own time, not the window's, is what a
faster step shortens."""


def read(run):
    w = run.work
    steps = [s for s in run.log.steps
             if run.t_open <= s.t0 and s.t1 <= run.t_close]
    step_s = sum(s.t1 - s.t0 for s in steps)
    if step_s <= 0:
        return None
    flops = 0.0
    for x in run.launches():
        if x.kind == "decode":
            flops += w.decode_round(x.contexts)[0]
        else:
            flops += w.chunk_flops(x.offset, x.tokens)
    return 100.0 * flops / (step_s * run.peaks["bf16_flops"] * run.chips)

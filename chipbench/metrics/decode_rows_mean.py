"""Mean rows of a decode round: decode tokens over the steps that ran a
decode round, inside the window."""


def read(run):
    steps = [s for s in run.log.steps
             if run.t_open <= s.t0 and s.decode_tokens > 0]
    if not steps:
        return None
    return sum(s.decode_tokens for s in steps) / len(steps)

"""Mean milliseconds per engine step in the program's ``engine.plan``
span: the abandon sweep, admissions, radix match and sub-page tail copy."""
from cb.program_spans import per_step_ms


def read(run):
    return per_step_ms(run, ("engine.plan",))

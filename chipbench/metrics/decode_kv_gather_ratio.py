"""KV pages the served attention gathered over the pages its sequences'
keys occupy, over the window's decode rounds: the sums of the program's
``kv_pages_gathered`` and ``kv_pages_attended`` on each ``backend.decode``
span. 1 is a ragged read of each sequence's pages once; the excess is
table padding and rows without a sequence. None where the program
counts neither."""
from cb.program_spans import window_spans


def read(run):
    spans = window_spans(run)
    if spans is None:
        return None
    mine = [s.attrs for s in spans if s.name == "backend.decode"
            and "kv_pages_attended" in s.attrs]
    attended = sum(a["kv_pages_attended"] for a in mine)
    if not attended:
        return None
    return sum(a["kv_pages_gathered"] for a in mine) / attended

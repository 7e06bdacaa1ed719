"""The program's own spans in a run's window, for the readers of metrics
whose source is ``program_span``.

The served path records its spans in ``repro.serving.telemetry``'s
process-wide recorder, on the harness's host clock
(``time.perf_counter``), and that recorder outlives the engine. A span
belongs to the window when its [t0, t1] lies in [t_open, t_close]. A
program without the recorder, a window with no span in it, and a window
from which the recorder's ring lost records all give None, so a reader
reports nothing rather than a part.
"""
from __future__ import annotations

from typing import List, Optional


def window_spans(run) -> Optional[List]:
    try:
        from repro.serving.telemetry import recorder
    except ImportError:
        return None
    rec = recorder()
    if not rec.complete_since(run.t_open):
        return None
    spans = rec.spans(lo=run.t_open, hi=run.t_close)
    return spans or None


def per_step_ms(run, names) -> Optional[float]:
    """Milliseconds per ``engine.step`` of the window spent in the spans
    named ``names`` (none of which nests in another)."""
    spans = window_spans(run)
    if spans is None:
        return None
    steps = sum(1 for s in spans if s.name == "engine.step")
    if not steps:
        return None
    inside = sum(s.t1 - s.t0 for s in spans if s.name in names)
    return 1e3 * inside / steps

"""The open-loop client and the harness's spans around the served path.

Each iteration submits every request whose due time has passed, then
calls ``ServeEngine.step()``; it sleeps to the next due time only when
the scheduler is idle. The tokens a step produced are stamped with the
wall time at which ``step()`` returned.

``instrument`` wraps the engine's ``ComputeBackend`` calls from here (not
in the program): each call runs under a ``jax.profiler.TraceAnnotation``
and is logged with its host times and the shape of its work, which the
metric readers and the trace reduction use.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np
from jax.profiler import TraceAnnotation


@dataclass
class Launch:
    """One ComputeBackend call: kind is first | extend | decode."""
    kind: str
    t0: float
    t1: float
    rid: Optional[int] = None         # prefill: the request
    tokens: int = 0                   # prefill: prompt tokens computed
    offset: int = 0                   # prefill: absolute start position
    width: int = 0                    # page-table width (program key)
    contexts: List[int] = field(default_factory=list)  # decode: keys per row


@dataclass
class StepRecord:
    t0: float
    t1: float
    prefill_tokens: int
    decode_tokens: int


@dataclass
class Log:
    launches: List[Launch] = field(default_factory=list)
    steps: List[StepRecord] = field(default_factory=list)
    lag: List[float] = field(default_factory=list)   # submit - due (s)


def instrument(eng, log: Log) -> None:
    """Wrap ``eng.backend.run_prefill_chunk`` / ``run_decode``."""
    be = eng.backend
    prefill, decode = be.run_prefill_chunk, be.run_decode

    def run_prefill_chunk(ck, page_table=None):
        kind = "first" if ck.first else "extend"
        t0 = time.perf_counter()
        with TraceAnnotation(f"cb.run_prefill_chunk:{kind}"):
            out = prefill(ck, page_table=page_table)
        log.launches.append(Launch(kind, t0, time.perf_counter(),
                                   rid=ck.request_id, tokens=len(ck.tokens),
                                   offset=int(ck.offset),
                                   width=int(np.shape(page_table)[-1])))
        return out

    def run_decode(slots, page_tables=None, audit_pages=None):
        # row s attends keys 0..positions[s] + 1 (the token it writes)
        ctx = [int(be.positions[s]) + 2 for s in slots]
        t0 = time.perf_counter()
        with TraceAnnotation("cb.run_decode"):
            out = decode(slots, page_tables=page_tables,
                         audit_pages=audit_pages)
        log.launches.append(Launch("decode", t0, time.perf_counter(),
                                   contexts=ctx,
                                   width=int(np.shape(page_tables)[-1])))
        return out

    be.run_prefill_chunk = run_prefill_chunk
    be.run_decode = run_decode


def run_window(eng, requests, seconds: float, log: Log,
               trace_tail_s: float = 0.0, trace_dir: Optional[str] = None):
    """Drive ``requests`` (due times relative to the window's start) for
    ``seconds`` of wall time. With ``trace_dir`` the profiler records the
    last ``trace_tail_s`` seconds under a ``cb.traced`` span. Returns
    (t_open, t_close, traced (start, end) or None) on the host clock."""
    import jax

    inflight = {}
    t_open = time.perf_counter()
    t_close = t_open + seconds
    t_trace = t_close - trace_tail_s if trace_dir else None
    tracing, marker, traced = False, None, None
    i, n = 0, len(requests)
    while True:
        now = time.perf_counter()
        if now >= t_close:
            break
        if t_trace is not None and not tracing and now >= t_trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            marker = TraceAnnotation("cb.traced")
            marker.__enter__()
            traced = [time.perf_counter(), None]
            tracing = True
        if i < n and t_open + requests[i].due <= now:
            with TraceAnnotation("cb.submit"):
                while i < n and t_open + requests[i].due <= now:
                    r = requests[i]
                    r.rid = eng.submit(r.tokens, max_new_tokens=r.max_new)
                    r.submitted = time.perf_counter()
                    log.lag.append(r.submitted - (t_open + r.due))
                    inflight[r.rid] = r
                    i += 1
        if eng.sched.idle:
            wake = min(t_open + requests[i].due if i < n else t_close,
                       t_close, t_trace if (t_trace and not tracing)
                       else t_close)
            with TraceAnnotation("cb.idle"):
                pause = wake - time.perf_counter()
                if pause > 0:
                    time.sleep(pause)
            continue
        t0 = time.perf_counter()
        with TraceAnnotation("cb.step"):
            rep = eng.step()
        t1 = time.perf_counter()
        log.steps.append(StepRecord(t0, t1, rep["prefill_tokens"],
                                    rep["decode_tokens"]))
        done = []
        for rid, r in inflight.items():
            out = eng.outputs[rid]
            while len(r.token_times) < len(out):
                r.token_times.append(t1)
            if len(out) >= r.max_new:
                done.append(rid)
        for rid in done:
            del inflight[rid]
    if tracing:
        # the device finishes what the last step queued before the trace
        # stops, so every traced launch has its device program in it
        jax.block_until_ready(eng.backend.paged_caches)
        traced[1] = time.perf_counter()
        marker.__exit__(None, None, None)
        jax.profiler.stop_trace()
    return t_open, t_close, (tuple(traced) if traced else None)


def warm_decode_widths(eng, widths) -> None:
    """Compile the batched decode program at each page-table width with
    no row active: nothing is written and no request advances."""
    B = eng.ecfg.max_slots
    for w in widths:
        eng.backend.run_decode([], page_tables=np.zeros((B, w), np.int32))

"""What the engine's own spans give the metrics.

``repro.serve.engine.Engine`` marks each phase of its host loop with a
``jax.profiler.TraceAnnotation`` named ``engine.<phase>``; the trace
reduction keeps them in ``run.trace.host`` on the clock of the device
operations. Phases of one loop iteration: ``engine.admit`` (rounds that
admit at least one request), ``engine.prefill_launch``,
``engine.decode_launch``, ``engine.fetch`` (the logits pulled to the host,
which waits there for the decode program) and ``engine.sample``; besides,
one ``engine.queued`` span per request, from ``submit`` to its admission.
The spans' arguments are not kept by the reduction.
"""
from __future__ import annotations

import bisect

from bench import serving
from bench.harness import percentile


def spans(run, name: str) -> list:
    """The spans named ``name`` that lie inside the traced window, in time order."""
    if run.trace is None:
        return []
    lo, hi = run.trace.window
    return [s for s in run.trace.host if s.name == name and s.start >= lo and s.end <= hi]


def mean_ms(run, name: str):
    """Mean duration of the window's ``name`` spans, in ms."""
    sp = spans(run, name)
    return 1e3 * sum(s.dur for s in sp) / len(sp) if sp else None


def queue_wait_p90_ms(run):
    """90th percentile of the ``engine.queued`` spans that closed inside the
    traced window, in ms. A request still queued when the trace stops leaves
    no span, so the longest waits of a backlog that outlives the trace are
    missing."""
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    waits = [s.dur for s in run.trace.host if s.name == "engine.queued" and lo <= s.end <= hi]
    return 1e3 * percentile(waits, 90) if waits else None


def logits_fetch_ms(run):
    """Mean over decode steps of the end of ``engine.fetch`` less the end of
    the decode execution it waited on (the last ``jit_gspmd_step`` execution
    to start before the fetch ended), in ms: the slice of the logits, their
    copy to the host and the host's wake-up. It reads host spans against
    device events, so a negative value means the two clocks disagree."""
    fetches = spans(run, "engine.fetch")
    if not fetches:
        return None
    ex = serving.executions(run, "decode")
    starts = [e.start for e in ex]
    gaps = []
    for f in fetches:
        i = bisect.bisect_left(starts, f.end) - 1
        if i >= 0:
            gaps.append(f.end - ex[i].end)
    return 1e3 * sum(gaps) / len(gaps) if gaps else None

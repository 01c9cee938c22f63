"""What the serving metrics share: the host records of the window and the
engine's step programs in the trace.

Names the trace gives the engine's programs (``repro.serve.engine.Engine``):
the decode step is ``jax.jit(gspmd_step)``, so its HLO module is
``jit_gspmd_step``; the prefill step is a ``jax.jit`` of a lambda, module
``jit__lambda``. The attention kernel of both is the Pallas
``flash_decode_pallas``: its HLO custom call carries that name
(``%flash_decode_pallas.6 = ... custom-call(...)``), one per layer.
"""
from __future__ import annotations

from bench import flops, trace_reduce

DECODE = "jit_gspmd_step"
PREFILL = "jit__lambda"
ATTENTION_KERNEL = "flash_decode_pallas"


def is_program(kind: str):
    name = DECODE if kind == "decode" else PREFILL
    return lambda prog: prog == name or prog.startswith(name + "_")


def calls(run, kind: str) -> list:
    return [c for c in run.calls if c[0] == kind]


def executions(run, kind: str):
    return trace_reduce.executions(run.trace, run.trace.devices[0], is_program(kind))


def paired(run, kind: str):
    """The window's calls of a step program and its executions on the device,
    in order; as many of each as both have."""
    cs, ex = calls(run, kind), executions(run, kind)
    n = min(len(cs), len(ex))
    return cs[:n], ex[:n]


def mean_exec_ms(run, kind: str):
    if run.trace is None:
        return None
    ex = executions(run, kind)
    return 1e3 * sum(e.dur for e in ex) / len(ex) if ex else None


def step_mfu(run, kind: str):
    """Required operations of the paired calls over their device time at the
    chip's peak, in percent."""
    if run.trace is None:
        return None
    cs, ex = paired(run, kind)
    t = sum(e.dur for e in ex)
    if not cs or t <= 0:
        return None
    count = flops.decode_flops if kind == "decode" else flops.prefill_flops
    work = sum(count(run.config, c[2]) for c in cs)
    return 100.0 * work / (t * run.peaks["bf16_flops"])


def attention_roofline(run, kind: str):
    """The attention kernel's share of its roofline inside one step program:
    the least time its required operations and bytes take, over its time."""
    if run.trace is None:
        return None
    cs, ex = paired(run, kind)
    if not cs:
        return None
    lo, hi = ex[0].start, ex[-1].end
    prog = is_program(kind)
    t = trace_reduce.op_time(run.trace, run.trace.devices[0],
                             lambda o: prog(o.program) and trace_reduce.op_label(o) == ATTENTION_KERNEL
                             and lo <= o.start <= hi)
    if t <= 0:
        return None
    cost = flops.decode_attention_cost if kind == "decode" else flops.prefill_attention_cost
    need = sum(flops.roofline_time(*cost(run.config, c[2]), run.peaks) for c in cs)
    return 100.0 * need / t


def device_idle(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    busy = trace_reduce.mean_busy_s(run.trace)
    return 100.0 * (1.0 - busy / run.trace.window_s)


def host_gap_ms(run):
    """Mean device-idle gap between consecutive step programs, leaving out
    gaps in which the benchmark waited for a request to arrive."""
    if run.trace is None:
        return None
    d = run.trace.devices[0]
    steps = trace_reduce.executions(run.trace, d, lambda p: is_program("decode")(p) or is_program("prefill")(p))
    idle = [s for s in run.trace.host if s.name == "bench.idle"]
    gaps = []
    for a, b in zip(steps, steps[1:]):
        g0, g1 = a.end, b.start
        if g1 > g0 and not any(s.start < g1 and s.end > g0 for s in idle):
            gaps.append(g1 - g0)
    return 1e3 * sum(gaps) / len(gaps) if gaps else None


def token_times(run):
    return [[t for t in r.times if t <= run.seconds] for r in run.requests]

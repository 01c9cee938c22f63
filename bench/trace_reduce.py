"""From a JAX profiler trace to the numbers the per-layer metrics read.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`load` reads it with ``jax.profiler.ProfileData`` and keeps three
things, all on the host's clock in seconds:

* ``ops[d]``: the operations that ran on device ``d``, each with its HLO
  instruction name (a Pallas kernel's is the kernel's name:
  ``flash_decode_pallas.6``), the program (HLO module) it belongs to, and
  whether it is a container (a ``while`` whose body's operations appear
  inside it on the same line);
* ``programs[d]``: one interval per execution of a program on device
  ``d``: the trace's own program events where it has them (the TPU's
  ``XLA Modules`` line), otherwise the span of the operations that share a
  program and a run id (the CPU backend);
* ``host``: the benchmark's own spans (``TraceAnnotation`` names that start
  with ``bench.`` or ``engine.``), and ``window``, the span named
  ``bench.window`` around the traced window.

A TPU device is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
the operations, each named by its HLO text (``%fusion.12 = bf16[...]
fusion(...)``), and its ``XLA Modules`` line the program executions
(``jit_gspmd_step(<id>)``); an operation belongs to the execution that holds
it in time. The ``Async XLA Ops`` line (copies in flight) is not read. On the
CPU backend the operations are host events that carry an ``hlo_op`` stat,
and ``device_ordinal`` names the device.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PREFIXES = ("bench.", "engine.")


@dataclasses.dataclass
class Span:
    start: float
    end: float
    name: str
    program: str = ""
    container: bool = False

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: dict
    programs: dict
    host: list
    window: tuple

    @property
    def devices(self) -> list[int]:
        return sorted(self.ops)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _program_name(name) -> str:
    """``jit_step(123)`` -> ``jit_step``: the trace appends an id."""
    return re.sub(r"\(\d+\)$", "", str(name))


def _op_name(text: str) -> str:
    """``%flash_decode_pallas.6 = bf16[...] custom-call(...)`` ->
    ``flash_decode_pallas.6``."""
    if text.startswith("%"):
        return text[1:].split(" = ", 1)[0]
    return text


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    ops, programs, host = defaultdict(list), defaultdict(list), []
    runs = defaultdict(list)  # (device, program, run id) -> op spans, CPU backend
    for plane in pd.planes:
        m = _TPU_PLANE.match(plane.name)
        if m:
            d = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        ops[d].append(Span(ev.start_ns * 1e-9, ev.end_ns * 1e-9, _op_name(ev.name)))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        programs[d].append(Span(ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                                                _program_name(ev.name)))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(HOST_PREFIXES):
                    host.append(Span(ev.start_ns * 1e-9, ev.end_ns * 1e-9, name))
                    continue
                st = dict(ev.stats)
                if "hlo_op" in st and ev.duration_ns > 0:
                    d = int(st.get("device_ordinal", 0))
                    prog = _program_name(st.get("hlo_module", ""))
                    sp = Span(ev.start_ns * 1e-9, ev.end_ns * 1e-9, str(st["hlo_op"]), prog)
                    ops[d].append(sp)
                    runs[(d, prog, st.get("run_id"))].append(sp)
    for d in ops:
        if not programs.get(d):
            for (dd, prog, _), sps in runs.items():
                if dd == d:
                    programs[d].append(Span(min(s.start for s in sps), max(s.end for s in sps), prog))
        ops[d].sort(key=lambda s: (s.start, -s.end))
        programs[d].sort(key=lambda s: s.start)
        for a, b in zip(ops[d], ops[d][1:]):
            if b.start < a.end and b.end <= a.end:
                a.container = True
    # ops carry their program where the trace names it; otherwise the
    # program execution that holds them in time
    for d in ops:
        progs = programs.get(d, [])
        i = 0
        for op in ops[d]:
            if op.program:
                continue
            while i < len(progs) and progs[i].end < op.start:
                i += 1
            if i < len(progs) and progs[i].start <= op.start:
                op.program = progs[i].name
    host.sort(key=lambda s: s.start)
    win = [s for s in host if s.name == "bench.window"]
    if win:
        window = (win[0].start, win[0].end)
    else:
        every = [s for d in ops for s in ops[d]]
        window = (min(s.start for s in every), max(s.end for s in every)) if every else (0.0, 0.0)
    return Trace(dict(ops), dict(programs), host, window)


# ------------------------------------------------------------- reductions ----

def merge(spans, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``spans`` clipped to ``[lo, hi]``, as sorted intervals."""
    out: list[list[float]] = []
    for s in sorted(spans, key=lambda s: s.start):
        a, b = max(s.start, lo), min(s.end, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(tr: Trace, device: int) -> float:
    """Seconds of the window in which some operation ran on ``device``."""
    return sum(b - a for a, b in merge(tr.ops.get(device, []), *tr.window))


def mean_busy_s(tr: Trace, devices=None) -> float:
    devices = tr.devices if devices is None else devices
    return sum(busy_s(tr, d) for d in devices) / len(devices) if devices else 0.0


def idle_gaps(tr: Trace, device: int) -> list[tuple[float, float]]:
    """The intervals of the window in which nothing ran on ``device``."""
    lo, hi = tr.window
    gaps, t = [], lo
    for a, b in merge(tr.ops.get(device, []), lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def executions(tr: Trace, device: int, pred) -> list[Span]:
    """Executions of the programs whose name satisfies ``pred``, inside the
    window, in time order."""
    lo, hi = tr.window
    return [p for p in tr.programs.get(device, []) if pred(p.name) and p.start >= lo and p.end <= hi]


def op_time(tr: Trace, device: int, pred) -> float:
    """Summed device time of the window's operations that satisfy ``pred``."""
    lo, hi = tr.window
    return sum(min(o.end, hi) - max(o.start, lo) for o in tr.ops.get(device, [])
               if pred(o) and o.end > lo and o.start < hi)


def uncovered_s(tr: Trace, device: int, pred_target, pred_cover) -> float:
    """Seconds in which an op satisfying ``pred_target`` runs on ``device``
    and no op satisfying ``pred_cover`` does: exposed collective time."""
    lo, hi = tr.window
    ops = tr.ops.get(device, [])
    target = merge([o for o in ops if pred_target(o)], lo, hi)
    cover = merge([o for o in ops if pred_cover(o)], lo, hi)
    total, j = 0.0, 0
    for a, b in target:
        t = a
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            ca, cb = cover[k]
            if ca > t:
                total += ca - t
            t = max(t, cb)
            k += 1
        if b > t:
            total += b - t
    return total


def host_label(tr: Trace, t: float) -> str:
    """The innermost benchmark span that holds time ``t``, or ``none``."""
    best = None
    for s in tr.host:
        if s.start > t:
            break
        if s.end >= t and (best is None or s.dur <= best.dur):
            best = s
    return best.name if best else "none"


def op_label(o: Span) -> str:
    """An operation's HLO name without its number: ``flash_decode_pallas``,
    ``fusion``, ``copy``."""
    return re.sub(r"\.\d+$", "", o.name)


def breakdown(tr: Trace, device: int | None = None, top: int = 10) -> dict:
    """The device operations that took most time (``program/instruction``,
    containers left out, their body's operations counted instead) and the
    idle time by what the host was doing, each as ``[[name, seconds], ...]``."""
    d = tr.devices[0] if device is None else device
    lo, hi = tr.window
    by_op: dict[str, float] = defaultdict(float)
    for o in tr.ops.get(d, []):
        if o.end > lo and o.start < hi and not o.container:
            by_op[f"{o.program}/{o.name}"] += min(o.end, hi) - max(o.start, lo)
    by_host: dict[str, float] = defaultdict(float)
    for a, b in idle_gaps(tr, d):
        by_host[host_label(tr, (a + b) / 2)] += b - a
    rank = lambda m: [[k, v] for k, v in sorted(m.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(by_op), "idle_gaps": rank(by_host)}

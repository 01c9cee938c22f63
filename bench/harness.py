"""One run of one benchmark cell, driven by ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the configuration as it is run, with a
  ``system`` key naming the system under test, and
  ``bench/configs/<config>.py`` beside it: its plain reference;
* ``bench/systems/<system>.py``: builds that system from a configuration
  and a seed, warms it up, drives it through the window and checks what it
  produced against the configuration's reference;
* ``bench/traffic/<mix>.json``: the mix, read by ``bench/traffic.py``;
* ``bench/metrics/<metric>.py``: ``read(run)`` gives the metric's value, or
  ``None`` where the run has nothing for it to read.

A run: set-up (weights or data from the seed, the system built, every
shape the cell's traffic uses compiled and run once), then the window of
``--seconds``, then the check. A traced run (``--trace 1``) runs the same
window with the profiler on for its first ``TRACE_SECONDS`` and reports the
per-layer metrics read from that part. ``setup_s`` is the time
from the process's start to the window's start.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
# a traced run traces the first seconds of its window only: a whole window
# of a serving cell holds millions of device operations
TRACE_SECONDS = 12.0

for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_module(path: Path):
    name = "bench_" + path.stem.replace("-", "_").replace(".", "_") + "_" + path.parent.name
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find(entries: list, name: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no entry named {name!r}; known: {[e['name'] for e in entries]}")


def cell_metrics(spec: dict, cell: str, traced: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or in a
    traced run its per-layer metrics."""
    e2e = [m for m in spec["end_to_end"] if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in names)]


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    import numpy as np

    return float(np.percentile(np.asarray(values, float), q))


# ------------------------------------------------------- compile counting ----

COMPILES: Counter = Counter()
_LISTENING = False


def _listen() -> None:
    """Count executables built (compiled or loaded from the persistent cache)
    and functions traced, so that the window can show it holds none."""
    global _LISTENING
    if _LISTENING:
        return
    import jax

    # the events JAX records for each executable it compiles or loads from
    # the persistent cache, and for each function it traces
    events = {"/jax/core/compile/backend_compile_duration": "executables",
              "/jax/core/compile/jaxpr_trace_duration": "traces"}

    def on_event(event, duration, **kw):
        if event in events:
            COMPILES[events[event]] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    _LISTENING = True


def enable_cache() -> None:
    """JAX's persistent compilation cache, at a fixed path in the checkout,
    holding every program so that only a cell's first run compiles."""
    import os

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


# ------------------------------------------------------------------- run ----

class Run:
    """What a run leaves for the metric readers: its settings, the host's
    records of the window, and in a traced run the reduced trace."""

    def __init__(self, cell, config, mix, seed, seconds, peaks):
        self.cell, self.config, self.mix = cell, config, mix
        self.seed, self.seconds, self.peaks = seed, seconds, peaks
        self.setup_s = None
        self.requests = []  # serving: one record per request due in the window
        self.steps = []  # serving: (start, end) of each ``Engine.run(max_steps=1)`` in the window
        self.calls = []  # serving: one record per step program called in the window
        self.trace = None
        self.info = {}


def verdict(checks: dict) -> bool:
    """``correct``: every number compared lies within its limit."""
    return bool(checks) and all(c["value"] <= c["limit"] for c in checks.values())


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *, t_start: float,
             require_tpu: bool = True, peaks: dict | None = None, config: dict | None = None,
             mix: dict | None = None, workload: dict | None = None, trace_dir: str | None = None,
             log=print) -> dict:
    """Run ``cell`` once and return its result line as a dict.

    ``workload``/``config``/``mix`` replace the entries and files, and
    ``require_tpu=False`` with ``peaks`` lets a test drive a run on the CPU."""
    import jax

    from bench import traffic
    from bench.peaks import peaks as peaks_of

    spec = load_spec()
    w = workload or find(spec["workloads"], cell)
    if config is None:
        config = json.loads((ROOT / find(spec["configs"], w["config"])["file"]).read_text())
    mix = mix or traffic.load(w["traffic"])
    devs = devices_for(w["chips"], require_tpu)
    peaks = peaks or peaks_of(devs[0].device_kind)
    _listen()

    run = Run(cell, config, mix, seed, seconds, peaks)
    system = load_module(BENCH / "systems" / f"{config['system']}.py").System(config, mix, seed, devs, run)
    system.setup()
    gc.collect()
    before = Counter(COMPILES)
    tmp = None
    if trace:
        tmp = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tmp)
    run.setup_s = time.perf_counter() - t_start
    span = jax.profiler.TraceAnnotation("bench.window")
    span.__enter__()
    open_ = [True]

    def close_trace(now=None):
        """Ends the traced part of the window: its first ``TRACE_SECONDS``."""
        if open_[0] and (now is None or now >= TRACE_SECONDS):
            span.__exit__(None, None, None)
            if trace:
                jax.profiler.stop_trace()
            open_[0] = False

    system.drive(seconds, close_trace if trace else lambda now: None)
    close_trace()
    in_window = {k: COMPILES[k] - before[k] for k in ("executables", "traces")}
    log(f"bench: compilations in the window: {in_window['executables']} executables built, "
        f"{in_window['traces']} functions traced")
    mem = [d.memory_stats() or {} for d in devs]
    memory_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    system.free()
    gc.collect()

    t_check = time.perf_counter()
    checks, attempted, failed = system.check()
    run.info["check_s"] = time.perf_counter() - t_check
    correct = verdict(checks)

    if trace:
        from bench import trace_reduce

        run.trace = trace_reduce.load(tmp)
        if trace_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)
    metrics = {}
    for m in cell_metrics(spec, cell, trace):
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    for k, v in run.info.items():
        log(f"bench: {k}: {json.dumps(v)}")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        from bench import trace_reduce

        tr = run.trace
        used = [d.id for d in devs if d.id in tr.ops] or tr.devices
        device["busy_s"] = trace_reduce.mean_busy_s(tr, used)
        device["window_s"] = tr.window_s
        result["breakdown"] = trace_reduce.breakdown(tr, used[0] if used else None)
    result["checks"] = checks
    return result

"""The SUMMA cell's readers: the five per-layer metrics on a small trace recorded from a four-device CPU run
(``record_summa_trace.py``: 4 multiplies at (256, 320, 176) on a 2x2
grid) and on a trace without the SUMMA spans; the serving reader of
latency on the system's records of the multiplies."""
import os

import pytest

from bench import gemm_work, harness, summa_trace
from bench import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data")
CONFIG = {"ni": 256, "nj": 320, "nk": 176, "grid": [2, 2], "dtype": "float32"}
PEAKS = {"bf16_flops": 197e12, "hbm_bw": 819e9}
READERS = ("exposed_comm_ms.gemm", "gemm_roofline.gemm", "summa_mfu.gemm",
           "dispatch_ms.gemm", "device_idle.gemm")
MULTIPLIES = 4


def run_of(trace_file):
    r = harness.Run("summa-xl-2x2", CONFIG, {}, 0, 10.0, PEAKS)
    r.trace = T.load(os.path.join(DATA, trace_file))
    return r


def read(name, run):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read(run)


@pytest.fixture(scope="module")
def run():
    return run_of("summa_trace.xplane.pb")


def host(run, name):
    return [s for s in run.trace.host if s.name == name]


def test_trace_holds_the_multiplies(run):
    assert run.trace.devices == [0, 1, 2, 3]
    assert summa_trace.multiplies(run) == MULTIPLIES
    assert len(host(run, "bench.summa.wait")) == MULTIPLIES
    for d in run.trace.devices:
        # each multiply: one ring shift and one reduce-scatter on every device
        names = [T.op_label(o) for o in run.trace.ops[d] if summa_trace.is_collective(o)]
        assert sorted(names) == ["ppermute"] * MULTIPLIES + ["reduce_scatter"] * MULTIPLIES


def test_dispatch_and_idle(run):
    sp = host(run, "bench.summa.dispatch")
    assert read("dispatch_ms.gemm", run) == pytest.approx(1e3 * sum(s.dur for s in sp) / len(sp))
    busy = T.mean_busy_s(run.trace)
    idle = read("device_idle.gemm", run)
    assert 0 < idle < 100
    assert idle == pytest.approx(100 * (1 - busy / run.trace.window_s))


def test_exposed_comm(run):
    tr = run.trace
    value = read("exposed_comm_ms.gemm", run)
    comm = [T.op_time(tr, d, summa_trace.is_collective) for d in tr.devices]
    # no more than the collectives' own time, per multiply and device
    assert 0 < value <= 1e3 * sum(comm) / len(comm) / MULTIPLIES + 1e-9
    compute = lambda o: not o.container and not summa_trace.is_collective(o)
    want = sum(T.uncovered_s(tr, d, summa_trace.is_collective, compute) for d in tr.devices) / 4
    assert value == pytest.approx(1e3 * want / MULTIPLIES)


def test_step_mfu(run):
    work = 2 * 256 * 320 * 176 * MULTIPLIES
    assert read("summa_mfu.gemm", run) == pytest.approx(100 * work / (4 * 197e12 * run.trace.window_s))


def test_gemm_roofline_counts_from_gemm_work(run, monkeypatch):
    """The CPU runs the inner step's reference, so the trace holds no
    ``gemm_panel_pallas`` and the reader gives nothing; read with the
    reference's product (``dot_general``, two a multiply) in the kernel's
    place, it takes each call's operations and bytes from
    ``bench/gemm_work.py``: doubling the bytes there doubles the reading,
    since at this size the bytes bound the call."""
    assert read("gemm_roofline.gemm", run) is None
    monkeypatch.setattr(summa_trace, "KERNEL", "dot_general")
    tr = run.trace
    t = sum(T.op_time(tr, d, summa_trace.is_kernel) for d in tr.devices)
    flops, nbytes = gemm_work.panel_gemm_cost(CONFIG)
    assert (flops, nbytes) == (2 * 128 * 160 * 88, (128 * 88 + 88 * 160 + 128 * 160) * 4)
    calls = 4 * 2 * MULTIPLIES
    value = read("gemm_roofline.gemm", run)
    assert value == pytest.approx(100 * calls * max(flops / 197e12, nbytes / 819e9) / t)
    monkeypatch.setattr(gemm_work, "panel_gemm_cost", lambda c: (flops, 2 * nbytes))
    assert read("gemm_roofline.gemm", run) == pytest.approx(2 * value)


def recorded(steps, seconds):
    """A run whose window held the multiplies ``steps``, recorded as the
    SUMMA system records them."""
    summa = harness.load_module(harness.BENCH / "systems" / "summa.py")
    r = harness.Run("summa-xl-2x2", CONFIG, {}, 0, seconds, PEAKS)
    r.steps = steps
    r.requests = [summa.Multiply(s, e) for s, e in steps]
    return r


def test_latency_is_the_p90_of_the_multiplies():
    steps = [(0.001 * i, 0.001 * i + 0.001 * (1 + i % 10)) for i in range(100)]
    r = recorded(steps, 10.0)
    # latencies 1..10 ms, ten of each
    assert read("ttft_p90_ms", r) == pytest.approx(harness.percentile([1e3 * (e - s) for s, e in steps], 90))
    # the call open at the close counts at its wait so far
    r = recorded([(0.0, 0.004), (0.004, 0.02)], 0.01)
    assert read("ttft_p90_ms", r) == pytest.approx(harness.percentile([4.0, 6.0], 90))


def test_latency_from_the_recorded_calls(run):
    lo = run.trace.window[0]
    calls = list(zip(host(run, "bench.summa.dispatch"), host(run, "bench.summa.wait")))
    steps = [(d.start - lo, w.end - lo) for d, w in calls]
    r = recorded(steps, steps[-1][1])
    want = harness.percentile([1e3 * (w.end - d.start) for d, w in calls], 90)
    assert read("ttft_p90_ms", r) == pytest.approx(want)


def test_no_summa_spans_reads_nothing():
    """A trace of another program gives no value, and no error."""
    r = run_of("cpu_trace.xplane.pb")
    assert all(read(name, r) is None for name in READERS if name != "device_idle.gemm")
    r.trace = None
    assert all(read(name, r) is None for name in READERS)

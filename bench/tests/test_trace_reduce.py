"""The trace reduction, on a small trace recorded on the CPU
(``record_trace.py``): three executions of ``jit_step`` in a
``bench.window`` span, a 50 ms ``bench.idle`` sleep after the first."""
import os

import pytest

from bench import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data", "cpu_trace.xplane.pb")


@pytest.fixture(scope="module")
def tr():
    return T.load(DATA)


def test_window_is_the_benchmark_span(tr):
    win = [s for s in tr.host if s.name == "bench.window"][0]
    assert tr.window == (win.start, win.end)
    assert 0.05 < tr.window_s < 0.5


def test_programs_and_ops(tr):
    assert tr.devices == [0]
    runs = T.executions(tr, 0, lambda p: p == "jit_step")
    assert len(runs) == 3
    assert all(o.program == "jit_step" for o in tr.ops[0])
    # every op lies inside one execution of its program
    for o in tr.ops[0]:
        assert any(r.start <= o.start and o.end <= r.end for r in runs)


def test_busy_idle_and_breakdown(tr):
    busy = T.busy_s(tr, 0)
    gaps = T.idle_gaps(tr, 0)
    assert 0 < busy < tr.window_s
    assert sum(b - a for a, b in gaps) + busy == pytest.approx(tr.window_s, rel=1e-9)
    bd = T.breakdown(tr)
    assert bd["idle_gaps"][0][0] == "bench.idle"
    assert 0.045 < bd["idle_gaps"][0][1] < 0.056
    assert sum(v for _, v in bd["device_ops"]) == pytest.approx(
        T.op_time(tr, 0, lambda o: True), rel=1e-9)
    assert bd["device_ops"][0][0].startswith("jit_step/dot_general")


def S(a, b, name="x"):
    return T.Span(a, b, name)


def test_merge_and_uncovered():
    assert T.merge([S(0, 2), S(1, 3), S(5, 6)], 0, 10) == [(0, 3), (5, 6)]
    assert T.merge([S(0, 2), S(5, 6)], 1, 5.5) == [(1, 2), (5, 5.5)]
    tr = T.Trace(ops={0: [S(0, 4, "collective-permute"), S(1, 2, "fusion"), S(3, 5, "fusion"),
                          S(6, 7, "reduce-scatter")]},
                 programs={0: []}, host=[], window=(0, 10))
    coll = lambda o: "fusion" not in o.name
    # 0-4 covered by 1-2 and 3-4: exposed 0-1 and 2-3; 6-7 wholly exposed
    assert T.uncovered_s(tr, 0, coll, lambda o: not coll(o)) == pytest.approx(3.0)
    assert T.busy_s(tr, 0) == pytest.approx(6.0)
    assert T.idle_gaps(tr, 0) == [(5, 6), (7, 10)]

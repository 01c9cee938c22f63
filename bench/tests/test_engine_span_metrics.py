"""The readers of the engine's own spans (``bench/engine_spans.py``), on a
small engine trace recorded on the CPU (``record_engine_trace.py``: 3
requests on 2 slots, 5 decode steps, the third request queued for 3 of
them), and on a trace without engine spans, as the parent of the spans
leaves."""
import os

import pytest

from bench import engine_spans, harness, serving
from bench import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data")
READERS = ("queue_wait_p90_ms.chat", "logits_fetch_ms.chat", "sample_ms.chat", "admit_ms.chat")


def run_of(trace_file):
    r = harness.Run("phi4-chat", {}, {}, 0, 10.0, {})
    r.trace = T.load(os.path.join(DATA, trace_file))
    return r


def read(name, run):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read(run)


@pytest.fixture(scope="module")
def run():
    return run_of("engine_trace.xplane.pb")


def test_spans_in_the_window(run):
    counts = {n: len(engine_spans.spans(run, n)) for n in
              ("engine.queued", "engine.admit", "engine.prefill_launch",
               "engine.decode_launch", "engine.fetch", "engine.sample")}
    assert counts == {"engine.queued": 3, "engine.admit": 2, "engine.prefill_launch": 2,
                      "engine.decode_launch": 5, "engine.fetch": 5, "engine.sample": 5}
    assert len(serving.executions(run, "decode")) == 5


def test_mean_span_readers(run):
    for name, span in (("sample_ms.chat", "engine.sample"), ("admit_ms.chat", "engine.admit")):
        sp = engine_spans.spans(run, span)
        assert read(name, run) == pytest.approx(1e3 * sum(s.dur for s in sp) / len(sp))


def test_queue_wait_tail(run):
    waits = sorted(s.dur for s in engine_spans.spans(run, "engine.queued"))
    # the third request waits for 3 decode steps, the others for one admission
    assert waits[-1] > 2 * waits[-2]
    assert read("queue_wait_p90_ms.chat", run) == pytest.approx(
        1e3 * (waits[1] + 0.8 * (waits[2] - waits[1])))


def test_logits_fetch_pairs_each_fetch_with_its_decode(run):
    fetches = engine_spans.spans(run, "engine.fetch")
    ex = serving.executions(run, "decode")
    # each decode execution ends inside the fetch that follows its launch
    for f, e in zip(fetches, ex):
        assert e.start < f.end and f.start <= e.end <= f.end
    want = sum(f.end - e.end for f, e in zip(fetches, ex)) / len(ex)
    value = read("logits_fetch_ms.chat", run)
    assert value >= 0 and value == pytest.approx(1e3 * want)


def test_idle_time_falls_in_engine_phases(run):
    """The phase spans tile each ``bench.step``: idle gaps are named by a
    phase, not by the step around it."""
    gaps = dict(T.breakdown(run.trace)["idle_gaps"])
    in_engine = sum(v for k, v in gaps.items() if k.startswith("engine."))
    assert gaps.get("bench.step", 0.0) < 0.1 * in_engine


def test_no_engine_spans_reads_nothing():
    """A program without the engine's spans gives no value, and no error."""
    r = run_of("cpu_trace.xplane.pb")
    assert all(read(name, r) is None for name in READERS)
    r.trace = None
    assert all(read(name, r) is None for name in READERS)

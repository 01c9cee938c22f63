"""The end-to-end metric arithmetic on made-up records."""
import types

import pytest

from bench import harness

BENCH = harness.BENCH


def metric(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py")


def req(arrival, times):
    return types.SimpleNamespace(arrival=arrival, times=times)


def run_with(requests, seconds=10.0, **kw):
    r = harness.Run("c", {}, {}, 0, seconds, {})
    r.requests = requests
    for k, v in kw.items():
        setattr(r, k, v)
    return r


def test_ttft_tail_counts_waiting_requests_at_their_wait():
    # 9 served after 1 s; one still waiting at the close, due at 2 s
    reqs = [req(0.0, [1.0, 1.1]) for _ in range(9)] + [req(2.0, [])]
    r = run_with(reqs, seconds=10.0)
    # waits: nine of 1 s and one of 8 s; p90 interpolates between them
    assert metric("ttft_p90_ms").read(r) == pytest.approx(1e3 * (1.0 + 0.1 * 7.0))
    # a first token after the close counts at the close
    r = run_with([req(0.0, [12.0])], seconds=10.0)
    assert metric("ttft_p90_ms").read(r) == pytest.approx(1e4)


def test_itl_over_all_gaps_inside_the_window():
    reqs = [req(0.0, [1.0, 1.5, 2.0]), req(0.0, [3.0, 3.1, 11.0])]
    r = run_with(reqs, seconds=10.0)
    # gaps 0.5, 0.5, 0.1; the token at 11 s is outside the window
    assert metric("itl_p99_ms").read(r) == pytest.approx(500.0)


def test_output_rate_is_the_whole_window():
    reqs = [req(0.0, [1.0, 2.0, 3.0]), req(0.0, [4.0, 10.5])]
    assert metric("output_tokens_per_s").read(run_with(reqs, seconds=10.0)) == pytest.approx(0.4)
    assert metric("output_tokens_per_s").read(run_with([], seconds=10.0)) is None
    # the step in progress at the close, 9.5 s to 10.5 s, counts with the
    # half of its one token that falls inside the window
    r = run_with(reqs, seconds=10.0, steps=[(0.5, 1.0), (9.5, 10.5)])
    assert metric("output_tokens_per_s").read(r) == pytest.approx(4.5 / 10.0)


def test_setup():
    assert metric("setup_s").read(run_with([], setup_s=12.5)) == 12.5


def test_every_named_metric_has_a_reader():
    spec = harness.load_spec()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists(), m["name"]
    for w in spec["workloads"]:
        e2e = harness.cell_metrics(spec, w["name"], False)
        assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
        assert harness.cell_metrics(spec, w["name"], True)

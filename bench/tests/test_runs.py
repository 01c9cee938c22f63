"""Whole runs on the CPU at a small size, through the same harness as the
chip runs: the harness's look for a chip is skipped, the rest runs. Each
fault planted in the timed path underneath must turn ``correct`` false."""
import os
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.tests import tiny

ROOT = str(harness.ROOT)


def run_tiny(cell, trace=False, seconds=3.0):
    mix = tiny.chat() if cell == "phi4-chat" else tiny.docs()
    return harness.run_cell(cell, 2**31 + 99, seconds, trace, t_start=time.perf_counter(),
                            require_tpu=False, peaks=tiny.PEAKS, config=tiny.config(), mix=mix,
                            log=lambda *a: None)


@pytest.mark.parametrize("cell", ["phi4-chat", "phi4-docs"])
def test_sound_run_is_correct(cell):
    r = run_tiny(cell, trace=cell == "phi4-docs")
    assert r["correct"] is True
    assert list(r)[-1] == "checks"
    assert r["checks"]["logit_gap"]["value"] < 0.05
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"]


def test_token_altered_where_it_is_produced_is_caught(monkeypatch):
    lm_sys = harness.load_module(harness.BENCH / "systems" / "lm_engine.py")
    orig = lm_sys.System.setup

    def setup(self):
        orig(self)
        decode, calls = self.engine.decode_fn, [0]

        def altered(*args):
            logits, state = decode(*args)
            calls[0] += 1
            if calls[0] % 3 == 0:  # every third step's greedy tokens move by one id
                logits = logits.at[:, -1].set(jax.numpy.roll(logits[:, -1], 1, axis=-1))
            return logits, state

        self.engine.decode_fn = altered

    import jax

    monkeypatch.setattr(lm_sys.System, "setup", setup)
    r = run_tiny("phi4-chat")
    assert r["correct"] is False
    assert r["checks"]["logit_gap"]["value"] > r["checks"]["logit_gap"]["limit"]


def tiny_system(cell, seed, seconds):
    import jax

    mix = tiny.chat() if cell == "phi4-chat" else tiny.docs()
    run = harness.Run(cell, tiny.config(), mix, seed, seconds, tiny.PEAKS)
    lm_sys = harness.load_module(harness.BENCH / "systems" / "lm_engine.py")
    s = lm_sys.System(tiny.config(), mix, seed, jax.devices()[:1], run)
    s.setup()
    s.drive(seconds, lambda now: None)
    return s


@pytest.mark.parametrize("cell", ["phi4-chat", "phi4-docs"])
def test_control_is_not_correct(cell):
    """The float8 control in the program's place, judged by the run's own
    comparison against the configuration's limit, at the small size."""
    s = tiny_system(cell, 2**31 + 3, 3.0)
    s.free()
    checks, _, _ = s.check()
    control, _, failed = s.check(control=True)
    assert harness.verdict(checks) is True
    assert harness.verdict(control) is False and failed > 0
    assert s.run.info["control_check"]["requests"] >= 2


def test_output_rate_counts_the_step_in_progress_at_the_close():
    s = tiny_system("phi4-docs", 2**31 + 4, 2.0)
    run = s.run
    start, end = run.steps[-1]
    assert start < run.seconds <= end
    late = sum(1 for r in run.requests for t in r.times if t == end)
    assert late > 0
    rate = harness.load_module(harness.BENCH / "metrics" / "output_tokens_per_s.py").read(run)
    share = (run.seconds - start) / (end - start)
    assert rate == pytest.approx((run.info["requests"]["output_tokens"] + late * share) / run.seconds)


def test_measurement_path_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload", "phi4-chat",
                          "--seed", "1", "--seconds", "1", "--trace", "0"],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "correct" not in out.stdout

"""Whole runs of the SUMMA cell on the CPU at a small size, through the same
harness as the chip runs, on four CPU devices in a subprocess (the test
process sees one): a sound run is ``correct``; one element of one sampled
call's C altered where the call returns it, and the program called on
operands rounded to bfloat16 in the program's place, are not."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness

ROOT = str(harness.ROOT)

# each dim divides over the 2x2 grid; at this size on the CPU sound runs read
# about 2e-7 and the bf16-rounded control about 2e-3
SMALL = {"ni": 256, "nj": 320, "nk": 176, "check": {"rel_err": 1e-4}}

RUNS = """
import json, os, sys, time
sys.path.insert(0, ROOT)
from bench import harness

config = json.loads((harness.BENCH / "configs" / "summa-xl-f32.json").read_text())
config.update(SMALL)
summa = harness.load_module(harness.BENCH / "systems" / "summa.py")
peaks = {"bf16_flops": 197e12, "hbm_bw": 819e9}


def run():
    return harness.run_cell("summa-xl-2x2", 2**31 + 99, 1.0, False, t_start=time.perf_counter(),
                            require_tpu=False, peaks=peaks, config=config, log=lambda *a: None)


out = {"sound": run()}

drive = summa.System.drive


def altered_drive(self, seconds, tick):
    fn, target, calls = self.fn, min(self.sampled_calls(seconds)), [0]

    def call(a, b):
        c = fn(a, b)
        if calls[0] == target:
            c = c.at[0, 0, 0, 0].add(1.0)
        calls[0] += 1
        return c

    self.fn = call
    drive(self, seconds, tick)


summa.System.drive = altered_drive
out["altered"] = run()
summa.System.drive = drive

import jax
from bench import traffic
mix = traffic.load("summa-loop")
r = harness.Run("summa-xl-2x2", config, mix, 2**31 + 7, 1.0, peaks)
s = summa.System(config, mix, 2**31 + 7, jax.devices()[:4], r)
s.setup()
s.drive(1.0, lambda now: None)
s.free()
out["sound_check"] = s.check()[0]
checks, _, failed = s.check(control=True)
out["control"] = {"checks": checks, "failed": failed, "info": r.info["control_check"]}

program = summa.summa_ring_program


def bf16_program(**kw):
    fn, meta = program(**kw)
    rounded = jax.jit(lambda x: x.astype(jax.numpy.bfloat16).astype(x.dtype))
    return (lambda a, b: fn(rounded(a), rounded(b))), meta


summa.summa_ring_program = bf16_program
try:
    run()
    out["bf16_program"] = "ran"
except RuntimeError as e:
    out["bf16_program"] = str(e)
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    code = f"ROOT = {ROOT!r}\nSMALL = {SMALL!r}\n" + RUNS
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          timeout=600, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.split("RESULT ", 1)[1])


def test_sound_run_is_correct(runs):
    r = runs["sound"]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    assert r["checks"]["rel_err"]["value"] < 1e-6
    assert set(r["metrics"]) == {"ttft_p90_ms", "setup_s"}
    assert r["metrics"]["ttft_p90_ms"]["value"] > 0
    assert r["device"]["count"] == 4


def test_c_altered_where_it_is_produced_is_caught(runs):
    r = runs["altered"]
    assert r["correct"] is False and r["failed"] == 1
    assert r["checks"]["rel_err"]["value"] > r["checks"]["rel_err"]["limit"]


def test_control_is_not_correct(runs):
    """The program on bf16-rounded operands, judged by the run's own
    comparison against the small configuration's limit."""
    assert harness.verdict(runs["sound_check"]) is True
    c = runs["control"]
    assert harness.verdict(c["checks"]) is False and c["failed"] == 1
    assert [call for call, _ in c["info"]["rel_err_by_call"]] == ["control"]


def test_program_below_the_stated_precision_fails_in_setup(runs):
    """A program that rounds its operands to bfloat16 never reaches the
    window: set-up compares its C with the float64 product and stops."""
    assert "does not compute at the configuration's precision" in runs["bf16_program"]

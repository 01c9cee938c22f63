"""Readings for a cell's correctness limit, many seeds in one process: for
each seed a fresh system (weights or data from that seed), a short window
at the cell's own load, then the check, once as a run makes it and once
with its control in the program's place. Prints, per seed, each number
compared beside its limit and the verdict, for the program and for the
control.

    python bench/tools/calibrate.py --workload phi4-chat --seeds 11,12,13 --seconds 35

The control is the configuration's reference computed one precision step
below the configuration's (``low=True`` in ``bench/configs/<config>.py``)
and judged by the same comparison and limit as the program: a sound limit
has the program ``correct`` on every seed and the control not ``correct``
on any.
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from bench import harness, traffic  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=25)
    args = ap.parse_args()
    harness.enable_cache()
    spec = harness.load_spec()
    w = harness.find(spec["workloads"], args.workload)
    entry = harness.find(spec["configs"], w["config"])
    config = json.loads((harness.ROOT / entry["file"]).read_text())
    mix = traffic.load(w["traffic"])
    devs = harness.devices_for(w["chips"], True)
    from bench.peaks import peaks

    mod = harness.load_module(harness.BENCH / "systems" / f"{config['system']}.py")
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        run = harness.Run(args.workload, config, mix, seed, args.seconds, peaks(devs[0].device_kind))
        system = mod.System(config, mix, seed, devs, run)
        system.setup()
        system.drive(args.seconds, lambda now: None)
        system.free()
        checks, attempted, failed = system.check()
        control, _, control_failed = system.check(control=True)
        print(json.dumps({"seed": seed, "correct": harness.verdict(checks), "checks": checks,
                          "attempted": attempted, "failed": failed,
                          "control_correct": harness.verdict(control), "control_checks": control,
                          "control_failed": control_failed, "check": run.info.get("check"),
                          "control_check": run.info.get("control_check"),
                          "requests": run.info.get("requests"), "seconds": time.perf_counter() - t0}), flush=True)
        del system, run
        gc.collect()


if __name__ == "__main__":
    main()

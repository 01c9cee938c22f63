"""Find the highest arrival rate the engine sustains, in one process: one
set-up, then one window per rate, the backlog emptied between windows.

    python bench/tools/sweep.py --workload phi4-chat --rates 1,1.5,2 --seconds 30 --seed 5

For each rate it prints the requests due and finished, the requests
waiting for a slot at each quarter of the window and at its close (a
backlog that grows across the window means the rate is above what the
engine sustains), the completed requests per second and the TTFT and ITL
tails.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from bench import harness, traffic  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    harness.enable_cache()
    spec = harness.load_spec()
    w = harness.find(spec["workloads"], args.workload)
    entry = harness.find(spec["configs"], w["config"])
    config = json.loads((harness.ROOT / entry["file"]).read_text())
    mix = traffic.load(w["traffic"])
    devs = harness.devices_for(w["chips"], True)
    from bench.peaks import peaks

    run = harness.Run(args.workload, config, mix, args.seed, args.seconds, peaks(devs[0].device_kind))
    system = harness.load_module(harness.BENCH / "systems" / f"{config['system']}.py").System(
        config, mix, args.seed, devs, run)
    system.setup()
    ttft = harness.load_module(harness.BENCH / "metrics" / "ttft_p90_ms.py")
    itl = harness.load_module(harness.BENCH / "metrics" / "itl_p99_ms.py")
    for rate in [float(r) for r in args.rates.split(",")]:
        system.mix = dict(mix, rate_per_s=rate)
        run.calls, run.info = [], {}
        marks, waiting = [args.seconds * f for f in (0.25, 0.5, 0.75)], []

        def tick(now):
            while len(waiting) < len(marks) and now >= marks[len(waiting)]:
                waiting.append(len(system.engine.queue))

        system.drive(args.seconds, tick)
        done = [r for r in run.requests if r.times and len(r.times) == r.max_new and r.times[-1] <= args.seconds]
        waits = sorted(r.times[0] - r.arrival for r in run.requests if r.times)
        print(json.dumps({"rate_per_s": rate, **run.info["requests"],
                          "waiting_by_quarter": waiting + [run.info["requests"]["waiting_at_close"]],
                          "completed_per_s": len(done) / args.seconds,
                          "ttft_p50_ms": 1e3 * waits[len(waits) // 2] if waits else None,
                          "ttft_p90_ms": ttft.read(run), "itl_p99_ms": itl.read(run)}), flush=True)
        eng = system.engine
        eng.queue.clear()
        eng.run()
        eng.finished.clear()


if __name__ == "__main__":
    main()

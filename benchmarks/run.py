"""Entry point for the paper's tables that are not timings.  Prints
``name,us_per_call,derived`` CSV blocks.

  * feature_matrix  — paper Table 1 (programmatic feature checks)

Usage: PYTHONPATH=src python -m benchmarks.run [--skip feature_matrix]

Each section runs in a child process of its own and this parent never
imports JAX: a device belongs to one process at a time.  Performance is
measured on the chip by ``bench/run.py`` (``BENCHMARK.json``).
"""
import argparse
import os
import subprocess
import sys
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip", action="append", default=[])
    args = ap.parse_args()

    # (skip key, title, module, call that returns the section's lines)
    sections = [
        ("feature_matrix", "feature_matrix (paper Table 1)", "feature_matrix", "run()"),
    ]

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    failures = 0
    for key, title, module, call in sections:
        if key in args.skip:
            continue
        print(f"\n=== {title} ===", flush=True)
        t0 = time.time()
        code = f"from benchmarks import {module}\nfor line in {module}.{call}:\n    print(line)"
        proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env)
        if proc.returncode == 0:
            print(f"# section completed in {time.time()-t0:.1f}s")
        else:
            failures += 1
            print(f"# SECTION FAILED: exit code {proc.returncode}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()

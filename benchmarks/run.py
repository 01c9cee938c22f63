"""Benchmark harness entry point: one section per paper table/figure plus
the LM-framework extensions.  Prints ``name,us_per_call,derived`` CSV blocks.

  * feature_matrix  — paper Table 1 (programmatic feature checks)
  * relayout_bench  — paper §3.2 transform taxonomy microbench
  * gemm_layouts    — paper Fig. 3 (8 C/A/B layout configs, MINI+EXTRALARGE,
                      8 ranks) — pass --quick to use MINI only
  * lm_step_bench   — per-arch smoke train/decode step times
  * roofline_table  — §Roofline aggregation of the dry-run artifacts

Usage: PYTHONPATH=src python -m benchmarks.run [--quick] [--skip gemm_layouts]

Each section runs in a child process of its own and this parent never
imports JAX: a device belongs to one process at a time, and a parent holding
it would leave a section's child (``gemm_layouts`` starts one) without it.
"""
import argparse
import os
import subprocess
import sys
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="smaller datasets")
    ap.add_argument("--skip", action="append", default=[])
    args = ap.parse_args()

    datasets = ("MINI",) if args.quick else ("MINI", "EXTRALARGE")
    # (skip key, title, module, call that returns the section's lines)
    sections = [
        ("feature_matrix", "feature_matrix (paper Table 1)", "feature_matrix", "run()"),
        ("relayout_bench", "relayout_bench (paper §3.2)", "relayout_bench", "run()"),
        ("gemm_layouts", "gemm_layouts (paper Fig. 3)", "gemm_layouts",
         f"run(datasets={datasets!r})"),
        ("lm_step_bench", "lm_step_bench (framework)", "lm_step_bench", "run()"),
        ("roofline_table", "roofline_table singlepod (§Roofline)", "roofline_table",
         "run('singlepod')"),
        ("roofline_table", "roofline_table multipod (§Dry-run)", "roofline_table",
         "run('multipod')"),
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

"""Serving launcher: load (or init) a model and run batched generation
through the continuous-batching engine.

Usage:
  python -m repro.launch.serve --arch phi4-mini-3.8b --smoke \
      --requests 6 --max-new 16
  python -m repro.launch.serve --arch phi4-mini-3.8b --smoke \
      --grid 4x2 --microbatches 2 --fake-devices 8   # explicit TP decode

``--grid R x C`` switches decode to the explicit tensor-parallel step
(:mod:`repro.serve.tp_decode`): per-layer reductions issued as non-blocking
collectives staggered behind the next microbatch's compute.
``--fake-devices`` forces that many XLA host devices (CPU bring-up).
``--max-steps`` bounds the decode loop; requests still resident when the
budget runs out are reported as in-flight with their partial outputs.
``--trace-dir DIR`` runs the submit/run loop under ``jax.profiler.trace(DIR)``:
open DIR in TensorBoard or Perfetto to see the engine's phase spans
(``engine.admit``, ``engine.prefill_launch``, ``engine.decode_launch``,
``engine.fetch``, ``engine.sample``) and each request's ``engine.queued``
span, with their arguments (admitted and queued requests, KV bytes in use,
rows per step, prefill bucket, bytes fetched a step), on the device events'
clock.
"""
import argparse
import contextlib
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-steps", type=int, default=10_000)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--grid", default=None, metavar="DxM",
                    help="data x model grid: decode through the explicit "
                         "TP step with staggered non-blocking collectives")
    ap.add_argument("--microbatches", type=int, default=2,
                    help="stagger depth of the TP decode comm plan")
    ap.add_argument("--fake-devices", type=int, default=0,
                    help="force N XLA host devices (CPU bring-up of --grid)")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="write a profiler trace of the serving loop to DIR")
    args = ap.parse_args()

    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.fake_devices}"
        )

    import jax
    import numpy as np

    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import lm
    from repro.serve.engine import Engine, ServeConfig

    enable_compile_cache()
    cfg = configs.get(args.arch, smoke=args.smoke)
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    if args.ckpt_dir:
        from repro.ckpt.manager import CheckpointManager

        from repro.train.optimizer import OptConfig, init_opt_state

        mgr = CheckpointManager(args.ckpt_dir)
        # training checkpoints carry {params, opt}; build a matching template
        restored, _ = mgr.restore({"params": params, "opt": init_opt_state(params, OptConfig())})
        params = restored["params"]
        print(f"[serve] restored from {mgr.latest_step()}")

    mesh = None
    microbatches = 0
    if args.grid:
        from repro.core import make_mesh

        grid = tuple(int(x) for x in args.grid.split("x"))
        mesh = make_mesh(grid, ("data", "model"))
        microbatches = args.microbatches
        print(f"[serve] explicit TP decode on {grid} "
              f"(data x model), {microbatches} staggered microbatches")

    scfg = ServeConfig(max_len=args.max_len, batch_slots=args.slots,
                       temperature=args.temperature, eos_token=-1)
    engine = Engine(cfg, params, scfg, mesh=mesh, microbatches=microbatches)
    rng = np.random.default_rng(0)
    traced = jax.profiler.trace(args.trace_dir) if args.trace_dir else contextlib.nullcontext()
    t0 = time.perf_counter()
    total_new = 0
    with traced:
        for rid in range(args.requests):
            prompt = rng.integers(2, min(cfg.vocab, 1000), size=rng.integers(3, 10)).tolist()
            engine.submit(rid, prompt, args.max_new)
            total_new += args.max_new
        done = engine.run(max_steps=args.max_steps)
    dt = time.perf_counter() - t0
    for rid in sorted(done):
        print(f"[serve] req {rid}: {done[rid]}")
    for rid, toks in sorted(engine.in_flight.items()):
        print(f"[serve] req {rid}: IN-FLIGHT after {args.max_steps} steps, "
              f"{len(toks)} tokens so far: {toks}")
    occ = engine.ledger.valid_fraction()
    print(f"[serve] {len(done)} done / {len(engine.in_flight)} in flight, "
          f"{total_new} tokens requested in {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s, kv occupancy {occ:.2f})")
    sys.exit(0 if len(done) == args.requests else 1)


if __name__ == "__main__":
    main()

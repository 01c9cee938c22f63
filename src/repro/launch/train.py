"""Production training launcher: mesh + recipe + data + checkpointing +
fault tolerance.

Fault-tolerance model (scales to 1000+ nodes; exercised here on the local
mesh):
  * deterministic step-indexed data  -> restart anywhere is exact;
  * async atomic checkpoints every --ckpt-every steps, keep-K rotation;
  * --watchdog wraps the training loop in a supervisor: if the trainer
    process dies or stops heartbeating (hang, "node failure"), it is
    restarted from the latest checkpoint — the single-host stand-in for a
    cluster-level supervisor (GKE/Borg restart policy + persistent store);
  * elastic rescale: on restart the mesh is rebuilt from the devices
    currently visible; checkpoints restore under the *new* recipe-derived
    shardings (layout-agnostic restore — see ckpt/manager.py).

XLA flags for a real TPU run (recorded here; harmless on CPU):
  --xla_tpu_enable_async_collective_fusion=true
  --xla_tpu_overlap_compute_collective_tc=true
  --xla_enable_async_all_gather=true

Usage:
  python -m repro.launch.train --arch phi4-mini-3.8b --smoke --steps 50
  python -m repro.launch.train --arch qwen2.5-32b --smoke --watchdog --steps 200
"""
import argparse
import dataclasses
import json
import os
import signal
import subprocess
import sys
import time


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--compress", default="none", choices=["none", "int8"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--data", default="synthetic")
    ap.add_argument("--data-path", default=None)
    ap.add_argument("--attn-mode", default="auto")
    ap.add_argument("--watchdog", action="store_true", help="supervise + auto-restart")
    ap.add_argument("--heartbeat-timeout", type=float, default=300.0)
    ap.add_argument("--max-restarts", type=int, default=3)
    ap.add_argument("--crash-at-step", type=int, default=None, help="fault-injection (tests)")
    return ap.parse_args(argv)


# --------------------------------------------------------------- watchdog ----

def watchdog(args) -> int:
    """Supervise the trainer; restart from checkpoint on crash or hang."""
    restarts = 0
    child_args = [a for a in sys.argv[1:] if a != "--watchdog"]
    hb_path = os.path.join(args.ckpt_dir, "HEARTBEAT")
    while True:
        proc = subprocess.Popen([sys.executable, "-m", "repro.launch.train"] + child_args,
                                env=dict(os.environ))
        while True:
            try:
                proc.wait(timeout=10)
                break
            except subprocess.TimeoutExpired:
                if os.path.exists(hb_path):
                    age = time.time() - os.path.getmtime(hb_path)
                    if age > args.heartbeat_timeout:
                        print(f"[watchdog] heartbeat stale ({age:.0f}s) — killing trainer")
                        proc.send_signal(signal.SIGKILL)
        if proc.returncode == 0:
            print("[watchdog] training completed")
            return 0
        restarts += 1
        if restarts > args.max_restarts:
            print(f"[watchdog] giving up after {restarts-1} restarts")
            return 1
        print(f"[watchdog] trainer exited rc={proc.returncode}; restart {restarts} from latest checkpoint")


# ------------------------------------------------------------------ train ----

def train(args) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.configs.base import ShapeCell
    from repro.ckpt.manager import CheckpointManager
    from repro.data.pipeline import DataConfig, make_batch
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_local_mesh
    from repro.models import lm
    from repro.models.sharding import make_recipe, batch_shardings
    from repro.train.optimizer import OptConfig, init_opt_state
    from repro.train.trainer import make_train_step

    enable_compile_cache()
    cfg = configs.get(args.arch, smoke=args.smoke)
    cell = ShapeCell("train", seq_len=args.seq_len, global_batch=args.global_batch, kind="train")
    dcfg = DataConfig(source=args.data, path=args.data_path)
    ocfg = OptConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                     total_steps=args.steps, compress=args.compress)

    # elastic: the mesh is whatever devices exist *now*
    n_dev = len(jax.devices())
    model_par = 1 if n_dev == 1 else 2 if n_dev % 2 == 0 else 1
    mesh = make_local_mesh(model=model_par)
    recipe = make_recipe(cfg, mesh, attn_mode=args.attn_mode) if n_dev > 1 else None
    print(f"[train] arch={cfg.name} devices={n_dev} mesh={dict(mesh.shape)} "
          f"attn_mode={recipe.attn_mode if recipe else 'n/a'}")

    specs = lm.build_specs(cfg)
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    if recipe:
        params = jax.tree.map(lambda x, s: jax.device_put(x, s), params,
                              recipe.param_shardings(specs))
    opt = init_opt_state(params, ocfg)

    mgr = CheckpointManager(args.ckpt_dir, keep=3)
    start_step = 0
    latest = mgr.latest_step()
    if latest is not None:
        shardings = {"params": recipe.param_shardings(specs)} if recipe else None
        restored, extra = mgr.restore(
            {"params": params, "opt": opt},
            shardings=None,  # opt-state template shardings inferred from params below
        )
        params, opt = restored["params"], restored["opt"]
        if recipe:
            params = jax.tree.map(lambda x, s: jax.device_put(x, s), params,
                                  recipe.param_shardings(specs))
        start_step = latest
        print(f"[train] resumed from step {latest}")

    step_fn = jax.jit(make_train_step(cfg, recipe, ocfg, microbatches=args.microbatches))
    b_shard = (lambda b: jax.tree.map(lambda x, s: jax.device_put(x, s), b,
                                      batch_shardings(recipe, b))) if recipe else (lambda b: b)

    hb_path = os.path.join(args.ckpt_dir, "HEARTBEAT")
    os.makedirs(args.ckpt_dir, exist_ok=True)
    t_start = time.time()
    for step in range(start_step, args.steps):
        if args.crash_at_step is not None and step == args.crash_at_step and latest is None:
            print(f"[train] FAULT INJECTION: crashing at step {step}", flush=True)
            os._exit(42)
        batch = b_shard(jax.tree.map(jnp.asarray, make_batch(cfg, cell, step, dcfg)))
        params, opt, metrics = step_fn(params, opt, batch)
        open(hb_path, "w").write(str(time.time()))
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"[train] step {step:5d} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} lr={float(metrics['lr']):.2e} "
                  f"({(time.time()-t_start):.1f}s)", flush=True)
        if (step + 1) % args.ckpt_every == 0 or step == args.steps - 1:
            mgr.save_async(step + 1, {"params": params, "opt": opt},
                           extra={"loss": float(metrics["loss"])})
    mgr.wait()
    print(f"[train] done: {args.steps} steps, final ckpt at {mgr.latest_step()}")
    return 0


def main() -> None:
    args = parse_args()
    if args.watchdog:
        sys.exit(watchdog(args))
    sys.exit(train(args))


if __name__ == "__main__":
    main()

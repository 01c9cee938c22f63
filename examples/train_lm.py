"""End-to-end driver: pretrain a ~100M-param dense LM for a few hundred
steps on synthetic data, with sharding, checkpointing and (optional)
fault-injection + auto-restart.

This is the example-scale version of ``repro.launch.train``; at full scale
the same code path runs the assigned architectures (see the dry-run).

Run (CPU, ~minutes):
  python examples/train_lm.py --steps 200
  python examples/train_lm.py --steps 200 --devices 8   # 4x2 mesh, sharded
  python examples/train_lm.py --steps 200 --devices 8 --zero
      # data-parallel mesh, explicit ZeRO-2 step: bucketed grad
      # reduce-scatters + sharded AdamW + param all-gather prefetch
"""
import argparse
import os
import sys

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=200)
ap.add_argument("--devices", type=int, default=1)
ap.add_argument("--seq-len", type=int, default=256)
ap.add_argument("--global-batch", type=int, default=16)
ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
ap.add_argument("--zero", action="store_true",
                help="explicit ZeRO-2 train step on a pure data mesh "
                     "(requires --devices > 1)")
ap.add_argument("--bucket-kb", type=int, default=4096,
                help="gradient bucket threshold (KiB) for --zero")
args = ap.parse_args()

if args.devices > 1 and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={args.devices}"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeCell
from repro.ckpt.manager import CheckpointManager
from repro.data.pipeline import DataConfig, make_batch
from repro.models import lm
from repro.models.sharding import make_recipe, batch_shardings
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.trainer import make_train_step

# ~100M params: 12 layers, d=768, untied 32k vocab
CFG = ArchConfig(
    name="demo-100m", family="dense",
    n_layers=12, d_model=768, n_heads=12, n_kv=4, d_ff=2048,
    vocab=32000, head_dim=64, attn_block=256,
)
print(f"model: {CFG.name}, {lm.count_params(CFG)/1e6:.1f}M params")

cell = ShapeCell("train", seq_len=args.seq_len, global_batch=args.global_batch, kind="train")
dcfg = DataConfig(seed=0)
ocfg = OptConfig(lr=3e-4, warmup_steps=20, total_steps=args.steps)

recipe = None
mesh = None
if args.zero:
    if args.devices < 2:
        ap.error("--zero needs --devices > 1 (a data-parallel mesh)")
    from repro.core import make_mesh
    mesh = make_mesh((args.devices,), ("data",))
    print(f"mesh {dict(mesh.shape)}, explicit ZeRO-2 step "
          f"(bucket threshold {args.bucket_kb} KiB)")
elif args.devices > 1:
    from repro.core import make_mesh
    mesh = make_mesh((args.devices // 2, 2), ("data", "model"))
    recipe = make_recipe(CFG, mesh)
    print(f"mesh {dict(mesh.shape)}, attn_mode={recipe.attn_mode}, bindings={recipe.bindings}")

params = lm.init_model(CFG, jax.random.PRNGKey(0))
specs = lm.build_specs(CFG)
if recipe:
    params = jax.tree.map(lambda x, s: jax.device_put(x, s), params, recipe.param_shardings(specs))

if args.zero:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.train.optimizer import init_zero_opt_state
    from repro.train.trainer import make_zero_train_step, zero_train_buckets

    buckets = zero_train_buckets(CFG, bucket_bytes=args.bucket_kb << 10,
                                 ranks=args.devices)
    print(f"{len(buckets)} gradient buckets, "
          f"largest {max(b.nbytes for b in buckets)/2**20:.1f} MiB")
    params = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P())), params)
    opt = init_zero_opt_state(params, buckets, ocfg)
    shard = lambda t: jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P("data"))), t)
    opt = opt._replace(mu=shard(opt.mu), nu=shard(opt.nu), err=shard(opt.err))
    step_fn = jax.jit(make_zero_train_step(
        CFG, mesh, ocfg, microbatches=2, bucket_bytes=args.bucket_kb << 10))
else:
    opt = init_opt_state(params, ocfg)
    step_fn = jax.jit(make_train_step(CFG, recipe, ocfg, microbatches=2))
mgr = CheckpointManager(args.ckpt_dir, keep=2)

import time

t0 = time.time()
for step in range(args.steps):
    batch = jax.tree.map(jnp.asarray, make_batch(CFG, cell, step, dcfg))
    if recipe:
        batch = jax.tree.map(lambda x, s: jax.device_put(x, s), batch, batch_shardings(recipe, batch))
    elif args.zero:
        from jax.sharding import NamedSharding, PartitionSpec as P
        batch = jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(mesh, P("data"))), batch)
    params, opt, m = step_fn(params, opt, batch)
    if step % 10 == 0:
        tok_s = (step + 1) * cell.global_batch * cell.seq_len / (time.time() - t0)
        print(f"step {step:4d}  loss {float(m['loss']):.4f}  gnorm {float(m['grad_norm']):.2f}  "
              f"{tok_s:,.0f} tok/s", flush=True)
    if (step + 1) % 50 == 0:
        mgr.save_async(step + 1, {"params": params, "opt": opt})
mgr.wait()
print(f"done in {time.time()-t0:.1f}s; checkpoints: {mgr.all_steps()}")

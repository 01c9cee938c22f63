"""LM substrate benchmark: smoke-scale train and decode step times for every
assigned architecture (CPU wall-clock; the full-scale numbers are the
dry-run roofline terms in benchmarks/results/).

A second table times the attention hot-path kernels themselves — the
carry-state flash step that sp_ring runs once per ring hop and the split-KV
decode kernel the serving engine runs per token — jnp reference vs the
Pallas kernel in interpret mode.  ``--attn-kernel-json PATH`` writes those
rows as the nightly ``attn_kernel_bench.json`` artifact.  Interpret-mode
wall-clock on CPU is a correctness-path number, not a perf claim; the
compiled-Pallas column only exists on a real TPU."""
import sys, os, time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

if any(a.startswith(("--moe", "--train")) for a in sys.argv):
    # the expert-parallel MoE and ZeRO train rows lower real fake-mesh
    # programs — fake the devices before jax initializes
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import ShapeCell
from repro.data.pipeline import DataConfig, make_batch
from repro.models import lm
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.trainer import make_train_step

CELL = ShapeCell("bench", seq_len=64, global_batch=4, kind="train")


def _time(fn, reps=5):
    fn()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(jax.tree.leaves(out)[0])
    return (time.perf_counter() - t0) / reps


def _serve_tok_s(cfg, params) -> float:
    """End-to-end engine throughput (tokens/sec): continuous batching with
    admission + prefill + greedy decode, timed on warm jits (the first
    request wave pays compilation, the second is measured)."""
    from repro.serve.engine import Engine, ServeConfig

    scfg = ServeConfig(max_len=64, batch_slots=2, temperature=0.0, eos_token=-1)
    eng = Engine(cfg, params, scfg)
    max_new = 8
    for rid in range(2):  # warm wave: compiles prefill + decode
        eng.submit(rid, [3 + rid, 7, 11], max_new_tokens=max_new)
    eng.run()
    for rid in range(2, 6):
        eng.submit(rid, [3 + rid, 7, 11], max_new_tokens=max_new)
    t0 = time.perf_counter()
    eng.run()
    return 4 * max_new / (time.perf_counter() - t0)


def run() -> list[str]:
    out = ["arch,train_us_per_call,decode_us_per_call,serve_tok_s"]
    key = jax.random.PRNGKey(0)
    for arch in configs.ARCH_IDS:
        cfg = configs.get(arch, smoke=True)
        params = lm.init_model(cfg, key)
        ocfg = OptConfig(warmup_steps=1)
        opt = init_opt_state(params, ocfg)
        batch = jax.tree.map(jnp.asarray, make_batch(cfg, CELL, 0, DataConfig()))
        step = jax.jit(make_train_step(cfg, None, ocfg))
        t_train = _time(lambda: step(params, opt, batch)[2]["loss"])

        state = lm.DecodeState(caches=lm.init_cache(cfg, CELL.global_batch, 128),
                               positions=jnp.zeros((CELL.global_batch,), jnp.int32))
        dec_batch = {}
        if cfg.input_kind == "embeds":
            dec_batch["embeds"] = jnp.zeros((CELL.global_batch, 1, cfg.d_model))
        else:
            dec_batch["tokens"] = jnp.zeros((CELL.global_batch, 1), jnp.int32)
        if cfg.input_kind == "tokens+image":
            dec_batch["image_embeds"] = jnp.zeros((CELL.global_batch, cfg.enc_len, cfg.enc_dim))
        dstep = jax.jit(lambda p, s, b: lm.decode_step(p, s, b, cfg))
        t_dec = _time(lambda: dstep(params, state, dec_batch)[0])
        # the engine does not feed encoder inputs, so the VLM family has no
        # serving row (cross-attn needs per-request image embeds)
        tok_s = "" if cfg.family == "vlm" else f"{_serve_tok_s(cfg, params):.1f}"
        out.append(f"{arch},{t_train*1e6:.0f},{t_dec*1e6:.0f},{tok_s}")
    return out


def attn_kernel_rows() -> list[dict]:
    """Time one sp_ring ring-step compute and one decode-attention call in
    both impls at representative smoke shapes (f32, CPU)."""
    from functools import partial

    from repro.kernels import ops

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    rows = []
    impls = (("ref", "jnp"), ("interpret", "pallas_interpret"))

    # one ring step: resident Q chunk vs the held KV block, carry threaded
    B, Hq, G, Sl, D = 2, 8, 2, 64, 32
    q = jax.random.normal(kq, (B, Hq, Sl, D), jnp.float32)
    k = jax.random.normal(kk, (B, G, Sl, D), jnp.float32)
    v = jax.random.normal(kv, (B, G, Sl, D), jnp.float32)
    for impl, label in impls:
        fn = jax.jit(partial(ops.flash_attention_carry, causal=True,
                             q_offset=Sl, k_offset=0, impl=impl, bq=Sl, bk=Sl))
        t = _time(lambda: fn(q, k, v))
        rows.append({"kernel": "sp_ring_step", "impl": label,
                     "shape": f"B{B}xH{Hq}xG{G}xS{Sl}xD{D}",
                     "us_per_call": t * 1e6})

    # one decode step: a single token per slot against the paged cache
    T = 128
    dq = jax.random.normal(kq, (B, Hq, 1, D), jnp.float32)
    kc = jax.random.normal(kk, (B, G, T, D), jnp.float32)
    vc = jax.random.normal(kv, (B, G, T, D), jnp.float32)
    clen = jnp.full((B,), T, jnp.int32)
    for impl, label in impls:
        fn = jax.jit(partial(ops.flash_decode, impl=impl, bk=64))
        t = _time(lambda: fn(dq, kc, vc, clen))
        rows.append({"kernel": "decode", "impl": label,
                     "shape": f"B{B}xH{Hq}xG{G}xT{T}xD{D}",
                     "us_per_call": t * 1e6})
    return rows


def moe_dispatch_rows() -> list[dict]:
    """Dense capacity dispatch vs expert-parallel ragged a2a dispatch on the
    phi3.5-MoE smoke shapes over a fake (2, 4) mesh: tokens/sec plus the
    modeled a2a valid/wire bytes against the dense path's replication bytes
    (valid must be strictly below dense replication — the whole point of
    routing tokens instead of replicating the expert table)."""
    from repro.core import make_mesh
    from repro.models import ffn
    from repro.models.module import init_params
    from repro.models.sharding import make_recipe, use_recipe

    cfg = configs.get("phi3.5-moe-42b-a6.6b", smoke=True)
    mesh = make_mesh((2, 4), ("data", "model"))
    recipe = make_recipe(cfg, mesh)
    B, S, m, E, k = 4, 64, cfg.d_model, cfg.n_experts, cfg.moe_top_k
    D, R = 2, 4
    T = B * S
    Tl = (B // D) * (S // R)
    cf = cfg.moe_capacity_factor
    counts = ffn.moe_ep_counts(E, Tl, k, cf)
    sched = ffn.moe_ep_schedule(E, R, counts, 2)
    dense_cap = int(max(k, round(k * T / E * cf)))  # moe_ffn's global C
    model = ffn.moe_comm_model(sched, d_model=m, itemsize=4,
                               dense_capacity=dense_cap)
    assert model["valid_bytes"] < model["dense_replication_bytes"]

    p = init_params(ffn.moe_specs(m, cfg.d_ff, E), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, m), jnp.float32)

    dense_fn = jax.jit(lambda xv: ffn.moe_ffn(p, xv, n_experts=E, top_k=k,
                                              capacity_factor=cf)[0])
    def ep(xv):
        with use_recipe(recipe):
            return ffn.moe_expert_parallel(p, xv, n_experts=E, top_k=k,
                                           counts=counts, n_groups=2)[0]
    with mesh:
        ep_fn = jax.jit(ep)
        t_ep = _time(lambda: ep_fn(x))
    t_dense = _time(lambda: dense_fn(x))

    def row(mode, t, wire, valid):
        return {"mode": mode, "tokens_per_s": T / t, "us_per_call": t * 1e6,
                "model_wire_bytes": wire, "model_valid_bytes": valid,
                "shape": f"B{B}xS{S}xm{m}xE{E}k{k}", "grid": "2x4"}

    return [
        # dense/grouped dispatch replicates the full (E*C, m) scatter table
        # across the model axis instead of moving routed tokens: wire ==
        # valid == the replication bytes
        row("dense_capacity", t_dense,
            model["dense_replication_bytes"], model["dense_replication_bytes"]),
        row("expert_parallel", t_ep,
            model["wire_bytes"], model["valid_bytes"]),
    ]


def train_step_rows() -> list[dict]:
    """GSPMD baseline vs the explicit ZeRO-2 step on a fake 8-way data mesh:
    tokens/sec wall-clock (CPU smoke shapes) plus the statically proven
    exposed collective bytes and the analytic wire/valid bytes of each
    schedule — the nightly evidence that the declared bucket plan hides its
    reduce-scatters/all-gathers while the baseline makes no such claim."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import make_mesh
    from repro.launch import hlo_walk
    from repro.train.buckets import zero_comm_model
    from repro.train.optimizer import init_zero_opt_state
    from repro.train.trainer import make_zero_train_step, zero_train_buckets

    arch = "phi4-mini-3.8b"
    cfg = configs.get(arch, smoke=True)
    R = 8
    mesh = make_mesh((R,), ("data",))
    cell = ShapeCell("bench", seq_len=64, global_batch=16, kind="train")
    tokens = cell.global_batch * cell.seq_len
    ocfg = OptConfig(warmup_steps=1)
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P())), params)
    batch = jax.tree.map(jnp.asarray, make_batch(cfg, cell, 0, DataConfig()))
    batch = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P("data"))), batch)

    rows = []

    opt = init_opt_state(params, ocfg)
    base = jax.jit(make_train_step(cfg, None, ocfg))
    t_base = _time(lambda: base(params, opt, batch)[2]["loss"])
    st = hlo_walk.analyze(base.lower(params, opt, batch).compile().as_text())
    rows.append({
        "mode": "gspmd_baseline", "arch": arch, "grid": f"{R}x1",
        "tokens_per_s": tokens / t_base, "us_per_call": t_base * 1e6,
        "exposed_bytes": st.exposed_collective_bytes(),
        "serialized": st.collectives_serialized(),
        "model_wire_bytes": None, "model_valid_bytes": None,
    })

    bucket_bytes = 64 << 10
    bkts = zero_train_buckets(cfg, bucket_bytes=bucket_bytes, ranks=R)
    model = zero_comm_model(bkts)
    zopt = init_zero_opt_state(params, bkts, ocfg)
    shard = lambda t: tuple(
        jax.device_put(x, NamedSharding(mesh, P("data"))) for x in t)
    zopt = zopt._replace(mu=shard(zopt.mu), nu=shard(zopt.nu))
    zstep = jax.jit(make_zero_train_step(cfg, mesh, ocfg,
                                         bucket_bytes=bucket_bytes))
    t_zero = _time(lambda: zstep(params, zopt, batch)[2]["loss"])
    st = hlo_walk.analyze(zstep.lower(params, zopt, batch).compile().as_text(),
                          valid_fractions=model["valid_fractions"])
    rows.append({
        "mode": "zero_explicit", "arch": arch, "grid": f"{R}x1",
        "tokens_per_s": tokens / t_zero, "us_per_call": t_zero * 1e6,
        "exposed_bytes": st.exposed_collective_bytes(),
        "serialized": st.collectives_serialized(),
        "model_wire_bytes": model["wire_bytes"],
        "model_valid_bytes": model["valid_bytes"],
        "n_buckets": len(bkts),
    })
    return rows


if __name__ == "__main__":
    import argparse, json

    ap = argparse.ArgumentParser()
    ap.add_argument("--attn-kernel-json", default=None,
                    help="write the attention-kernel rows to this JSON path")
    ap.add_argument("--kernels-only", action="store_true",
                    help="skip the per-arch table (fast nightly artifact run)")
    ap.add_argument("--moe-dispatch-json", default=None,
                    help="write the dense-vs-expert-parallel MoE dispatch "
                         "rows to this JSON path (nightly artifact)")
    ap.add_argument("--moe-only", action="store_true",
                    help="run only the MoE dispatch rows (fast artifact run)")
    ap.add_argument("--train-json", default=None,
                    help="write the GSPMD-vs-ZeRO train-step rows to this "
                         "JSON path (nightly train_step_bench.json artifact)")
    ap.add_argument("--train-only", action="store_true",
                    help="run only the train-step rows (fast artifact run)")
    args = ap.parse_args()

    train_csv = "mode,arch,grid,tokens_per_s,exposed_bytes,serialized,model_wire_bytes,model_valid_bytes"

    def train_csv_line(r):
        return (f"{r['mode']},{r['arch']},{r['grid']},{r['tokens_per_s']:.1f},"
                f"{r['exposed_bytes']},{r['serialized']},"
                f"{r['model_wire_bytes']},{r['model_valid_bytes']}")

    if args.train_only:
        rows = train_step_rows()
        print("\n".join([train_csv] + [train_csv_line(r) for r in rows]))
        if args.train_json:
            with open(args.train_json, "w") as f:
                json.dump({"rows": rows, "backend": jax.default_backend()}, f, indent=2)
        sys.exit(0)

    if args.moe_only:
        moe = moe_dispatch_rows()
        lines = ["mode,shape,grid,tokens_per_s,model_wire_bytes,model_valid_bytes"]
        lines += [f"{r['mode']},{r['shape']},{r['grid']},{r['tokens_per_s']:.1f},"
                  f"{r['model_wire_bytes']},{r['model_valid_bytes']}" for r in moe]
        print("\n".join(lines))
        if args.moe_dispatch_json:
            with open(args.moe_dispatch_json, "w") as f:
                json.dump({"rows": moe, "backend": jax.default_backend()}, f, indent=2)
        sys.exit(0)

    lines = [] if args.kernels_only else run()
    kern = attn_kernel_rows()
    lines += ["", "kernel,impl,shape,us_per_call"]
    lines += [f"{r['kernel']},{r['impl']},{r['shape']},{r['us_per_call']:.0f}"
              for r in kern]
    moe = moe_dispatch_rows() if args.moe_dispatch_json else None
    if moe:
        lines += ["", "mode,shape,grid,tokens_per_s,model_wire_bytes,model_valid_bytes"]
        lines += [f"{r['mode']},{r['shape']},{r['grid']},{r['tokens_per_s']:.1f},"
                  f"{r['model_wire_bytes']},{r['model_valid_bytes']}" for r in moe]
    train_rows = train_step_rows() if args.train_json else None
    if train_rows:
        lines += ["", train_csv] + [train_csv_line(r) for r in train_rows]
    print("\n".join(lines).lstrip("\n"))
    if args.attn_kernel_json:
        with open(args.attn_kernel_json, "w") as f:
            json.dump({"rows": kern, "backend": jax.default_backend()}, f, indent=2)
    if args.moe_dispatch_json and moe:
        with open(args.moe_dispatch_json, "w") as f:
            json.dump({"rows": moe, "backend": jax.default_backend()}, f, indent=2)
    if args.train_json and train_rows:
        with open(args.train_json, "w") as f:
            json.dump({"rows": train_rows, "backend": jax.default_backend()}, f, indent=2)

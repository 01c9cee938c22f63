"""Compile a cell's programs at full size for a described TPU v5e chip, on a
machine without one, and print what the chip's compiler says of each:
whether it accepts it, and its memory.

    JAX_PLATFORMS=cpu python bench/tools/compile_check.py

Compiles the weight draw, the engine's prefill step at (8 slots, 2048
positions) and (8, 1024), its decode step, and one layer and the head of
the float32 reference at the check's block of rows.
"""
import dataclasses
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import harness  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)


def main():
    lm_sys = harness.load_module(harness.BENCH / "systems" / "lm_engine.py")
    ref = harness.load_module(harness.BENCH / "configs" / "phi4-mini-3.8b.py")
    from repro.models import lm
    from repro.serve.engine import Engine, ServeConfig

    config = json.loads((harness.BENCH / "configs" / "phi4-mini-3.8b.json").read_text())
    cfg = dataclasses.replace(lm_sys.arch_config(config), attn_impl="pallas")
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    on = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)

    def report(name, lowered):
        t0 = time.perf_counter()
        c = lowered.compile()
        m = c.memory_analysis()
        print(json.dumps({"program": name, "compile_s": round(time.perf_counter() - t0, 1),
                          "argument_bytes": m.argument_size_in_bytes, "output_bytes": m.output_size_in_bytes,
                          "temp_bytes": m.temp_size_in_bytes, "alias_bytes": m.alias_size_in_bytes,
                          "custom_call": "tpu_custom_call" in c.as_text()}), flush=True)

    params = on(lm.abstract_model(cfg))
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one)
    report("weights", lm_sys.weights_fn(cfg).lower(key))
    d = config["deployment"]
    B, T = d["batch_slots"], d["max_len"]
    eng = Engine(cfg, jax.tree.map(lambda x: x, params), ServeConfig(max_len=T, batch_slots=B))
    state = on(jax.eval_shape(lambda: eng.state))
    counts = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one)
    for S in (2048, 1024):
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one)}
        report(f"prefill B={B} S={S}", eng.prefill_fn.lower(params, state, batch, counts))
    batch = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one)}
    report(f"decode B={B}", eng.decode_fn.lower(params, state, batch, counts))
    x = jax.ShapeDtypeStruct((2, T, cfg.d_model), jnp.float32, sharding=one)
    tg = jax.ShapeDtypeStruct((2, T), jnp.int32, sharding=one)
    kw = dict(heads=cfg.n_heads, kv=cfg.n_kv, theta=cfg.rope_theta, rot=cfg.head_dim, eps=1e-5)
    for low in (False, True):
        report(f"reference layer low={low}", ref._layer.lower(params["blocks"], 0, x, low=low, **kw))
        report(f"reference head low={low}", ref._head.lower(params["embed"], params["final_norm"], x, tg,
                                                             vocab=cfg.vocab, eps=1e-5, low=low))


if __name__ == "__main__":
    main()

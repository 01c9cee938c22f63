"""Smoke run of the system's main paths on TPU v5e chips.

This checks that the paths run on the chip and give the right answers. It is
not a benchmark: its times include cold compiles and one-off set-up.

  python chip_smoke.py               # one chip: serve phi4-mini-3.8b
  python chip_smoke.py --four-chips  # four chips: SUMMA GEMM + TP decode

One chip.  ``phi4-mini-3.8b`` at its published widths with bf16 weights
drawn from ``--seed`` is served by the continuous-batching engine
(``lm.init_model`` -> ``serve.engine.Engine.submit/run``): 12 requests with
prompts of 128-1024 tokens and 32 new tokens each, on 8 slots, so admission
runs again when slots free up.  Checks: every request ends with 32 tokens;
every step's logits are finite; the compiled decode step holds the Pallas
kernel (``tpu_custom_call``); and the first admission's prefill (one
one-row call a slot) and the first decode step agree with the same steps
built with ``attn_impl="jnp"``.

Four chips (``--four-chips``), one process driving all of them: the paper's
SUMMA GEMM at PolyBench EXTRALARGE on a 2x2 grid against the host's f32
``A @ B``, and the tensor-parallel engine serving full-width phi4-mini on a
(1, 4) (data, model) mesh against the one-chip engine: its first
admission's prefill and first decode step, and each device holding only
its shard of the weights.

The last line of standard output is ``{"ok": true, "device": {...}}``.  It is
printed only when every phase passed; any failure exits non-zero before it,
and so does a run where JAX finds no TPU.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

SEED = 0
N_REQUESTS, MAX_NEW, PROMPT_LENS = 12, 32, (128, 1024)
SLOTS, MAX_LEN = 8, 2048

# Logit tolerances, as ||got - want||_2 / ||want||_2 over the active rows.
# Pallas vs jnp attention: the kernel rounds the unnormalized probability
# tile to bf16, the jnp path the normalized one; at phi4-mini widths the
# attention outputs differ by 2.9e-3 (each ~2e-3 from an f32 reference),
# and the random-weight network amplifies that to 8.3e-3 of the logits
# after 1 layer, 9.7e-3 after 2 and 1.04e-2 after 4 (interpret-mode kernel
# on the CPU), 1.5e-2 after 32 (on the chip).  3e-2 leaves room for that; a
# wrong mask, head mapping or shard order moves the logits by O(1).
LOGIT_TOL = 3e-2
# TP on four chips vs one chip, same attention kernel: the head and FFN
# contractions split over 4 ranks sum in f32 in another order.  With f32
# activations the two differ by 1.1e-6 to 1.3e-6 (full width, 1 and 4
# layers, CPU).  In bf16 those differences flip rare roundings, and the
# random-weight network grows the flips with depth: 9.2e-4 after 1 layer
# and 7.3e-3 after 4 on the CPU; 6.9e-3 after 4 and 1.44e-2 after 32 on the
# chip.  3e-2 is twice the largest reading; a causal mask one position off
# in the TP step gives 1.7e-1 after 4 layers.
TP_LOGIT_TOL = 3e-2
# SUMMA vs the host's f32 product, as max |err| / max |C|: the v5e MXU may
# take f32 operands in bf16 passes, ~2^-9 relative per operand; over
# nk = 1408 random-sign terms that is ~2e-3 of max |C|.
SUMMA_TOL = 1e-2


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def tpu_devices():
    """The TPU devices, or exit non-zero before any work."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform!r}", file=sys.stderr)
        sys.exit(2)
    return devs


class StepRecorder:
    """Wraps an engine step program ``(params, state, batch, counts[, slot])
    -> (logits, state)``: compiles each new input signature ahead of time
    and times it, runs the compiled executable, keeps the first ``keep``
    calls' inputs and the logits of their active rows, and fails on
    non-finite logits of any active row."""

    def __init__(self, name, fn, keep: int = 1):
        self.name, self.fn, self.keep = name, fn, keep
        self.compiled = {}
        self.compile_s = []
        self.kept = []

    def __call__(self, params, state, batch, counts, *slot):
        import jax
        import numpy as np

        key = str(jax.tree.map(lambda x: (x.shape, str(x.dtype)), (batch, slot)))
        if key not in self.compiled:
            t0 = time.perf_counter()
            self.compiled[key] = self.fn.lower(params, state, batch, counts, *slot).compile()
            self.compile_s.append(time.perf_counter() - t0)
            log(f"compiled {self.name} for {key}: {self.compile_s[-1]:.1f} s")
        logits, state = self.compiled[key](params, state, batch, counts, *slot)
        active = np.asarray(counts) > 0
        rows = np.asarray(logits[:, -1], np.float32)[active]
        if not np.isfinite(rows).all():
            raise RuntimeError(f"{self.name}: non-finite logits")
        if len(self.kept) < self.keep:
            self.kept.append(dict(args=(batch, counts, *slot), logits=rows))
        return logits, state

    def logits(self):
        """The kept calls' logits of their active rows, in call order."""
        import numpy as np

        return np.concatenate([c["logits"] for c in self.kept])

    def hlo(self) -> str:
        return next(iter(self.compiled.values())).as_text()


def compare(name: str, got, want, tol: float) -> None:
    import numpy as np

    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    agree = float((got.argmax(-1) == want.argmax(-1)).mean())
    log(f"{name}: rel L2 {rel:.3e} (tolerance {tol:g}), max |diff| "
        f"{float(np.abs(got - want).max()):.3e}, max |logit| "
        f"{float(np.abs(want).max()):.3e}, argmax agreement {agree:.3f}")
    if not rel <= tol:
        raise RuntimeError(f"{name}: rel L2 {rel:.3e} exceeds {tol:g}")


def phi4_bf16():
    import jax.numpy as jnp

    from repro import configs

    return dataclasses.replace(configs.get("phi4-mini-3.8b"), param_dtype=jnp.bfloat16)


def make_prompts(cfg, n: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, size=n)
    return [rng.integers(0, cfg.vocab, size=int(m)).tolist() for m in lens]


def init_params(cfg, seed: int):
    import jax

    from repro.models import lm

    t0 = time.perf_counter()
    params = jax.block_until_ready(lm.init_model(cfg, jax.random.PRNGKey(seed)))
    leaves = jax.tree.leaves(params)
    log(f"{cfg.name}: {sum(x.size for x in leaves)} params, "
        f"{sum(x.nbytes for x in leaves)} bytes, made in {time.perf_counter() - t0:.1f} s")
    return params


def device_bytes(dev, key: str = "peak_bytes_in_use") -> int:
    return dev.memory_stats()[key]


def serve_phase(cfg, *, seed: int) -> None:
    """Serve N_REQUESTS through the engine, then replay its first
    admission's prefill calls and first decode step with jnp attention and
    compare logits."""
    import jax
    import numpy as np

    from repro.serve.engine import Engine, ServeConfig

    params = init_params(cfg, seed)
    scfg = ServeConfig(max_len=MAX_LEN, batch_slots=SLOTS, temperature=0.0, eos_token=-1)
    prompts = make_prompts(cfg, N_REQUESTS, seed)
    log(f"{N_REQUESTS} requests, prompt lengths {[len(p) for p in prompts]}, "
        f"{MAX_NEW} new tokens each, {SLOTS} slots")

    engine = Engine(cfg, params, scfg)
    # the first admission fills every slot before the first decode step,
    # one prefill call a slot
    prefill = engine.prefill_fn = StepRecorder("prefill", engine.prefill_fn, keep=SLOTS)
    decode = engine.decode_fn = StepRecorder("decode", engine.decode_fn)
    for rid, prompt in enumerate(prompts):
        engine.submit(rid, prompt, max_new_tokens=MAX_NEW)
    t0 = time.perf_counter()
    done = engine.run()
    wall = time.perf_counter() - t0
    compile_s = sum(prefill.compile_s) + sum(decode.compile_s)
    new = {rid: len(done.get(rid, ())) - len(p) for rid, p in enumerate(prompts)}
    log(f"engine.run: {wall:.1f} s wall, of which {compile_s:.1f} s compiling; "
        f"{sum(new.values())} tokens produced")
    if any(n != MAX_NEW for n in new.values()):
        raise RuntimeError(f"requests did not all finish with {MAX_NEW} tokens: {new}")
    if "tpu_custom_call" not in decode.hlo():
        raise RuntimeError("the compiled decode step holds no tpu_custom_call")
    log(f"peak_bytes_in_use after serving: {device_bytes(jax.devices()[0])}")
    del engine

    # The same steps with jnp attention, from a fresh cache: the first
    # admission's prefill calls replayed in order, then the first decode step.
    ref = Engine(dataclasses.replace(cfg, attn_impl="jnp"), params, scfg)
    ref_prefill = StepRecorder("prefill (jnp)", ref.prefill_fn, keep=SLOTS)
    ref_decode = StepRecorder("decode (jnp)", ref.decode_fn)
    state = ref.state
    for call in prefill.kept:
        _, state = ref_prefill(params, state, *call["args"])
    ref_decode(params, state, *decode.kept[0]["args"])
    del ref, state
    compare("first admission's prefill, pallas vs jnp", prefill.logits(),
            ref_prefill.logits(), LOGIT_TOL)
    compare("first decode step, pallas vs jnp", decode.logits(),
            ref_decode.logits(), LOGIT_TOL)
    log(f"peak_bytes_in_use: {device_bytes(jax.devices()[0])}")


def summa_phase() -> None:
    """The paper's SUMMA GEMM at PolyBench EXTRALARGE over all devices."""
    import jax
    import numpy as np

    sys.path.insert(0, ROOT)
    from examples.distributed_gemm import default_grid, run_summa_gemm
    from repro.configs.gemm_case_study import DATASETS

    ni, nj, nk = DATASETS["EXTRALARGE"]
    grid = default_grid(len(jax.devices()))
    t0 = time.perf_counter()
    C, want = run_summa_gemm(ni=ni, nj=nj, nk=nk, grid=grid, majors="I/I/K")
    err = float(np.abs(C - want).max())
    scale = float(np.abs(want).max())
    log(f"SUMMA {grid} grid, (ni, nj, nk) = ({ni}, {nj}, {nk}): max_err {err:.3e}, "
        f"max |C| {scale:.3e}, ratio {err / scale:.3e} (tolerance {SUMMA_TOL:g}); "
        f"{time.perf_counter() - t0:.1f} s with compile")
    if not err <= SUMMA_TOL * scale:
        raise RuntimeError(f"SUMMA max_err {err:.3e} exceeds {SUMMA_TOL:g} x {scale:.3e}")


def first_steps(engine, label: str, prompts):
    """Admit ``prompts`` and run one engine step; returns the logits of the
    admission's prefill (every call's active rows, in slot order) and of the
    first decode step."""
    prefill = engine.prefill_fn = StepRecorder(f"prefill ({label})", engine.prefill_fn, keep=SLOTS)
    decode = engine.decode_fn = StepRecorder(f"decode ({label})", engine.decode_fn)
    for rid, prompt in enumerate(prompts):
        engine.submit(rid, prompt, max_new_tokens=MAX_NEW)
    engine.run(max_steps=1)
    return prefill.logits(), decode.logits()


def tp_decode_phase(cfg, *, seed: int) -> None:
    """The TP engine on a (1, 4) mesh against the one-chip engine, from the
    same weights and prompts."""
    import jax

    from repro.core import make_mesh
    from repro.serve.engine import Engine, ServeConfig

    params = init_params(cfg, seed)
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    scfg = ServeConfig(max_len=MAX_LEN, batch_slots=SLOTS, temperature=0.0, eos_token=-1)
    prompts = make_prompts(cfg, SLOTS, seed)
    mesh = make_mesh((1, len(jax.devices())), ("data", "model"))
    one = Engine(cfg, params, scfg)
    want = first_steps(one, "one chip", prompts)
    del one
    tp = Engine(cfg, params, scfg, mesh=mesh, microbatches=2)
    del params  # from here each device holds only its shard of the weights
    got = first_steps(tp, "TP", prompts)
    in_use = [device_bytes(d, "bytes_in_use") for d in jax.devices()]
    log("bytes_in_use per device with the TP engine: "
        + ", ".join(f"{d.id}: {n}" for d, n in zip(jax.devices(), in_use)))
    log("peak_bytes_in_use per device: "
        + ", ".join(f"{d.id}: {device_bytes(d)}" for d in jax.devices()))
    if max(in_use) > param_bytes / 2:
        raise RuntimeError(f"a device holds {max(in_use)} bytes under TP, more than "
                           f"half the {param_bytes} bytes of weights")
    compare("first admission's prefill, TP (1, 4) vs one chip", got[0], want[0], TP_LOGIT_TOL)
    compare("first decode step, TP (1, 4) vs one chip", got[1], want[1], TP_LOGIT_TOL)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phases (SUMMA GEMM, TP decode)")
    ap.add_argument("--seed", type=int, default=SEED)
    args = ap.parse_args()

    devs = tpu_devices()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    log("smoke run: checks that the path runs and is right; not a benchmark")
    log(f"device_kind {devs[0].device_kind}, {len(devs)} device(s), "
        f"compile cache {enable_compile_cache()}")
    if args.four_chips:
        if len(devs) != 4:
            raise RuntimeError(f"--four-chips needs 4 devices, found {len(devs)}")
        summa_phase()
        tp_decode_phase(phi4_bf16(), seed=args.seed)
    else:
        serve_phase(phi4_bf16(), seed=args.seed)
    print(json.dumps({"ok": True, "device": {"platform": devs[0].platform,
                                              "kind": devs[0].device_kind,
                                              "count": len(devs)}}))


if __name__ == "__main__":
    main()

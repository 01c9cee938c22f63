"""Compile rehearsals for the TPU v5e: the main path's Pallas kernels at
phi4-mini-3.8b widths, compiled by the TPU compiler for a described (not
attached) ``v5e:2x2`` topology.  Nothing runs; a refusal here (block shapes
off the (8, 128) tiling, too much VMEM) is what the chip itself would raise.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import dataclasses
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro import configs
from repro.kernels.flash_attention import flash_attention_carry_pallas, flash_attention_pallas
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.gemm import gemm_panel_pallas

# phi4-mini-3.8b attention widths (configs/phi4_mini_3_8b.py): 24 query
# heads over 8 KV groups of head_dim 128; 8 serving slots
B, HQ, G, D = 8, 24, 8, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """Compiles for a described chip are written to the persistent cache but
    cannot be read back without one; keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _arg(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("S,T", [(1, 2048), (1024, 2048)], ids=["decode", "prefill_chunk"])
def test_flash_decode_compiles_for_v5e(one_chip, no_cache, S, T):
    """The serving attention: a decode step (S=1) and a whole-prompt prefill
    chunk (S=1024) over a 2048-position bf16 cache."""
    bf = jnp.bfloat16
    txt = _compiled_text(
        lambda q, k, v, n, s: flash_decode_pallas(q, k, v, n, q_start=s),
        _arg(one_chip, (B, HQ, S, D), bf),
        _arg(one_chip, (B, G, T, D), bf),
        _arg(one_chip, (B, G, T, D), bf),
        _arg(one_chip, (B,), jnp.int32),
        _arg(one_chip, (B,), jnp.int32),
    )
    assert "tpu_custom_call" in txt


def test_flash_attention_carry_compiles_for_v5e(one_chip, no_cache):
    """One sp_ring step: a resident 1024-row query chunk against a held KV
    block, (acc, m, l) carried in and out."""
    Sq, Skv = 1024, 1024
    bf, f32 = jnp.bfloat16, jnp.float32
    carry = (
        _arg(one_chip, (1, HQ, Sq, D), f32),
        _arg(one_chip, (1, HQ, Sq), f32),
        _arg(one_chip, (1, HQ, Sq), f32),
    )
    txt = _compiled_text(
        lambda q, k, v, c, qo, ko: flash_attention_carry_pallas(
            q, k, v, c, q_offset=qo, k_offset=ko),
        _arg(one_chip, (1, HQ, Sq, D), bf),
        _arg(one_chip, (1, G, Skv, D), bf),
        _arg(one_chip, (1, G, Skv, D), bf),
        carry,
        _arg(one_chip, (), jnp.int32),
        _arg(one_chip, (), jnp.int32),
    )
    assert "tpu_custom_call" in txt


def test_flash_attention_compiles_for_v5e(one_chip, no_cache):
    """Training/prefill attention over a 4096-token sequence."""
    S = 4096
    bf = jnp.bfloat16
    txt = _compiled_text(
        flash_attention_pallas,
        _arg(one_chip, (1, HQ, S, D), bf),
        _arg(one_chip, (1, G, S, D), bf),
        _arg(one_chip, (1, G, S, D), bf),
    )
    assert "tpu_custom_call" in txt


def test_gemm_panel_compiles_for_v5e(one_chip, no_cache):
    """The SUMMA inner step at PolyBench EXTRALARGE on a 2x2 grid: a
    (1024, 704) A tile times a (704, 1280) B panel into a (1024, 2560)
    partial panel; 704 divides no 128-multiple, so K is one whole block."""
    f32 = jnp.float32
    txt = _compiled_text(
        lambda a, b, panel, jb: gemm_panel_pallas(a, b, panel, jb, bk=704),
        _arg(one_chip, (1024, 704), f32),
        _arg(one_chip, (704, 1280), f32),
        _arg(one_chip, (1024, 2560), f32),
        _arg(one_chip, (), jnp.int32),
    )
    assert "tpu_custom_call" in txt


def _mosaic_matmuls(lowered_text):
    """The ``tpu.matmul`` operations of every Mosaic kernel in a lowered
    program, as MLIR text: each kernel's body rides in its custom call's
    backend config as base64 MLIR bytecode."""
    import base64

    from jax._src.lib.mlir import ir

    found = []
    for body in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', lowered_text):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            text = str(ir.Module.parse(base64.b64decode(body)))
        found += [ln.strip() for ln in text.splitlines() if "tpu.matmul" in ln]
    return found


def test_gemm_panel_float32_products_for_v5e(one_chip, no_cache):
    """The SUMMA inner step at the summa-xl-2x2 cell's tile shapes
    (PolyBench EXTRALARGE on a 2x2 grid: mi=1024, kc=704, jr=1280, blocks
    256 x 704 x 256 as ``summa_ring_program`` picks them) compiles with
    float32 operands, and its dot is one float32-precision matmul of the
    float32 tiles, not a product of operands rounded to bf16. bf16 operands
    keep the single bf16 pass."""
    from examples.distributed_gemm import _tile_block

    mi, kc, jr = 1024, 704, 1280
    blocks = dict(bm=_tile_block(mi), bn=_tile_block(jr), bk=_tile_block(kc))
    assert blocks == dict(bm=256, bn=256, bk=704)
    fn = jax.jit(lambda a, b, panel, jb: gemm_panel_pallas(a, b, panel, jb, **blocks))
    for dtype, precision in ((jnp.float32, "precision = #tpu.contract_precision<fp32>"),
                             (jnp.bfloat16, None)):
        lowered = fn.lower(_arg(one_chip, (mi, kc), dtype), _arg(one_chip, (kc, jr), dtype),
                           _arg(one_chip, (mi, 2 * jr), jnp.float32), _arg(one_chip, (), jnp.int32))
        assert "tpu_custom_call" in lowered.compile().as_text()
        (matmul,) = _mosaic_matmuls(lowered.as_text())
        name = jnp.dtype(dtype).name.replace("float", "f")  # f32, bf16
        assert re.search(rf"\(vector<256x704x{name}>, vector<704x256x{name}>", matmul), matmul
        if precision:
            assert precision in matmul, matmul
        else:
            assert "precision" not in matmul, matmul


@pytest.mark.parametrize("S", [1, 1024], ids=["decode", "prefill_chunk"])
def test_tp_decode_reductions_stay_apart_for_v5e(topo, no_cache, S):
    """The explicit TP step at phi4-mini widths (depth cut to 2 layers) on a
    (1, 4) mesh: each microbatch's reduction stays its own all-reduce, one
    ordered behind the other, so each can hide behind the next
    microbatch's compute.  Without that order the TPU compiler combines
    the two microbatches' reductions into one tuple all-reduce, which
    waits for both partials."""
    from repro.models import lm
    from repro.serve.tp_decode import make_tp_decode_step, tp_decode_specs

    L, slots, mb = 2, 8, 2
    cfg = dataclasses.replace(configs.get("phi4-mini-3.8b"),
                              param_dtype=jnp.bfloat16, n_layers=L)
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    p_specs, kv_spec, len_spec = tp_decode_specs(cfg)
    P = jax.sharding.PartitionSpec

    def place(spec, tree):
        return jax.tree.map(lambda x: _arg(NamedSharding(mesh, spec), x.shape, x.dtype), tree)

    params = jax.tree.map(place, p_specs,
                          jax.eval_shape(lambda: lm.init_model(cfg, jax.random.PRNGKey(0))),
                          is_leaf=lambda x: isinstance(x, P))
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, slots, 2048))
    rows = NamedSharding(mesh, P("data"))
    state = lm.DecodeState(
        caches=type(cache)(place(kv_spec, cache.k), place(kv_spec, cache.v),
                           place(len_spec, cache.length)),
        positions=_arg(rows, (slots,), jnp.int32))
    batch = {"tokens": _arg(NamedSharding(mesh, P("data", None)), (slots, S), jnp.int32)}
    step = make_tp_decode_step(cfg, mesh, slots=slots, microbatches=mb, attn_impl="pallas")
    txt = _compiled_text(step, params, state, batch, _arg(rows, (slots,), jnp.int32))
    reductions = re.findall(r"= (.*?) all-reduce(?:-start)?\(", txt)
    # the embedding's routing psum, then one per (layer, stage, microbatch)
    assert len(reductions) == 1 + L * 2 * mb
    assert not [r for r in reductions if r.startswith("(")], "tuple all-reduce"
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("S", [1, 1024], ids=["decode", "prefill_chunk"])
@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "minicpm3-4b"])
def test_engine_steps_update_the_cache_in_place_for_v5e(one_chip, no_cache, arch, S):
    """The engine's step programs at full widths (depth cut to 2 layers; 8
    slots x 2048 positions) write the stacked cache in place: no copy of a
    stacked cache leaf, and the leaves keep the layout the donated buffers
    arrive in.  The decode step feeds all 8 slots; the prefill chunk one
    row, into the slot its traced index names.  A layer scan that passes the cache through its inputs and
    outputs copies it whole; a carried stack laid out to suit the masked
    rows' read is converted in and out.  MLA's 32-wide rope-key cache is
    left out: the compiler lays it out positions-minor on the device and
    re-lays it for the loop, with or without the mask."""
    from repro.models import lm
    from repro.serve.engine import Engine, ServeConfig

    slots, T = 8, 2048
    cfg = dataclasses.replace(configs.get(arch), param_dtype=jnp.bfloat16, n_layers=2,
                              attn_impl="pallas")
    on = lambda tree: jax.tree.map(lambda x: _arg(one_chip, x.shape, x.dtype), tree)
    params = on(jax.eval_shape(lambda: lm.init_model(cfg, jax.random.PRNGKey(0))))
    eng = Engine(cfg, params, ServeConfig(max_len=T, batch_slots=slots))
    state = on(jax.eval_shape(lambda: eng.state))
    if S == 1:
        fn, rows, slot = eng.decode_fn, slots, ()
    else:
        fn, rows, slot = eng.prefill_fn, 1, (_arg(one_chip, (), jnp.int32),)
    txt = fn.lower(params, state, {"tokens": _arg(one_chip, (rows, S), jnp.int32)},
                   _arg(one_chip, (rows,), jnp.int32), *slot).compile().as_text()
    leaves = [state.caches.k, state.caches.v] if arch.startswith("phi4") else [state.caches.c]
    for leaf in leaves:
        shape = re.escape(",".join(map(str, leaf.shape)))
        default = ",".join(map(str, reversed(range(leaf.ndim))))
        layouts = set(re.findall(rf"bf16\[{shape}\]\{{([\d,]+)", txt))
        assert layouts == {default}, (leaf.shape, layouts)
        assert not re.search(rf"= bf16\[{shape}\]\{{[^}}]*\}} copy\(", txt), leaf.shape

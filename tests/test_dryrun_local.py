"""Dry-run machinery integration tests: lower+compile programs on an
8-device mesh (subprocess) and check what the HLO walker proves about them."""


def test_lower_compile_and_hlo_walk_smoke(distributed):
    out = distributed(
        """
import jax, numpy as np
from repro import configs
from repro.configs.base import ShapeCell
from repro.data.pipeline import batch_specs
from repro.models import lm
from repro.models.sharding import make_recipe, batch_shardings
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.trainer import make_train_step
from repro.launch import hlo_walk

cfg = configs.get('phi4-mini-3.8b', smoke=True)
cell = ShapeCell('t', seq_len=128, global_batch=8, kind='train')
from repro.core import make_mesh
mesh = make_mesh((4, 2), ('data', 'model'))
recipe = make_recipe(cfg, mesh)
specs = lm.build_specs(cfg)
params_abs = lm.abstract_model(cfg)
params_sh = recipe.param_shardings(specs)
batch_abs = batch_specs(cfg, cell)
batch_sh = batch_shardings(recipe, batch_abs)
ocfg = OptConfig()
opt_abs = jax.eval_shape(lambda p: init_opt_state(p, ocfg), params_abs)
from jax.sharding import NamedSharding, PartitionSpec as P
opt_sh = type(opt_abs)(step=NamedSharding(mesh, P()), mu=params_sh, nu=params_sh, err=())
step = make_train_step(cfg, recipe, ocfg)
with mesh:
    lowered = jax.jit(step, in_shardings=(params_sh, opt_sh, batch_sh)).lower(params_abs, opt_abs, batch_abs)
    compiled = lowered.compile()
mem = compiled.memory_analysis()
assert mem is not None
st = hlo_walk.analyze(compiled.as_text())
# scan over 2 layers must be loop-multiplied
assert 2 in st.loop_trip_counts, st.loop_trip_counts
assert st.flops > 0 and st.bytes > 0
# there must be real collectives on a 4x2 mesh
assert st.collective_bytes > 0, st.coll_by_op
print('OK flops=%.3g bytes=%.3g coll=%.3g' % (st.flops, st.bytes, st.collective_bytes))
"""
    )
    assert "OK" in out


def test_summa_double_buffer_overlap_hlo(distributed):
    """ISSUE 2 acceptance: the double-buffered SUMMA trace contains exactly
    steps-1 collective-permutes, ALL classified overlapped (0 serialized
    ring-shift transfers), its collective-permute bytes match the analytic
    comm-volume model exactly, and the numerics match the blocking path bit
    for bit at f32."""
    import os

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    out = distributed(
        f"""
import sys
sys.path.insert(0, {root!r})
"""
        + """
import numpy as np
from examples.distributed_gemm import run_summa_gemm, summa_ring_program
from repro.launch import hlo_walk

R, Cc = 4, 2
fn, meta = summa_ring_program(ni=16, nj=16, nk=16, grid=(R, Cc), majors="J/K/J",
                              double_buffer=True)
st = hlo_walk.analyze(fn.lower(*meta["abstract_args"]).compile().as_text())
# exactly steps-1 ring transfers, every one off the compute def-use chain
perms = st.of_kind("collective-permute")
assert len(perms) == R - 1, perms
assert st.collectives_serialized("collective-permute") == 0, perms
assert st.collectives_overlapped("collective-permute") == R - 1
assert st.overlap_fraction("collective-permute") == 1.0
# measured collective-permute bytes == the analytic ring model, exactly
model = meta["comm_model"]
assert st.coll_by_op["collective-permute"] == model["ring_bytes"], (
    st.coll_by_op, model)
assert model["ring_bytes"] == (R - 1) * (16 // Cc) * (16 // R) * 4
assert st.collective_bytes >= model["ring_bytes"]  # + reduce-scatter epilogue
# kind-generic: the reduce-scatter epilogue is terminal (no downstream
# compute) -> 0 serialized collectives of ANY kind, 0 exposed bytes
assert st.collectives_serialized() == 0, st.collectives
assert st.exposed_collective_bytes() == 0.0
assert set(st.overlap_by_kind()) >= {"collective-permute", "reduce-scatter"}

# numerics: double-buffered == blocking, bit for bit at f32
C_db, ref = run_summa_gemm(ni=16, nj=16, nk=16, grid=(R, Cc), majors="J/K/J",
                           double_buffer=True)
C_bl, _ = run_summa_gemm(ni=16, nj=16, nk=16, grid=(R, Cc), majors="J/K/J",
                         double_buffer=False)
assert np.array_equal(C_db, C_bl)
np.testing.assert_allclose(C_db, ref, rtol=1e-3, atol=1e-3)
print('OK')
"""
    )
    assert "OK" in out


def test_pipeline_ring_classified_serialized(distributed):
    """The positive control for the overlap classifier: a ring pipeline that
    ships each dot's OUTPUT to the next rank puts the transfer on the def-use
    chain between consecutive dots — serialized, both unrolled and inside a
    scan's while body (via the loop-carried root->parameter edges)."""
    out = distributed(
        """
import jax, jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.core import make_mesh
from repro.launch import hlo_walk

mesh = make_mesh((8,), ('r',))
pairs = [(i, (i + 1) % 8) for i in range(8)]

def pipeline(x, w):
    def inner(x, w):
        for _ in range(3):
            x = jax.lax.ppermute(jnp.dot(x, w), 'r', pairs)
        return x
    return shard_map(inner, mesh=mesh, in_specs=(P('r', None), P('r', None)),
                     out_specs=P('r', None))(x, w)

x = jax.ShapeDtypeStruct((64, 8), jnp.float32)
st = hlo_walk.analyze(jax.jit(pipeline).lower(x, x).compile().as_text())
# middle transfers sit between two dots; the last one has no downstream dot
perms = st.of_kind("collective-permute")
assert len(perms) == 3 and st.collectives_serialized("collective-permute") == 2, perms

def pipeline_scan(x, w):
    def inner(x, w):
        def body(c, _):
            return jax.lax.ppermute(jnp.dot(c, w), 'r', pairs), None
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out
    return shard_map(inner, mesh=mesh, in_specs=(P('r', None), P('r', None)),
                     out_specs=P('r', None))(x, w)

st = hlo_walk.analyze(jax.jit(pipeline_scan).lower(x, x).compile().as_text())
# one permute in the while body, loop-multiplied, serialized via loop carry
perms = st.of_kind("collective-permute")
assert st.collectives_serialized("collective-permute") >= 1, perms
assert any(p.mult == 5.0 for p in perms), perms

def db_scan(a, b):
    def inner(a, b):
        def body(carry, _):
            acc, cur = carry
            nxt = jax.lax.ppermute(cur, 'r', pairs)
            acc = acc + jnp.dot(a, cur)
            return (acc, jax.lax.optimization_barrier(nxt)), None
        (acc, _), _ = jax.lax.scan(body, (jnp.zeros_like(a), b), None, length=5)
        return acc
    return shard_map(inner, mesh=mesh, in_specs=(P('r', None), P('r', None)),
                     out_specs=P('r', None))(a, b)

st = hlo_walk.analyze(jax.jit(db_scan).lower(x, x).compile().as_text())
# rolled double buffering: the rotating buffer never touches the dot chain
perms = st.of_kind("collective-permute")
assert perms and st.collectives_serialized("collective-permute") == 0, perms
print('OK')
"""
    )
    assert "OK" in out


def test_permute_classification_hand_built_hlo():
    """Walker unit test on hand-written HLO: a permute fed by a dot that
    feeds a later dot is serialized; one fed from a parameter is overlapped."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.launch import hlo_walk

    hlo = """HloModule test

ENTRY %main (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %dot.1 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %p0, f32[8,8]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %cp.1 = f32[8,8]{1,0} collective-permute(f32[8,8]{1,0} %dot.1), source_target_pairs={{0,1},{1,0}}
  %dot.2 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %cp.1, f32[8,8]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %cp.2 = f32[8,8]{1,0} collective-permute(f32[8,8]{1,0} %p1), source_target_pairs={{0,1},{1,0}}
  ROOT %add.1 = f32[8,8]{1,0} add(f32[8,8]{1,0} %dot.2, f32[8,8]{1,0} %cp.2)
}
"""
    by_var = {
        p.var: p.classification
        for p in hlo_walk.classify_collectives(hlo, kinds=("collective-permute",))
    }
    assert by_var == {"%cp.1": "serialized", "%cp.2": "overlapped"}, by_var

    st = hlo_walk.analyze(hlo)
    kind = "collective-permute"
    assert st.collectives_serialized(kind) == 1 and st.collectives_overlapped(kind) == 1
    assert st.overlap_fraction(kind) == 0.5
    assert all(p.bytes == 8 * 8 * 4 for p in st.of_kind(kind))

    # regression: a permute fed by a dot and feeding a while whose BODY (not
    # condition) contains a dot is on the compute chain — the `body=` callee
    # must be extracted from the while line (condition=..., body=... pairs)
    hlo_while = """HloModule testw

%wcond (cp: (f32[8,8], s32[])) -> pred[] {
  %cp = (f32[8,8]{1,0}, s32[]) parameter(0)
  %it = s32[] get-tuple-element((f32[8,8]{1,0}, s32[]) %cp), index=1
  %lim = s32[] constant(3)
  ROOT %lt = pred[] compare(s32[] %it, s32[] %lim), direction=LT
}

%wbody (bp: (f32[8,8], s32[])) -> (f32[8,8], s32[]) {
  %bp = (f32[8,8]{1,0}, s32[]) parameter(0)
  %x = f32[8,8]{1,0} get-tuple-element((f32[8,8]{1,0}, s32[]) %bp), index=0
  %i = s32[] get-tuple-element((f32[8,8]{1,0}, s32[]) %bp), index=1
  %dot.b = f32[8,8]{1,0} dot(f32[8,8]{1,0} %x, f32[8,8]{1,0} %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %inc = s32[] add(s32[] %i, s32[] %one)
  ROOT %out = (f32[8,8]{1,0}, s32[]) tuple(f32[8,8]{1,0} %dot.b, s32[] %inc)
}

ENTRY %main (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %dot.0 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %p0, f32[8,8]{1,0} %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %cp.w = f32[8,8]{1,0} collective-permute(f32[8,8]{1,0} %dot.0), source_target_pairs={{0,1},{1,0}}
  %zero = s32[] constant(0)
  %tup = (f32[8,8]{1,0}, s32[]) tuple(f32[8,8]{1,0} %cp.w, s32[] %zero)
  %loop = (f32[8,8]{1,0}, s32[]) while((f32[8,8]{1,0}, s32[]) %tup), condition=%wcond, body=%wbody
  ROOT %res = f32[8,8]{1,0} get-tuple-element((f32[8,8]{1,0}, s32[]) %loop), index=0
}
"""
    by_var = {
        p.var: p.classification
        for p in hlo_walk.classify_collectives(hlo_while, kinds=("collective-permute",))
    }
    assert by_var == {"%cp.w": "serialized"}, by_var


def test_collective_classification_kind_generic_hand_built_hlo():
    """Kind-generic classifier unit tests on hand-written HLO:

    * an all-gather on a dot->dot chain with no sibling compute is
      serialized, exactly like a permute there (the kind doesn't matter);
    * the *independence clause*: the same chain plus a compute op ordered
      with neither side (a sibling branch the scheduler can hide the
      transfer behind — the double-buffered-ring shape) flips the verdict
      to overlapped;
    * per-kind stats: bytes factors (all-reduce x2), exposed bytes, and the
      permute-only deprecation shims filter correctly.
    """
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.launch import hlo_walk

    # all-gather between two dots, nothing else: serialized (any kind)
    hlo_chain = """HloModule chain

ENTRY %main (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %dot.1 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %p0, f32[8,8]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ag.1 = f32[8,8]{1,0} all-gather(f32[8,8]{1,0} %dot.1), dimensions={0}
  ROOT %dot.2 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %ag.1, f32[8,8]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    cs = hlo_walk.classify_collectives(hlo_chain)
    assert [(c.kind, c.classification) for c in cs] == [("all-gather", "serialized")], cs

    # same chain + an independent sibling dot: the transfer is hideable
    hlo_sibling = """HloModule sibling

ENTRY %main (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %dot.1 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %p0, f32[8,8]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %cp.1 = f32[8,8]{1,0} collective-permute(f32[8,8]{1,0} %dot.1), source_target_pairs={{0,1},{1,0}}
  %dot.2 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %cp.1, f32[8,8]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %dot.3 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %dot.1, f32[8,8]{1,0} %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %add.1 = f32[8,8]{1,0} add(f32[8,8]{1,0} %dot.2, f32[8,8]{1,0} %dot.3)
}
"""
    cs = hlo_walk.classify_collectives(hlo_sibling)
    assert [(c.kind, c.classification) for c in cs] == [
        ("collective-permute", "overlapped")
    ], cs

    # per-kind stats on a mixed-kind module
    hlo_mixed = """HloModule mixed

ENTRY %main (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %dot.1 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %p0, f32[8,8]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar.1 = f32[8,8]{1,0} all-reduce(f32[8,8]{1,0} %dot.1), to_apply=%sum
  %dot.2 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %ar.1, f32[8,8]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %cp.1 = f32[8,8]{1,0} collective-permute(f32[8,8]{1,0} %p1), source_target_pairs={{0,1},{1,0}}
  ROOT %add.1 = f32[8,8]{1,0} add(f32[8,8]{1,0} %dot.2, f32[8,8]{1,0} %cp.1)
}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(f32[] %a, f32[] %b)
}
"""
    st = hlo_walk.analyze(hlo_mixed)
    tb = 8 * 8 * 4
    # the gradient-style all-reduce sits between two dots with no sibling
    assert st.collectives_serialized() == 1 and st.collectives_overlapped() == 1
    assert st.exposed_collective_bytes() == 2 * tb  # all-reduce factor x2
    by_kind = st.overlap_by_kind()
    assert by_kind["all-reduce"]["serialized"] == 1
    assert by_kind["all-reduce"]["exposed_bytes"] == 2 * tb
    assert by_kind["collective-permute"]["overlapped"] == 1
    assert by_kind["collective-permute"]["exposed_bytes"] == 0.0
    # byte-weighted: cp tb overlapped of (cp tb + ar 2tb) total
    assert abs(st.overlap_fraction() - 1.0 / 3.0) < 1e-12
    # the PR-2 permute-only shims (st.permutes etc.) are gone: the
    # kind-generic API above is the only surface
    assert not hasattr(st, "permutes")
    assert not hasattr(hlo_walk, "classify_permutes")


def test_ragged_summa_uneven_gate(distributed):
    """ISSUE 4 acceptance: a SUMMA GEMM with dims NOT divisible by the grid
    sides runs end-to-end via ragged tiles, matches the single-device
    reference, and its dry-run trace shows 0 serialized collectives with
    modeled bytes equal to the analytic ragged ring model — valid bytes
    (35/4 x 35/2 per hop on average), not the padded capacity the wire
    moves."""
    import os

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    out = distributed(
        f"""
import sys
sys.path.insert(0, {root!r})
"""
        + """
import numpy as np
from examples.distributed_gemm import run_ragged_summa_gemm, ragged_summa_program
from repro.launch import hlo_walk

R, Cc = 4, 2  # 35 % 4 = 3, 35 % 2 = 1: every dim is ragged
fn, meta = ragged_summa_program(ni=35, nj=35, nk=35, grid=(R, Cc), majors="J/K/J",
                                double_buffer=True)
model = meta["comm_model"]
st = hlo_walk.analyze(fn.lower(*meta["abstract_args"]).compile().as_text(),
                      valid_fractions=model["valid_fractions"])
# exactly steps-1 ring transfers at padded capacity, all overlapped
perms = st.of_kind("collective-permute")
assert len(perms) == R - 1, perms
assert st.collectives_serialized() == 0, st.collectives
assert st.exposed_collective_bytes() == 0.0
# wire bytes == the padded model, modeled bytes == the VALID ragged model
assert st.coll_by_op["collective-permute"] == model["ring_padded_bytes"], (
    st.coll_by_op, model)
assert abs(st.coll_by_op_valid["collective-permute"] - model["ring_bytes"]) < 1e-6
assert model["ring_bytes"] == (R - 1) * (35 / Cc) * (35 / R) * 4
assert model["ring_bytes"] < model["ring_padded_bytes"]  # padding discounted
by_kind = st.overlap_by_kind()
assert set(by_kind) >= {"collective-permute", "reduce-scatter"}
for row in by_kind.values():
    assert row["valid_bytes"] < row["total_bytes"]  # every kind is ragged here

# numerics: ragged tiles end-to-end == the single-device reference, and the
# double-buffered and blocking variants are bit-identical
C_db, ref = run_ragged_summa_gemm(ni=35, nj=35, nk=35, grid=(R, Cc), majors="J/K/J",
                                  double_buffer=True)
C_bl, _ = run_ragged_summa_gemm(ni=35, nj=35, nk=35, grid=(R, Cc), majors="J/K/J",
                                double_buffer=False)
assert np.array_equal(C_db, C_bl)
np.testing.assert_allclose(C_db, ref, rtol=1e-3, atol=1e-3)
print('OK')
"""
    )
    assert "OK" in out


def test_valid_fractions_discount_padding():
    """Unit test for the wire-vs-valid split on hand-built HLO: a
    valid_fractions entry scales the payload/exposed bytes of its kind while
    the wire figures stay exact; other kinds are untouched."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    import pytest
    from repro.launch import hlo_walk

    hlo = """HloModule chain

ENTRY %main (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %dot.1 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %p0, f32[8,8]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %cp.1 = f32[8,8]{1,0} collective-permute(f32[8,8]{1,0} %dot.1), source_target_pairs={{0,1},{1,0}}
  %ag.1 = f32[8,8]{1,0} all-gather(f32[8,8]{1,0} %cp.1), dimensions={0}
  ROOT %dot.2 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %ag.1, f32[8,8]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    tb = 8 * 8 * 4
    dense = hlo_walk.analyze(hlo)
    ragged = hlo_walk.analyze(hlo, valid_fractions={"collective-permute": 0.75})
    # wire accounting identical
    assert ragged.collective_bytes == dense.collective_bytes == 2 * tb
    assert ragged.coll_by_op == dense.coll_by_op
    # payload accounting discounts only the permute
    assert dense.valid_collective_bytes == 2 * tb
    assert ragged.valid_collective_bytes == 0.75 * tb + tb
    assert ragged.coll_by_op_valid["collective-permute"] == 0.75 * tb
    assert ragged.coll_by_op_valid["all-gather"] == tb
    # exposed bytes (both collectives sit on the dot chain with no sibling)
    assert dense.exposed_collective_bytes() == 2 * tb
    assert ragged.exposed_collective_bytes() == 0.75 * tb + tb
    # per-kind table carries both columns
    bk = ragged.overlap_by_kind()
    assert bk["collective-permute"]["total_bytes"] == tb
    assert bk["collective-permute"]["valid_bytes"] == 0.75 * tb
    # invalid inputs fail loudly
    with pytest.raises(ValueError):
        hlo_walk.analyze(hlo, valid_fractions={"nope": 0.5})
    with pytest.raises(ValueError):
        hlo_walk.analyze(hlo, valid_fractions={"all-gather": 0.0})


def test_hlo_walker_loop_multiplication():
    """The walker's core invariant on a hand-built scan program."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    import jax
    import jax.numpy as jnp
    from repro.launch import hlo_walk

    def f(x, w):
        def body(c, _):
            return jnp.dot(c, w), None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    st = hlo_walk.analyze(compiled.as_text())
    # 7 iterations x (2 * 64^3) flops
    expect = 7 * 2 * 64 ** 3
    assert abs(st.flops - expect) / expect < 0.05, (st.flops, expect)
    assert 7 in st.loop_trip_counts


def test_serve_tp_decode_gate(distributed):
    """ISSUE 7 acceptance: one continuous-batching decode step through the
    explicit TP path compiles to 0 serialized collectives when the per-layer
    reductions are staggered over independent microbatches, the declared
    plan intent agrees with the proven HLO verdict, and the unstaggered
    negative control shows the same reductions ON the critical path."""
    out = distributed(
        """
from repro.launch.dryrun import serve_dryrun
from repro.serve.tp_decode import DECODE_TP_PLAN_INTENT

assert DECODE_TP_PLAN_INTENT == "overlapped"
rep = serve_dryrun(grid=(4, 2), slots=8, microbatches=2, verbose=False)

stag = rep["staggered"]
assert stag["serialized"] == 0, stag  # nothing on the decode critical path
assert stag["plan"]["agree"] and stag["plan"]["proven"] == "overlapped", stag
bk = stag["overlap_by_kind"]
# per-layer TP partial-sum reductions + the terminal vocab all-gather
assert bk["all-reduce"]["overlapped"] > 0 and bk["all-reduce"]["serialized"] == 0
assert bk["all-gather"]["serialized"] == 0
assert stag["exposed_bytes"] == 0.0

# negative control: microbatches=1 has no sibling compute to hide behind —
# the same reductions must be provably serialized (the gate measures the
# schedule, not walker blindness)
single = rep["single"]
assert single["serialized"] > 0, single
assert not single["plan"]["agree"]
print('OK')
"""
    )
    assert "OK" in out


def test_moe_ep_dispatch_gate(distributed):
    """ISSUE 9 acceptance: the expert-parallel MoE FFN compiles to 0
    serialized collectives — both ragged a2a legs (token dispatch + gated
    combine) complete behind sibling expert GEMMs under the double-buffered
    dispatch plan — with walker wire/valid a2a bytes equal to the analytic
    counts-table model, under balanced AND skewed routing (zero-token
    experts riding as zero split extents).  One expert group leaves the
    dispatch leg no sibling compute: the negative control must serialize."""
    out = distributed(
        """
from repro.launch.dryrun import moe_dryrun
from repro.models.ffn import MOE_DISPATCH_PLAN_INTENT

assert MOE_DISPATCH_PLAN_INTENT == "overlapped"
reps = {}
for routing in ("balanced", "skewed"):
    rep = moe_dryrun(routing=routing, verbose=False)
    reps[routing] = rep
    ov = rep["overlapped"]
    assert ov["serialized"] == 0, (routing, ov)
    assert ov["plan"]["agree"] and ov["plan"]["proven"] == "overlapped", (routing, ov)
    # one dispatch + one combine instruction per plan step, all overlapped
    assert ov["all_to_alls"] == 2 * ov["steps"], (routing, ov)
    # the wire is the padded capacity blocks, the valid payload is the
    # MPI_Alltoallv counts table — both must match the walker's accounting
    assert ov["wire_matches_model"] and ov["valid_matches_model"], (routing, ov)
    assert ov["exposed_bytes"] == 0.0, (routing, ov)
    single = rep["single"]
    assert single["serialized_a2a"] > 0, (routing, single)
    assert not single["plan"]["agree"]
# skewed routing concentrates tokens on rank 0's experts: the zero-count
# experts pad the wire, so valid bytes drop strictly below wire bytes
sk = reps["skewed"]["overlapped"]
assert sk["hlo_valid_a2a_bytes"] < sk["hlo_wire_a2a_bytes"], sk
bal = reps["balanced"]["overlapped"]
assert sk["hlo_wire_a2a_bytes"] > bal["hlo_wire_a2a_bytes"]  # padding costs wire
print('OK')
"""
    )
    assert "OK" in out

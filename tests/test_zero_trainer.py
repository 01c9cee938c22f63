"""Explicit ZeRO-2 train step (ISSUE 10): bucket assembly invariants
(counts/displacements over the flattened param pytree), pack/unpack
round-trip, the analytic comm model, microbatch metric accumulation, and —
on the fake mesh — the 0-serialized overlap gate plus bitwise parity of the
explicit step against the GSPMD baseline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.train.buckets import (
    GradBucket,
    assign_buckets,
    bucket_leaves,
    pack_bucket,
    unpack_bucket,
    zero_comm_model,
)


@st.composite
def _leaf_sets(draw):
    n = draw(st.integers(1, 8))
    shapes, dtypes = [], []
    for _ in range(n):
        rank = draw(st.integers(1, 3))
        shapes.append(tuple(draw(st.integers(1, 7)) for _ in range(rank)))
        dtypes.append(draw(st.sampled_from(["float32", "bfloat16"])))
    bucket_bytes = draw(st.sampled_from([64, 256, 1024, 1 << 20]))
    ranks = draw(st.sampled_from([1, 2, 4, 8]))
    return shapes, dtypes, bucket_bytes, ranks


@given(_leaf_sets())
@settings(max_examples=40, deadline=None)
def test_bucket_assembly_properties(case):
    """Every leaf in exactly one bucket (flat order preserved); buckets are
    dtype-homogeneous; a bucket's valid bytes stay under the threshold
    unless a single tensor alone exceeds it; counts/displs are consistent
    prefix-sum tables; padded = ranks * cap >= size."""
    shapes, dtypes, bucket_bytes, ranks = case
    leaves = [jax.ShapeDtypeStruct(s, np.dtype(d)) for s, d in zip(shapes, dtypes)]
    buckets = assign_buckets(leaves, bucket_bytes=bucket_bytes, ranks=ranks)

    covered = [i for b in buckets for i in b.indices]
    assert covered == list(range(len(leaves)))  # exactly once, in flat order

    for b in buckets:
        assert isinstance(b, GradBucket)
        assert len({np.dtype(leaves[i].dtype) for i in b.indices}) == 1
        assert np.dtype(b.dtype) == np.dtype(leaves[b.indices[0]].dtype)
        if len(b.indices) > 1:  # multi-leaf buckets respect the threshold
            assert b.nbytes <= bucket_bytes, (b.nbytes, bucket_bytes)
        assert b.counts == tuple(int(np.prod(s)) for s in b.shapes)
        assert b.displs == tuple(int(d) for d in np.cumsum((0,) + b.counts[:-1]))
        assert b.size == sum(b.counts)
        assert len(b.extents) == ranks
        assert b.padded == b.cap * ranks >= b.size
        assert sum(b.extents) == b.size
        assert all(0 <= e <= b.cap for e in b.extents)


@given(_leaf_sets())
@settings(max_examples=25, deadline=None)
def test_bucket_pack_unpack_roundtrip(case):
    """pack -> unpack is the identity through the counts/displacements
    tables, and re-assembling every bucket's unpacked leaves at their flat
    indices rebuilds the original leaf list exactly."""
    shapes, dtypes, bucket_bytes, ranks = case
    rng = np.random.default_rng(7)
    leaves = [jnp.asarray(rng.standard_normal(s), np.dtype(d))
              for s, d in zip(shapes, dtypes)]
    buckets = assign_buckets(leaves, bucket_bytes=bucket_bytes, ranks=ranks)

    rebuilt = [None] * len(leaves)
    for b in buckets:
        flat = pack_bucket(leaves, b)
        assert flat.shape == (b.padded,) and flat.dtype == leaves[b.indices[0]].dtype
        # the capacity-pad tail is zero
        assert not np.any(np.asarray(flat[b.size:], np.float32))
        outs = unpack_bucket(flat, b)
        assert [o.shape for o in outs] == [l.shape for l in bucket_leaves(leaves, b)]
        for i, o in zip(b.indices, outs):
            rebuilt[i] = o
    for orig, back in zip(leaves, rebuilt):
        np.testing.assert_array_equal(np.asarray(orig), np.asarray(back))


def test_bucket_validation_errors():
    leaves = [jax.ShapeDtypeStruct((4,), np.float32)]
    with pytest.raises(ValueError):
        assign_buckets(leaves, bucket_bytes=0, ranks=4)
    with pytest.raises(ValueError):
        assign_buckets(leaves, bucket_bytes=1024, ranks=0)
    with pytest.raises(ValueError):
        zero_comm_model(())


def test_zero_comm_model_bytes():
    """Walker byte conventions: RS moves one capacity shard per bucket, AG
    the full padded flat; the valid fraction discounts only the capacity
    padding.  A size that does not divide ranks shows wire > valid."""
    leaves = [jax.ShapeDtypeStruct((5, 5), np.float32),  # 25 elems: ragged on 4
              jax.ShapeDtypeStruct((3,), np.float32)]
    buckets = assign_buckets(leaves, bucket_bytes=1 << 20, ranks=4)
    assert len(buckets) == 1 and buckets[0].size == 28 and buckets[0].cap == 7
    m = zero_comm_model(buckets)
    assert m["rs_wire_bytes"] == 4 * 7          # one (cap,) shard
    assert m["ag_wire_bytes"] == 4 * 28         # full padded flat
    assert m["valid_fractions"]["reduce-scatter"] == 1.0  # 28 == 4*7, no pad

    ragged = assign_buckets([jax.ShapeDtypeStruct((10,), np.float32)],
                            bucket_bytes=1 << 20, ranks=4)
    m2 = zero_comm_model(ragged)  # cap = 3, padded = 12 > 10
    assert m2["rs_wire_bytes"] == 4 * 3 and m2["ag_wire_bytes"] == 4 * 12
    assert m2["valid_bytes"] < m2["wire_bytes"]
    frac = 10 / 12
    assert abs(m2["valid_fractions"]["all-gather"] - frac) < 1e-12
    assert abs(m2["rs_valid_bytes"] - m2["rs_wire_bytes"] * frac) < 1e-9


def test_split_batch_raises_on_indivisible():
    """Satellite fix: indivisible microbatching is a ValueError naming the
    shapes, not a bare assert."""
    from repro.train.trainer import _split_batch

    batch = {"tokens": jnp.zeros((6, 8), jnp.int32)}
    with pytest.raises(ValueError, match=r"batch 6 .*4 microbatches"):
        _split_batch(batch, 4)
    out = _split_batch(batch, 2)
    assert out["tokens"].shape == (2, 3, 8)


def test_microbatch_accumulation_keeps_aux_metrics():
    """Satellite fix: the accumulation scan used to drop the per-microbatch
    aux metrics dict; it must now return the same metric keys as the
    unaccumulated step, averaged over microbatches."""
    from repro import configs
    from repro.configs.base import ShapeCell
    from repro.data.pipeline import DataConfig, make_batch
    from repro.models import lm
    from repro.train.optimizer import OptConfig, init_opt_state
    from repro.train.trainer import make_train_step

    cfg = configs.get("phi4-mini-3.8b", smoke=True)
    cell = ShapeCell("t", seq_len=32, global_batch=4, kind="train")
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    ocfg = OptConfig(warmup_steps=1)
    opt = init_opt_state(params, ocfg)
    batch = jax.tree.map(jnp.asarray, make_batch(cfg, cell, 0, DataConfig(seed=4)))

    _, _, m1 = jax.jit(make_train_step(cfg, None, ocfg))(params, opt, batch)
    _, _, m2 = jax.jit(make_train_step(cfg, None, ocfg, microbatches=2))(
        params, opt, batch)
    assert set(m1) == set(m2), (sorted(m1), sorted(m2))
    for k in ("loss", "nll", "aux", "grad_norm", "lr"):
        assert k in m2 and np.isfinite(float(m2[k])), k
    # microbatch average of per-micro means tracks the full-batch mean
    assert abs(float(m1["nll"]) - float(m2["nll"])) < 5e-2


def test_zero_train_overlap_gate(distributed):
    """ISSUE 10 acceptance: the bucketed train step compiles to 0
    serialized reduce-scatter/all-gather collectives in the backward, the
    declared bucket-plan intent agrees with the proven verdict on both
    legs, walker wire/valid bytes equal the analytic ZeRO comm model, and
    the whole-model single bucket serializes its reduce-scatter (negative
    control) — with and without int8 gradient compression."""
    out = distributed(
        """
from repro.launch.dryrun import train_dryrun
from repro.train.trainer import ZERO_TRAIN_PLAN_INTENT

assert ZERO_TRAIN_PLAN_INTENT == "overlapped"
for compress in ("none", "int8"):
    rep = train_dryrun(compress=compress, verbose=False)
    bk = rep["bucketed"]
    assert bk["n_buckets"] > 1, bk
    assert bk["serialized_rs"] == 0 and bk["serialized_ag"] == 0, (compress, bk)
    assert bk["serialized"] == 0, (compress, bk)
    assert bk["plan_rs"]["agree"] and bk["plan_rs"]["proven"] == "overlapped"
    assert bk["plan_ag"]["agree"] and bk["plan_ag"]["proven"] == "overlapped"
    assert bk["wire_matches_model"] and bk["valid_matches_model"], (compress, bk)
    assert bk["exposed_bytes"] == 0.0, (compress, bk)
    # blocking interpretation: same buckets, same wire
    assert rep["blocking"]["wire_matches_model"], compress
    # negative control: one whole-model bucket leaves the reduce-scatter no
    # sibling norm/update math — it must land on the compute chain
    single = rep["single_bucket"]
    assert single["serialized_rs"] > 0, (compress, single)
    assert not single["plan_rs"]["agree"]
print('OK')
"""
    )
    assert "OK" in out


def test_zero_train_bitwise_parity(distributed):
    """ISSUE 10 acceptance: the explicit step's loss and reduced gradients
    match the GSPMD baseline BITWISE at f32 (power-of-two rank scaling
    commutes with rounding), the double-buffered and blocking
    interpretations of the bucket plan are bit-identical, and the updated
    params agree with the baseline to f32 round-off (the clip norm's
    reduction order is the only difference)."""
    out = distributed(
        """
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.configs.base import ShapeCell
from repro.core import make_mesh
from repro.core.collectives import shard_all_gatherv_start, shard_reduce_scatterv_start
from jax import shard_map
from repro.data.pipeline import DataConfig, make_batch
from repro.models import lm
from repro.train.buckets import pack_bucket, unpack_bucket
from repro.train.optimizer import OptConfig, init_opt_state, init_zero_opt_state
from repro.train.trainer import make_train_step, make_zero_train_step, zero_train_buckets

R = 8
cfg = dataclasses.replace(configs.get('phi4-mini-3.8b', smoke=True),
                          act_dtype=jnp.float32)
cell = ShapeCell('t', seq_len=64, global_batch=16, kind='train')
mesh = make_mesh((R,), ('data',))
rep_sh = NamedSharding(mesh, P())
dp_sh = NamedSharding(mesh, P('data'))
params = jax.tree.map(lambda x: jax.device_put(x, rep_sh),
                      lm.init_model(cfg, jax.random.PRNGKey(0)))
batch = jax.tree.map(lambda x: jax.device_put(jnp.asarray(x), dp_sh),
                     make_batch(cfg, cell, 0, DataConfig(seed=2)))
ocfg = OptConfig(lr=3e-3, warmup_steps=5, total_steps=100)

# GSPMD baseline: loss + grads + one Adam step
(base_loss, _), base_grads = jax.jit(
    jax.value_and_grad(lambda p, b: lm.loss_fn(p, b, cfg), has_aux=True))(params, batch)
p_base, _, m_base = jax.jit(make_train_step(cfg, None, ocfg))(
    params, init_opt_state(params, ocfg), batch)

# explicit reduction path: local grads of the LOCAL-mean loss, bucket
# reduce-scatter, /R, regather — must equal the baseline grads bitwise
buckets = zero_train_buckets(cfg, bucket_bytes=64 << 10, ranks=R)
def grads_body(p, b):
    (_, _), g = jax.value_and_grad(lambda p, b: lm.loss_fn(p, b, cfg),
                                   has_aux=True)(p, b)
    leaves, treedef = jax.tree.flatten(g)
    out = [None] * len(leaves)
    for bk in buckets:
        red = shard_reduce_scatterv_start(
            pack_bucket(leaves, bk), 'data', extents=bk.extents).wait()
        full = shard_all_gatherv_start(
            red * (1.0 / R), 'data', extents=bk.extents).wait()
        for i, leaf in zip(bk.indices, unpack_bucket(full, bk)):
            out[i] = leaf
    return jax.tree.unflatten(treedef, out)

rep_tree = jax.tree.map(lambda _: P(), params)
expl_grads = jax.jit(shard_map(
    grads_body, mesh=mesh,
    in_specs=(rep_tree, jax.tree.map(lambda _: P('data'), batch)),
    out_specs=rep_tree, check_vma=False))(params, batch)
for a, b in zip(jax.tree.leaves(base_grads), jax.tree.leaves(expl_grads)):
    assert np.array_equal(np.asarray(a), np.asarray(b)), 'grads not bitwise'

# the full explicit step: loss metric bitwise, params at f32 round-off
def zero_step(db):
    opt = init_zero_opt_state(params, buckets, ocfg)
    opt = opt._replace(
        mu=tuple(jax.device_put(x, dp_sh) for x in opt.mu),
        nu=tuple(jax.device_put(x, dp_sh) for x in opt.nu))
    fn = jax.jit(make_zero_train_step(cfg, mesh, ocfg, bucket_bytes=64 << 10,
                                      double_buffer=db))
    return fn(params, opt, batch)

p_db, o_db, m_db = zero_step(True)
p_bl, o_bl, m_bl = zero_step(False)
assert float(m_db['loss']) == float(base_loss), 'loss not bitwise'

# double-buffered == blocking, bit for bit, across every output
for a, b in zip(jax.tree.leaves((p_db, o_db, m_db)),
                jax.tree.leaves((p_bl, o_bl, m_bl))):
    assert np.array_equal(np.asarray(a), np.asarray(b)), 'db != blocking'

# params vs baseline: identical up to the clip-norm reduction order
for a, b in zip(jax.tree.leaves(p_base), jax.tree.leaves(p_db)):
    d = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
    assert d < 1e-6, d
assert abs(float(m_base['grad_norm']) - float(m_db['grad_norm'])) < 1e-4
print('OK')
"""
    )
    assert "OK" in out

import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           "--xla_disable_hlo_passes=cpu-all-reduce-combiner")

# NOTE: the lines above MUST run before any other import (jax locks the
# device count at first init), which is why the docstring follows them and no
# `from __future__` import is used in this module.  The gates classify the
# program's own comm schedule from the CPU compile.  The CPU backend drops
# optimization barriers before its all-reduce combiner runs, so the combiner
# would merge reductions that a stagger plan orders one behind the other
# into tuple all-reduces; the TPU compiler keeps them apart
# (tests/test_tpu_compile.py::test_tp_decode_reductions_stay_apart_for_v5e),
# and with the CPU pass off the gate sees the same collectives.

DOC = """Program gates: lower + compile each comm-plan program on fake CPU
devices and prove its collective schedule from the optimized HLO.

Everything is lowered from ShapeDtypeStructs — no arrays are allocated.
Every gate runs on a grid of at most 8 devices.

Usage:
  python -m repro.launch.dryrun --summa-gemm   # SUMMA ring: 0 serialized gate
  python -m repro.launch.dryrun --uneven       # ragged SUMMA: same gate + byte model
  python -m repro.launch.dryrun --sp-ring      # ring attention: same gate
  python -m repro.launch.dryrun --serve        # serving TP decode: same gate
  python -m repro.launch.dryrun --moe          # MoE expert-parallel dispatch
  python -m repro.launch.dryrun --train        # ZeRO train step: 0 serialized
                                               # reduce-scatter/all-gather gate

The program gates also assert *plan/HLO agreement*: each program's declared
comm-plan intent (repro.core.plan) must match what the HLO walker proves
about the compiled artifact.  ``--plan-report out.json`` runs all of them
and writes the per-plan agreement table (the nightly CI artifact).
"""

import argparse
import json

import jax
import numpy as np

from repro import configs
from repro.data.pipeline import batch_specs
from repro.models import lm
from repro.train.optimizer import OptConfig


def _abstract(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _import_examples_gemm():
    """examples/ lives at the repo root, not in src/ — bootstrap the path."""
    import sys

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))
    if root not in sys.path:
        sys.path.insert(0, root)
    import examples.distributed_gemm as dg

    return dg


def summa_dryrun(*, ni: int = 256, nj: int = 256, nk: int = 256,
                 grid: tuple[int, int] = (2, 4), majors: str = "I/I/K",
                 verbose: bool = True) -> dict:
    """Dry-run the SUMMA ring program (both variants): lower + compile on the
    fake mesh, classify every collective of every kind (ring
    ``collective-permute``s AND the reduce-scatter epilogue) from the
    optimized HLO, and compare measured collective bytes against the
    analytic comm-volume model — the static proof that the double-buffered
    rewrite keeps 0 transfers on the compute chain, without multi-host
    hardware.
    """
    from repro.launch import hlo_walk

    dg = _import_examples_gemm()
    out: dict = {"ni": ni, "nj": nj, "nk": nk, "grid": list(grid), "majors": majors}
    for variant, db in (("double_buffered", True), ("blocking", False)):
        fn, meta = dg.summa_ring_program(ni=ni, nj=nj, nk=nk, grid=grid,
                                         majors=majors, double_buffer=db)
        st = hlo_walk.analyze(fn.lower(*meta["abstract_args"]).compile().as_text())
        out[variant] = {
            "collective_permutes": len(st.of_kind("collective-permute")),
            "overlapped": st.collectives_overlapped("collective-permute"),
            "serialized": st.collectives_serialized("collective-permute"),
            "permute_overlap_fraction": st.overlap_fraction("collective-permute"),
            "hlo_permute_bytes": st.coll_by_op.get("collective-permute", 0.0),
            "model_ring_bytes": meta["comm_model"]["ring_bytes"],
            "model_total_bytes": meta["comm_model"]["total_bytes"],
            # kind-generic classification: every collective kind, not just
            # the ring permutes — the epilogue reduce-scatter shows up here
            "collectives_serialized_any_kind": st.collectives_serialized(),
            "collectives_overlapped_any_kind": st.collectives_overlapped(),
            "exposed_bytes": st.exposed_collective_bytes(),
            "overlap_by_kind": st.overlap_by_kind(),
            # plan-declared intent vs HLO-proven verdict (gate: must agree)
            "plan": hlo_walk.plan_agreement(st, meta["plan_intent"]),
        }
    if verbose:
        print(json.dumps(out, indent=1))
    return out


def ragged_summa_dryrun(*, ni: int = 35, nj: int = 35, nk: int = 35,
                        grid: tuple[int, int] = (2, 4), majors: str = "I/I/K",
                        verbose: bool = True) -> dict:
    """The ``--uneven`` gate: dry-run the *ragged* SUMMA ring (dims that do
    NOT divide the grid — padded capacity tiles + per-rank extents) and prove

      * 0 serialized collectives of any kind (the ragged panels double-buffer
        exactly like the dense ones — raggedness costs no overlap), and
      * the walker's wire bytes equal the analytic *padded* ring model while
        its valid bytes equal the *valid* (payload) model — the static proof
        that padding rides the wire but never inflates the modeled cost.
    """
    from repro.launch import hlo_walk

    dg = _import_examples_gemm()
    out: dict = {"ni": ni, "nj": nj, "nk": nk, "grid": list(grid), "majors": majors,
                 "ragged": True}
    for variant, db in (("double_buffered", True), ("blocking", False)):
        fn, meta = dg.ragged_summa_program(ni=ni, nj=nj, nk=nk, grid=grid,
                                           majors=majors, double_buffer=db)
        model = meta["comm_model"]
        st = hlo_walk.analyze(fn.lower(*meta["abstract_args"]).compile().as_text(),
                              valid_fractions=model["valid_fractions"])
        wire = st.coll_by_op.get("collective-permute", 0.0)
        valid = st.coll_by_op_valid.get("collective-permute", 0.0)
        out[variant] = {
            "collectives": len(st.collectives),
            "overlapped": st.collectives_overlapped(),
            "serialized": st.collectives_serialized(),
            "exposed_bytes": st.exposed_collective_bytes(),
            "hlo_wire_permute_bytes": wire,
            "hlo_valid_permute_bytes": valid,
            "model_ring_padded_bytes": model["ring_padded_bytes"],
            "model_ring_valid_bytes": model["ring_bytes"],
            "wire_matches_padded_model": wire == model["ring_padded_bytes"],
            "valid_matches_ragged_model": abs(valid - model["ring_bytes"]) < 1e-6,
            "overlap_by_kind": st.overlap_by_kind(),
            "plan": hlo_walk.plan_agreement(st, meta["plan_intent"]),
        }
    if verbose:
        print(json.dumps(out, indent=1))
    return out


def sp_ring_dryrun(*, batch: int = 2, seq: int = 256, d_model: int = 64,
                   n_heads: int = 4, n_kv: int = 2, head_dim: int = 16,
                   grid: tuple[int, int] = (2, 4), attn_impl: str | None = None,
                   verbose: bool = True) -> dict:
    """Dry-run the sequence-parallel ring-attention trace (both variants):
    lower+compile a GQA attention op — QKV projections, the double-buffered
    KV ring, output projection — under an ``sp_ring`` recipe on a
    (data, model) fake mesh, and classify every collective of every kind.

    The acceptance gate: 0 serialized collectives — the KV rotations stay
    off the compute def-use chain even though their payloads were *produced*
    by the projection GEMMs, because each step's local attention is an
    independent sibling branch the scheduler can hide the transfer behind.

    A ``seq`` that does not divide the model axis runs the *ragged* ring
    (padded capacity KV chunks + masked scores): the walker's permute bytes
    then include the padding, so the report scales them by the statically
    known valid fraction ``seq / (R * cap)`` — the sp_ring twin of the
    ragged SUMMA's valid-bytes accounting.  The ragged pad slice used to be
    a mid-graph boundary reshard (XLA all-gathered the padded seq-sharded
    output just to slice it): the attention op now projects on the padded
    seq and slices *last*, so the slice is terminal and nothing serializes
    — ``boundary_serialized`` must be 0 for dense AND ragged traces.  The
    plan agreement stays scoped to the plan's own collective kind
    (``collective-permute``); the boundary count is reported separately as
    a regression tripwire.

    ``attn_impl="interpret"`` traces the ring steps through the carry-state
    Pallas flash kernel in interpret mode (plain HLO on CPU), so the gate
    proves the same 0-serialized verdict *with the kernel in the traced
    program* — each step's kernel consumes the held KV block and is a
    sibling of the in-flight rotation, exactly like the jnp merge it
    replaces.  ``None`` keeps the jnp ring-step body.
    """
    from types import SimpleNamespace

    from repro.launch import hlo_walk
    from repro.models import attention as attn
    from repro.models.sharding import make_recipe, ragged_seq_extents, use_recipe
    from repro.core import make_mesh

    cfg = SimpleNamespace(n_heads=n_heads, n_kv=n_kv, head_dim=head_dim,
                          d_model=d_model, d_ff=4 * d_model,
                          vocab_padded=256, n_experts=0, family="dense")
    mesh = make_mesh(grid, ("data", "model"))
    params = {
        "wq": jax.ShapeDtypeStruct((d_model, n_heads, head_dim), np.float32),
        "wk": jax.ShapeDtypeStruct((d_model, n_kv, head_dim), np.float32),
        "wv": jax.ShapeDtypeStruct((d_model, n_kv, head_dim), np.float32),
        "wo": jax.ShapeDtypeStruct((n_heads, head_dim, d_model), np.float32),
    }
    x = jax.ShapeDtypeStruct((batch, seq, d_model), np.float32)

    # ragged seq shards: the KV ring moves padded capacity chunks; the valid
    # payload fraction is known statically from the extents table
    R = grid[1]
    valid_fractions = None
    if seq % R:
        cap, _ = ragged_seq_extents(seq, R)
        valid_fractions = {"collective-permute": seq / (R * cap)}

    out: dict = {"batch": batch, "seq": seq, "d_model": d_model,
                 "n_heads": n_heads, "n_kv": n_kv, "grid": list(grid),
                 "ragged_seq": bool(seq % R), "attn_impl": attn_impl,
                 "valid_fraction": None if valid_fractions is None
                 else valid_fractions["collective-permute"]}
    for variant, db in (("double_buffered", True), ("blocking", False)):
        recipe = make_recipe(cfg, mesh, attn_mode="sp_ring")

        def fwd(p, x, _r=recipe, _db=db):
            with use_recipe(_r):
                o, _ = attn.gqa_attention(p, x, n_heads=n_heads, n_kv=n_kv,
                                          head_dim=head_dim, sp_ring_double_buffer=_db,
                                          attn_impl=attn_impl)
            return o

        with mesh:
            compiled = jax.jit(fwd).lower(params, x).compile()
        st = hlo_walk.analyze(compiled.as_text(), valid_fractions=valid_fractions)
        # R-1 ring steps x (K, V) rotations
        out[variant] = {
            "collectives": len(st.collectives),
            "overlapped": st.collectives_overlapped(),
            "serialized": st.collectives_serialized(),
            "exposed_bytes": st.exposed_collective_bytes(),
            "hlo_wire_permute_bytes": st.coll_by_op.get("collective-permute", 0.0),
            "hlo_valid_permute_bytes": st.coll_by_op_valid.get("collective-permute", 0.0),
            "overlap_by_kind": st.overlap_by_kind(),
            "expected_ring_transfers": 2 * (grid[1] - 1),
            # the attention plan's transfers are the KV ring permutes; the
            # ragged output-slice all-gather is a caller-side reshard
            "plan": hlo_walk.plan_agreement(st, attn.RING_ATTENTION_PLAN_INTENT,
                                            kind="collective-permute"),
            "boundary_serialized": (st.collectives_serialized()
                                    - st.collectives_serialized("collective-permute")),
        }
    if verbose:
        print(json.dumps(out, indent=1))
    return out


def serve_dryrun(*, arch: str = "phi4-mini-3.8b", slots: int = 8,
                 max_len: int = 64, grid: tuple[int, int] = (4, 2),
                 microbatches: int = 2, attn_impl: str | None = None,
                 verbose: bool = True) -> dict:
    """Dry-run the serving engine's explicit tensor-parallel decode step
    (:func:`repro.serve.tp_decode.make_tp_decode_step`): lower + compile one
    continuous-batching decode step on a (data, model) fake mesh and
    classify every collective of every kind.

    The acceptance gate: with ``microbatches >= 2`` the staggered schedule
    serializes **nothing** — each microbatch's per-layer ``Iallreduce`` (and
    the terminal logits ``Iallgather``) completes behind the next
    microbatch's compute, so no collective sits on the decode critical path
    — and the declared plan intent (``stagger`` -> overlapped) must agree
    with the proven HLO verdict.  The same program with ``microbatches=1``
    is the negative control: no sibling compute exists, the reductions land
    on the def-use chain, and the walker must see serialized collectives —
    proving the gate measures the schedule, not walker blindness.

    ``attn_impl="interpret"`` routes each microbatch's attention through the
    flash-decoding Pallas kernel in interpret mode, proving the
    staggered schedule still serializes nothing with the kernel in the
    traced program (the kernel is microbatch ``s``'s compute — the sibling
    that hides microbatch ``s-1``'s Iallreduce).
    """
    from repro.core import make_mesh
    from repro.launch import hlo_walk
    from repro.serve.tp_decode import DECODE_TP_PLAN_INTENT, make_tp_decode_step

    cfg = configs.get(arch, smoke=True)
    mesh = make_mesh(grid, ("data", "model"))
    params = _abstract(jax.eval_shape(lambda: lm.init_model(cfg, jax.random.PRNGKey(0))))
    state = lm.DecodeState(
        caches=_abstract(jax.eval_shape(lambda: lm.init_cache(cfg, slots, max_len))),
        positions=jax.ShapeDtypeStruct((slots,), np.int32),
    )
    tokens_in = cfg.input_kind != "embeds"
    batch = {"tokens": jax.ShapeDtypeStruct((slots, 1), np.int32)} if tokens_in \
        else {"embeds": jax.ShapeDtypeStruct((slots, 1, cfg.d_model), np.float32)}
    counts = jax.ShapeDtypeStruct((slots,), np.int32)

    out: dict = {"arch": arch, "slots": slots, "max_len": max_len,
                 "grid": list(grid), "microbatches": microbatches,
                 "attn_impl": attn_impl}
    for variant, mb in (("staggered", microbatches), ("single", 1)):
        step = make_tp_decode_step(cfg, mesh, slots=slots, microbatches=mb,
                                   attn_impl=attn_impl)
        compiled = jax.jit(step).lower(params, state, batch, counts).compile()
        st = hlo_walk.analyze(compiled.as_text())
        out[variant] = {
            "collectives": len(st.collectives),
            "overlapped": st.collectives_overlapped(),
            "serialized": st.collectives_serialized(),
            "exposed_bytes": st.exposed_collective_bytes(),
            "overlap_by_kind": st.overlap_by_kind(),
            "plan": hlo_walk.plan_agreement(st, DECODE_TP_PLAN_INTENT),
        }
    if verbose:
        print(json.dumps(out, indent=1))
    return out


def moe_dryrun(*, batch: int = 4, seq: int = 8, d_model: int = 64,
               d_ff: int = 128, n_experts: int = 8, top_k: int = 2,
               grid: tuple[int, int] = (2, 4), routing: str = "balanced",
               n_groups: int = 2, verbose: bool = True) -> dict:
    """Dry-run the expert-parallel MoE dispatch
    (:func:`repro.models.ffn.moe_expert_parallel`): lower + compile the
    routed FFN on a (data, model) fake mesh and classify every collective.

    The acceptance gate: with ``n_groups >= 2`` expert groups the
    ``dispatch`` comm plan double-buffers both ragged all-to-all legs —
    group g+1's dispatch and group g's combine complete behind group g's /
    g+1's expert GEMMs — so **nothing serializes**, and the walker's wire /
    valid all-to-all bytes must equal the analytic counts-table model
    (:func:`repro.models.ffn.moe_comm_model`: wire = padded capacity
    blocks, valid = the ``MPI_Alltoallv`` counts).  The same program with
    ``n_groups=1`` is the negative control: one group leaves the dispatch
    leg no sibling compute (router GEMM upstream, expert GEMM downstream),
    so the walker must see it serialized.

    ``routing="skewed"`` routes every token to rank 0's experts (one per
    group, all other experts zero-count): zero split extents ride the wire
    as pure padding, the valid fraction collapses, and the overlap verdict
    must not change — the gate runs balanced AND skewed in CI.
    """
    from types import SimpleNamespace

    from repro.core import make_mesh
    from repro.launch import hlo_walk
    from repro.models import ffn
    from repro.models.sharding import (make_recipe, ragged_expert_extents,
                                       use_recipe)

    E, k = n_experts, top_k
    cfg = SimpleNamespace(n_heads=4, n_kv=2, head_dim=d_model // 4,
                          d_model=d_model, d_ff=d_ff, vocab_padded=256,
                          n_experts=E, family="moe")
    mesh = make_mesh(grid, ("data", "model"))
    D, R = grid
    Tl = (batch // D) * (seq // R)
    if routing == "balanced":
        counts = ffn.moe_ep_counts(E, Tl, k, 1.25)
    elif routing == "skewed":
        # everything to rank 0's experts, one per group; zero-token experts
        # everywhere else (zero split extents on ranks 1..R-1)
        cap_e, _ = ragged_expert_extents(E, R)
        step = max(1, cap_e // max(n_groups, 1))
        hot = tuple(range(0, cap_e, step))[:n_groups]
        counts = tuple(Tl if e in hot else 0 for e in range(E))
    else:
        raise ValueError(f"unknown routing {routing!r} (balanced | skewed)")

    params = {
        "router": jax.ShapeDtypeStruct((d_model, E), np.float32),
        "w_gate": jax.ShapeDtypeStruct((E, d_model, d_ff), np.float32),
        "w_up": jax.ShapeDtypeStruct((E, d_model, d_ff), np.float32),
        "w_down": jax.ShapeDtypeStruct((E, d_ff, d_model), np.float32),
    }
    x = jax.ShapeDtypeStruct((batch, seq, d_model), np.float32)

    out: dict = {"batch": batch, "seq": seq, "d_model": d_model, "d_ff": d_ff,
                 "n_experts": E, "top_k": k, "grid": list(grid),
                 "routing": routing, "counts": list(counts),
                 "n_groups": n_groups}
    for variant, ng in (("overlapped", n_groups), ("single", 1)):
        recipe = make_recipe(cfg, mesh)
        sched = ffn.moe_ep_schedule(E, R, counts, ng)
        model = ffn.moe_comm_model(sched, d_model=d_model, itemsize=4)

        def fwd(p, xv, _r=recipe, _ng=ng):
            with use_recipe(_r):
                # merge=False: y stays in (D, R, Tl, m) split form so the
                # boundary reshard of the merge cannot pollute the a2a gate
                y, aux = ffn.moe_expert_parallel(
                    p, xv, n_experts=E, top_k=k, counts=counts, n_groups=_ng,
                    merge=False)
            return y, aux

        with mesh:
            compiled = jax.jit(fwd).lower(params, x).compile()
        st = hlo_walk.analyze(compiled.as_text(),
                              valid_fractions=model["valid_fractions"])
        wire = st.coll_by_op.get("all-to-all", 0.0)
        valid = st.coll_by_op_valid.get("all-to-all", 0.0)
        out[variant] = {
            "steps": len(sched.groups),
            "collectives": len(st.collectives),
            "all_to_alls": len(st.of_kind("all-to-all")),
            "overlapped": st.collectives_overlapped(),
            "serialized": st.collectives_serialized(),
            "serialized_a2a": st.collectives_serialized("all-to-all"),
            "exposed_bytes": st.exposed_collective_bytes(),
            "hlo_wire_a2a_bytes": wire,
            "hlo_valid_a2a_bytes": valid,
            "model_wire_bytes": model["wire_bytes"],
            "model_valid_bytes": model["valid_bytes"],
            "wire_matches_model": wire == model["wire_bytes"],
            "valid_matches_model": abs(valid - model["valid_bytes"]) < 1e-6,
            "overlap_by_kind": st.overlap_by_kind(),
            "plan": hlo_walk.plan_agreement(st, ffn.MOE_DISPATCH_PLAN_INTENT,
                                            kind="all-to-all"),
        }
    if verbose:
        print(json.dumps(out, indent=1))
    return out


def train_dryrun(*, arch: str = "phi4-mini-3.8b", ranks: int = 8,
                 seq: int = 64, batch: int = 16, bucket_kb: int = 64,
                 compress: str = "none", microbatches: int = 1,
                 verbose: bool = True) -> dict:
    """Dry-run the explicit ZeRO-2 train step
    (:func:`repro.train.trainer.make_zero_train_step`): lower + compile one
    bucketed fwd+bwd+AdamW step on a fake ``data`` mesh and classify every
    collective of every kind.

    The acceptance gate: with multiple gradient buckets **nothing
    serializes** among the plan's reduce-scatters and all-gathers — each
    bucket's ``MPI_Ireduce_scatter`` completes behind the sibling buckets'
    norm/update math and every param ``MPI_Iallgatherv`` prefetch is
    terminal (no downstream compute) — and the declared ``bucket`` plan
    intent must agree with the proven HLO verdict, kind-scoped to both
    legs.  The walker's wire bytes must equal the analytic ZeRO comm model
    (:func:`repro.train.buckets.zero_comm_model`: RS moves one capacity
    shard per bucket, AG the full padded flat) and its valid bytes the
    pad-discounted model.

    The same program with ``bucket_kb`` large enough to hold the whole
    model in ONE bucket is the negative control: a single reduce-scatter
    has the backward upstream, its own norm dot downstream, and no sibling
    compute, so the walker must see it serialized — proving the gate
    measures the bucketed schedule, not walker blindness.

    ``compress="int8"`` quantizes each reduced bucket shard (error-feedback
    residual): pure elementwise work on the arrived shards, so the overlap
    verdict and the byte model must not change — the gate runs both in CI.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import ShapeCell
    from repro.core import make_mesh
    from repro.launch import hlo_walk
    from repro.train.buckets import zero_comm_model
    from repro.train.optimizer import init_zero_opt_state
    from repro.train.trainer import (ZERO_TRAIN_PLAN_INTENT,
                                     make_zero_train_step, zero_train_buckets)

    cfg = configs.get(arch, smoke=True)
    mesh = make_mesh((ranks,), ("data",))
    shape = ShapeCell("train_gate", seq_len=seq, global_batch=batch, kind="train")
    ocfg = OptConfig(compress=compress)
    params_abs = lm.abstract_model(cfg)
    batch_abs = batch_specs(cfg, shape)

    def _sh(tree, spec):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=NamedSharding(mesh, spec)),
            tree,
        )

    def lower(bucket_bytes, db):
        bkts = zero_train_buckets(cfg, bucket_bytes=bucket_bytes, ranks=ranks)
        opt_abs = init_zero_opt_state(params_abs, bkts, ocfg)
        opt_abs = opt_abs._replace(
            step=jax.ShapeDtypeStruct((), np.int32,
                                      sharding=NamedSharding(mesh, P())),
            mu=_sh(opt_abs.mu, P("data")),
            nu=_sh(opt_abs.nu, P("data")),
            err=_sh(opt_abs.err, P("data")),
        )
        step = make_zero_train_step(cfg, mesh, ocfg, microbatches=microbatches,
                                    bucket_bytes=bucket_bytes, double_buffer=db)
        hlo = jax.jit(step).lower(
            _sh(params_abs, P()), opt_abs, _sh(batch_abs, P("data"))
        ).compile().as_text()
        model = zero_comm_model(bkts)
        st = hlo_walk.analyze(hlo, valid_fractions=model["valid_fractions"])
        rs_wire = sum(b for op, b in st.coll_by_op.items() if "reduce-scatter" in op)
        ag_wire = sum(b for op, b in st.coll_by_op.items() if "all-gather" in op)
        rs_valid = sum(b for op, b in st.coll_by_op_valid.items() if "reduce-scatter" in op)
        ag_valid = sum(b for op, b in st.coll_by_op_valid.items() if "all-gather" in op)
        return {
            "n_buckets": len(bkts),
            "collectives": len(st.collectives),
            "overlapped": st.collectives_overlapped(),
            "serialized": st.collectives_serialized(),
            "serialized_rs": st.collectives_serialized("reduce-scatter"),
            "serialized_ag": st.collectives_serialized("all-gather"),
            "exposed_bytes": st.exposed_collective_bytes(),
            "hlo_wire_rs_bytes": rs_wire,
            "hlo_wire_ag_bytes": ag_wire,
            "hlo_valid_rs_bytes": rs_valid,
            "hlo_valid_ag_bytes": ag_valid,
            "model": {k: model[k] for k in
                      ("n_buckets", "param_elems", "padded_elems",
                       "rs_wire_bytes", "rs_valid_bytes", "ag_wire_bytes",
                       "ag_valid_bytes", "wire_bytes", "valid_bytes")},
            "wire_matches_model": (rs_wire == model["rs_wire_bytes"]
                                   and ag_wire == model["ag_wire_bytes"]),
            "valid_matches_model": (
                abs(rs_valid - model["rs_valid_bytes"]) < 1e-6
                and abs(ag_valid - model["ag_valid_bytes"]) < 1e-6),
            "overlap_by_kind": st.overlap_by_kind(),
            "plan_rs": hlo_walk.plan_agreement(st, ZERO_TRAIN_PLAN_INTENT,
                                               kind="reduce-scatter"),
            "plan_ag": hlo_walk.plan_agreement(st, ZERO_TRAIN_PLAN_INTENT,
                                               kind="all-gather"),
        }

    out: dict = {"arch": arch, "ranks": ranks, "seq": seq, "batch": batch,
                 "bucket_kb": bucket_kb, "compress": compress,
                 "microbatches": microbatches}
    out["bucketed"] = lower(bucket_kb << 10, True)
    out["blocking"] = lower(bucket_kb << 10, False)
    # one bucket holding the whole model: no sibling buckets to hide behind
    out["single_bucket"] = lower(1 << 40, True)
    if verbose:
        print(json.dumps(out, indent=1))
    return out


def plan_report(path: str, verbose: bool = True) -> int:
    """Run every comm-plan dry run (SUMMA ring, ragged SUMMA ring, sp ring
    attention dense AND ragged seq) and write the per-plan overlap/agreement
    table to ``path`` — the nightly CI artifact.  Returns a process exit
    code: non-zero iff any plan's declared intent disagrees with the proven
    HLO verdict."""
    programs = {
        "summa_ring": summa_dryrun(verbose=False),
        "ragged_summa_ring": ragged_summa_dryrun(verbose=False),
        "sp_ring_attention": sp_ring_dryrun(verbose=False),
        "sp_ring_attention_ragged": sp_ring_dryrun(seq=250, verbose=False),
    }
    rows = []
    for prog, rep in programs.items():
        for variant in ("double_buffered", "blocking"):
            cell = rep[variant]
            rows.append({
                "program": prog,
                "variant": variant,
                **cell["plan"],
                "exposed_bytes": cell["exposed_bytes"],
                "overlap_by_kind": cell["overlap_by_kind"],
            })
    for routing in ("balanced", "skewed"):
        moe = moe_dryrun(routing=routing, verbose=False)
        rows.append({
            "program": f"moe_ep_dispatch_{routing}",
            "variant": "double_buffered",
            **moe["overlapped"]["plan"],
            "exposed_bytes": moe["overlapped"]["exposed_bytes"],
            "overlap_by_kind": moe["overlapped"]["overlap_by_kind"],
            # single expert group = no sibling GEMM for the dispatch leg:
            # the a2a must serialize there or the walker proves nothing here
            "negative_control_serialized": moe["single"]["serialized_a2a"],
        })
    serve = serve_dryrun(verbose=False)
    rows.append({
        "program": "serve_tp_decode",
        "variant": "staggered",
        **serve["staggered"]["plan"],
        "exposed_bytes": serve["staggered"]["exposed_bytes"],
        "overlap_by_kind": serve["staggered"]["overlap_by_kind"],
        # unstaggered schedule's serialized count (must be > 0): evidence the
        # walker sees the reductions when nothing hides them
        "negative_control_serialized": serve["single"]["serialized"],
    })
    for compress in ("none", "int8"):
        train = train_dryrun(compress=compress, verbose=False)
        for leg, plan_key in (("reduce_scatter", "plan_rs"),
                              ("all_gather", "plan_ag")):
            rows.append({
                "program": f"zero_train_{compress}_{leg}",
                "variant": "bucketed",
                **train["bucketed"][plan_key],
                "exposed_bytes": train["bucketed"]["exposed_bytes"],
                "overlap_by_kind": train["bucketed"]["overlap_by_kind"],
                # whole model in one bucket = no sibling norm/update math:
                # its reduce-scatter must land on the chain there
                "negative_control_serialized":
                    train["single_bucket"]["serialized_rs"],
            })
    disagreements = [r for r in rows if not r["agree"]]
    report = {
        "plans": rows,
        "n_plans": len(rows),
        "n_disagreements": len(disagreements),
        "agree_all": not disagreements,
    }
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    if verbose:
        for r in rows:
            mark = "ok " if r["agree"] else "FAIL"
            print(f"[{mark}] {r['program']}/{r['variant']}: declared="
                  f"{r['declared']} proven={r['proven']} "
                  f"(serialized={r['serialized']} overlapped={r['overlapped']})")
        print(f"plan report -> {path} ({len(rows)} plans, "
              f"{len(disagreements)} disagreements)")
    return 1 if disagreements else 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--summa-gemm", action="store_true",
                    help="dry-run the SUMMA ring program and report the "
                         "kind-generic collective overlap classification")
    ap.add_argument("--summa-dims", default="256,256,256", help="ni,nj,nk for --summa-gemm")
    ap.add_argument("--summa-grid", default="2x4", help="rows x cols for --summa-gemm")
    ap.add_argument("--sp-ring", action="store_true",
                    help="dry-run the sp ring-attention trace and gate on 0 "
                         "serialized collectives of any kind")
    ap.add_argument("--sp-ring-seq", type=int, default=256, help="seq len for --sp-ring")
    ap.add_argument("--sp-ring-grid", default="2x4", help="data x model for --sp-ring")
    ap.add_argument("--uneven", action="store_true",
                    help="dry-run the RAGGED SUMMA (dims not divisible by the "
                         "grid) and gate on 0 serialized collectives AND "
                         "modeled bytes == the analytic ragged ring model "
                         "(valid bytes, not padded)")
    # 35 is odd AND 3 mod 4: every dim is genuinely ragged on the default grid
    ap.add_argument("--uneven-dims", default="35,35,35", help="ni,nj,nk for --uneven")
    ap.add_argument("--uneven-grid", default="2x4", help="rows x cols for --uneven")
    ap.add_argument("--serve", action="store_true",
                    help="serving TP-decode dry run: lower one continuous-"
                         "batching decode step (staggered microbatch comm "
                         "plan) and assert 0 serialized collectives + "
                         "plan/HLO agreement")
    ap.add_argument("--serve-grid", default="4x2", help="data x model for --serve")
    ap.add_argument("--serve-slots", type=int, default=8, help="batch slots for --serve")
    ap.add_argument("--serve-microbatches", type=int, default=2,
                    help="stagger depth for --serve (1 = negative control)")
    ap.add_argument("--moe", action="store_true",
                    help="expert-parallel MoE dispatch dry run: lower the "
                         "ragged all-to-all dispatch/combine FFN and assert "
                         "0 serialized collectives, plan/HLO agreement, and "
                         "walker wire/valid a2a bytes == the counts-table "
                         "model; n_groups=1 is the serialized negative "
                         "control")
    ap.add_argument("--moe-grid", default="2x4", help="data x model for --moe")
    ap.add_argument("--moe-groups", type=int, default=2,
                    help="expert groups (double-buffer depth) for --moe")
    ap.add_argument("--moe-routing", default="both",
                    choices=["balanced", "skewed", "both"],
                    help="routing profile for --moe: balanced counts, skewed "
                         "(all tokens to rank 0's experts, zero-token "
                         "experts elsewhere), or both")
    ap.add_argument("--train", action="store_true",
                    help="explicit ZeRO-2 train-step dry run: lower one "
                         "bucketed fwd+bwd+AdamW step and assert 0 "
                         "serialized reduce-scatter/all-gather collectives "
                         "in the backward, kind-scoped plan/HLO agreement, "
                         "and walker wire/valid bytes == the analytic ZeRO "
                         "comm model; the whole-model single bucket is the "
                         "serialized negative control")
    ap.add_argument("--train-grid", type=int, default=8,
                    help="data-parallel ranks for --train")
    ap.add_argument("--train-bucket-kb", type=int, default=64,
                    help="gradient bucket threshold (KiB) for --train")
    ap.add_argument("--train-compress", default="none",
                    choices=["none", "int8"],
                    help="gradient compression for --train: int8 quantizes "
                         "each reduced bucket shard (error feedback); the "
                         "overlap verdict and byte model must not change")
    ap.add_argument("--attn-impl", default=None, choices=["jnp", "interpret"],
                    help="attention kernel impl for the --sp-ring/--serve "
                         "gates: 'interpret' traces the Pallas kernels "
                         "(carry-state flash ring step / flash decode) in "
                         "interpret mode so the 0-serialized verdict is "
                         "proven with the kernels in the program; default "
                         "keeps the jnp bodies")
    ap.add_argument("--plan-report", default=None, metavar="PATH",
                    help="run every comm-plan dry run (SUMMA, ragged SUMMA, "
                         "sp ring — dense and ragged seq — and the serving "
                         "TP decode) and write the per-plan overlap/"
                         "agreement table as JSON")
    args = ap.parse_args()

    if args.plan_report:
        raise SystemExit(plan_report(args.plan_report))

    if args.summa_gemm:
        ni, nj, nk = (int(x) for x in args.summa_dims.split(","))
        grid = tuple(int(x) for x in args.summa_grid.split("x"))
        rep = summa_dryrun(ni=ni, nj=nj, nk=nk, grid=grid)
        bad = sum(rep[v]["collectives_serialized_any_kind"]
                  for v in ("double_buffered", "blocking"))
        bad += sum(0 if rep[v]["plan"]["agree"] else 1
                   for v in ("double_buffered", "blocking"))
        raise SystemExit(1 if bad else 0)

    if args.uneven:
        ni, nj, nk = (int(x) for x in args.uneven_dims.split(","))
        grid = tuple(int(x) for x in args.uneven_grid.split("x"))
        rep = ragged_summa_dryrun(ni=ni, nj=nj, nk=nk, grid=grid)
        bad = 0
        for v in ("double_buffered", "blocking"):
            bad += rep[v]["serialized"]
            bad += 0 if rep[v]["wire_matches_padded_model"] else 1
            bad += 0 if rep[v]["valid_matches_ragged_model"] else 1
            bad += 0 if rep[v]["plan"]["agree"] else 1
        raise SystemExit(1 if bad else 0)

    if args.sp_ring:
        grid = tuple(int(x) for x in args.sp_ring_grid.split("x"))
        rep = sp_ring_dryrun(seq=args.sp_ring_seq, grid=grid,
                             attn_impl=args.attn_impl)
        bad = 0
        for v in ("double_buffered", "blocking"):
            bad += rep[v]["plan"]["serialized"]  # ring permutes on the chain
            bad += 0 if rep[v]["plan"]["agree"] else 1
            # dense AND ragged: nothing may serialize — the ragged pad slice
            # is fused behind the output projection (terminal, off-chain)
            bad += rep[v]["serialized"]
        raise SystemExit(1 if bad else 0)

    if args.serve:
        grid = tuple(int(x) for x in args.serve_grid.split("x"))
        rep = serve_dryrun(grid=grid, slots=args.serve_slots,
                           microbatches=args.serve_microbatches,
                           attn_impl=args.attn_impl)
        stag = rep["staggered"]
        bad = stag["serialized"]  # 0 serialized collectives per decode step
        bad += 0 if stag["plan"]["agree"] else 1
        # negative control: the unstaggered schedule must show the reductions
        # on the chain, or the gate is measuring walker blindness
        bad += 0 if rep["single"]["serialized"] > 0 else 1
        raise SystemExit(1 if bad else 0)

    if args.train:
        rep = train_dryrun(ranks=args.train_grid,
                           bucket_kb=args.train_bucket_kb,
                           compress=args.train_compress)
        bad = 0
        for v in ("bucketed", "blocking"):
            # byte accounting must match the analytic ZeRO model in both
            # interpretations (same buckets -> same wire)
            bad += 0 if rep[v]["wire_matches_model"] else 1
            bad += 0 if rep[v]["valid_matches_model"] else 1
        bk = rep["bucketed"]
        # the tentpole gate: nothing on the grad reduce / param prefetch
        # legs may sit on the compute chain
        bad += bk["serialized_rs"] + bk["serialized_ag"]
        bad += 0 if bk["plan_rs"]["agree"] else 1
        bad += 0 if bk["plan_ag"]["agree"] else 1
        # negative control: one whole-model bucket must serialize its
        # reduce-scatter, or the gate is measuring walker blindness
        bad += 0 if rep["single_bucket"]["serialized_rs"] > 0 else 1
        raise SystemExit(1 if bad else 0)

    if args.moe:
        grid = tuple(int(x) for x in args.moe_grid.split("x"))
        routings = (("balanced", "skewed") if args.moe_routing == "both"
                    else (args.moe_routing,))
        bad = 0
        for routing in routings:
            rep = moe_dryrun(grid=grid, routing=routing,
                             n_groups=args.moe_groups)
            ov, single = rep["overlapped"], rep["single"]
            bad += ov["serialized"]
            bad += 0 if ov["plan"]["agree"] else 1
            bad += 0 if (ov["wire_matches_model"]
                         and ov["valid_matches_model"]) else 1
            bad += 0 if single["serialized_a2a"] > 0 else 1
        raise SystemExit(1 if bad else 0)

    ap.error("choose a gate: --summa-gemm, --uneven, --sp-ring, --serve, "
             "--moe, --train or --plan-report")


if __name__ == "__main__":
    main()

"""train_step construction: loss/grad (with microbatch accumulation), AdamW
update — as a GSPMD baseline and as an explicit ZeRO-2 comm program.

``make_train_step(cfg, recipe, ocfg, microbatches=k)`` is the baseline:
gradients and the DP reduction are wherever XLA's partitioner puts them,
with no declared communication schedule.  It exists as the numerics oracle
(`tests/test_zero_trainer.py` holds the explicit step to it bitwise) and as
the recipe-driven path for arbitrary meshes.

``make_zero_train_step(cfg, mesh, ocfg, ...)`` is the training twin of the
serving engine's explicit decode (:mod:`repro.serve.tp_decode`): the step
states its communication instead of hoping a runtime schedules it well.
One ZeRO-2 schedule, declared as a :func:`repro.core.plan.bucket` comm plan:

  * gradients pack into size-thresholded, dtype-homogeneous **buckets**
    (MPI counts/displacements over the flattened param pytree —
    :mod:`repro.train.buckets`);
  * each bucket's ``MPI_Ireduce_scatter``
    (:func:`repro.core.collectives.shard_reduce_scatterv_start`) is issued
    before any wait — every reduction in flight at once, completing behind
    the sibling buckets' norm/update math (``dryrun --train`` proves 0
    serialized reduce-scatter/all-gather collectives statically);
  * AdamW runs on the **1/R optimizer shard** only
    (:func:`repro.train.optimizer.init_zero_opt_state` — ZeRO partitioning
    of moments over the ``data`` axis);
  * each updated param shard's ``MPI_Iallgatherv``
    (:func:`~repro.core.collectives.shard_all_gatherv_start`) prefetches
    the full params for the next forward, off the compute chain.

Microbatching (both steps): the global batch splits into ``k`` microbatches
and gradients are accumulated with a ``lax.scan`` — the standard memory
lever at scale; per-microbatch aux metrics are accumulated and averaged
alongside the loss.  Remat comes from ``cfg.remat`` inside the model.  The
baseline derives every sharding from the recipe (the paper's binding
mechanism); the explicit step derives its schedule from the bucket tables
and contains the program's only collectives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import lm
from repro.models.sharding import use_recipe
from .optimizer import (
    OptConfig,
    OptState,
    adamw_leaf_update,
    apply_updates,
    compress_leaf,
    lr_at_step,
)

__all__ = ["make_train_step", "make_eval_step", "make_zero_train_step",
           "ZERO_TRAIN_PLAN_INTENT", "zero_train_buckets"]


def _split_batch(batch, k: int):
    def sp(x):
        B = x.shape[0]
        if B % k:
            raise ValueError(
                f"batch {B} (leaf shape {tuple(x.shape)}) does not divide "
                f"into {k} microbatches"
            )
        return x.reshape((k, B // k) + x.shape[1:])

    return jax.tree.map(sp, batch)


def _accum_loss_grads(params, batch, cfg, microbatches: int):
    """(loss, metrics, grads) with optional scan-accumulated microbatches;
    metrics are per-microbatch aux values, accumulated and averaged."""
    if microbatches == 1:
        (loss, metrics), grads = jax.value_and_grad(lm.loss_fn, has_aux=True)(
            params, batch, cfg
        )
        return loss, metrics, grads

    mb = _split_batch(batch, microbatches)
    metric_shapes = jax.eval_shape(
        lambda p, b: lm.loss_fn(p, b, cfg)[1],
        params, jax.tree.map(lambda x: x[0], mb),
    )

    def accum(carry, micro):
        g_acc, l_acc, m_acc = carry
        (l, m), g = jax.value_and_grad(lm.loss_fn, has_aux=True)(params, micro, cfg)
        g_acc = jax.tree.map(lambda a, b: a + b, g_acc, g)
        m_acc = jax.tree.map(lambda a, b: a + b, m_acc, m)
        return (g_acc, l_acc + l, m_acc), None

    zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    zero_m = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32), metric_shapes)
    (grads, loss_sum, metric_sum), _ = jax.lax.scan(accum, (zero_g, 0.0, zero_m), mb)
    grads = jax.tree.map(lambda g: g / microbatches, grads)
    metrics = jax.tree.map(lambda m: m / microbatches, metric_sum)
    return loss_sum / microbatches, metrics, grads


def make_train_step(cfg, recipe, ocfg: OptConfig, *, microbatches: int = 1):
    def train_step(params, opt_state, batch):
        with use_recipe(recipe):
            loss, metrics, grads = _accum_loss_grads(params, batch, cfg, microbatches)
            new_params, new_opt, opt_metrics = apply_updates(params, grads, opt_state, ocfg)
        out_metrics = {"loss": loss, **{k: v for k, v in metrics.items()}, **opt_metrics}
        return new_params, new_opt, out_metrics

    return train_step


# ====================================================== explicit ZeRO step ====

# declared overlap intent of the bucketed gradient schedule, consumed by the
# --train dry run's plan/HLO agreement gate (kind-scoped to the plan's own
# reduce-scatter and all-gather legs)
from repro.core.plan import intent_of as _intent_of

ZERO_TRAIN_PLAN_INTENT = _intent_of("bucket")


def zero_train_buckets(cfg, *, bucket_bytes: int, ranks: int):
    """The step's bucket tables, from the abstract params (no allocation)."""
    from repro.train.buckets import assign_buckets

    params_abs = lm.abstract_model(cfg)
    return assign_buckets(params_abs, bucket_bytes=bucket_bytes, ranks=ranks)


def make_zero_train_step(cfg, mesh, ocfg: OptConfig, *, microbatches: int = 1,
                         bucket_bytes: int = 4 << 20, double_buffer: bool = True):
    """Build the explicit ZeRO-2 ``train_step(params, opt_state, batch)``.

    ``mesh`` must carry a ``data`` axis (any other axes must be size 1 —
    the explicit step is data-parallel; TP rides the GSPMD baseline).
    ``opt_state`` comes from :func:`repro.train.optimizer.init_zero_opt_state`
    over the same bucket tables (``zero_train_buckets(cfg,
    bucket_bytes=..., ranks=mesh.shape['data'])``); its flat moment buffers
    shard ``P('data')``.

    Per step: each rank takes grads of the *local-mean* loss on its batch
    shard (recipe-free trace — the program's only collectives are the
    plan's), the :func:`repro.core.plan.bucket` plan reduce-scatters every
    bucket, the global clip scale is computed from per-shard norm terms
    (one scalar ``psum``), AdamW updates the 1/R shard, and the updated
    shards regather.  Summing rank partials then dividing by the
    power-of-two rank count is exact in f32, so the blocking interpretation
    reproduces the GSPMD baseline's loss and gradients bitwise at f32
    (tests/test_zero_trainer.py); the double-buffered form is bit-identical
    to blocking by plan construction.  With a non-uniform ``loss_mask`` the
    per-rank normalization gives the mean-of-local-means semantics
    (standard DP gradient averaging).

    ``ocfg.compress="int8"`` quantizes each *reduced bucket shard* with a
    sharded error-feedback residual (update compression: the wire moves f32
    grads; the per-shard int8 scales replace the baseline's per-leaf ones).
    """
    from jax.sharding import PartitionSpec as P

    from repro.core.collectives import (
        shard_all_gatherv_start,
        shard_reduce_scatterv_start,
    )
    from repro.core.plan import bucket as bucket_plan
    from repro.train.buckets import pack_bucket, unpack_bucket

    if "data" not in mesh.shape:
        raise ValueError(f"zero train step needs a 'data' mesh axis, have {dict(mesh.shape)}")
    for name, size in mesh.shape.items():
        if name != "data" and size != 1:
            raise ValueError(
                f"zero train step is data-parallel only: mesh axis {name!r} "
                f"has size {size} (use the GSPMD baseline for TP)"
            )
    R = mesh.shape["data"]
    buckets = zero_train_buckets(cfg, bucket_bytes=bucket_bytes, ranks=R)
    compress = ocfg.compress == "int8"
    inv_R = 1.0 / R  # R is a mesh axis size (power of two): exact scaling

    def body(params, step_ctr, mu_flats, nu_flats, err_flats, batch_local):
        ridx = jax.lax.axis_index("data")
        loss, metrics, grads = _accum_loss_grads(params, batch_local, cfg, microbatches)
        g_leaves = jax.tree.leaves(grads)
        p_leaves, p_treedef = jax.tree.flatten(params)
        packs = [pack_bucket(g_leaves, b) for b in buckets]

        step = step_ctr + 1
        lr = lr_at_step(step, ocfg)
        b1c = 1 - ocfg.b1 ** step.astype(jnp.float32)
        b2c = 1 - ocfg.b2 ** step.astype(jnp.float32)

        # closure cells for the shard-local opt-state outputs and the clip
        # norm (the combine leg regathers params only — tp_decode's
        # new_k_l pattern)
        new_mu: list = [None] * len(buckets)
        new_nu: list = [None] * len(buckets)
        new_err: list = [None] * len(buckets)
        norm_cell: list = [None]

        def transfer(_state, s):
            return shard_reduce_scatterv_start(packs[s], "data",
                                               extents=buckets[s].extents)

        def reduce(arrived):
            # per-bucket mean grads on the local shard (+ optional int8
            # error-feedback compression), then the global clip scale: each
            # bucket contributes one norm *dot* — the downstream compute of
            # its own reduce-scatter and the sibling compute of the others'
            shards = []
            sq = 0.0
            for s, a in enumerate(arrived):
                g = a.astype(jnp.float32) * inv_R
                if compress:
                    g, new_err[s] = compress_leaf(g, err_flats[s])
                shards.append(g)
                sq = sq + jnp.dot(g[None, :], g[:, None])[0, 0]
            gnorm = jnp.sqrt(jax.lax.psum(sq, "data"))
            scale = jnp.minimum(1.0, ocfg.clip_norm / jnp.maximum(gnorm, 1e-12))
            norm_cell[0] = gnorm
            return {"shards": shards, "scale": scale}

        def compute(gval, _arrived_s, s):
            b = buckets[s]
            p_flat = pack_bucket(p_leaves, b)
            p_shard = jax.lax.dynamic_slice(p_flat, (ridx * b.cap,), (b.cap,))
            new_p, new_mu[s], new_nu[s] = adamw_leaf_update(
                p_shard, gval["shards"][s], mu_flats[s], nu_flats[s],
                scale=gval["scale"], lr=lr, b1c=b1c, b2c=b2c, ocfg=ocfg,
            )
            return new_p

        def combine(p_shard, s):
            return shard_all_gatherv_start(p_shard, "data",
                                           extents=buckets[s].extents)

        gathered = bucket_plan(
            len(buckets), transfer=transfer, reduce=reduce, compute=compute,
            combine=combine,
        ).run(None, None, double_buffer=double_buffer)

        out_leaves: list = [None] * len(p_leaves)
        for b, flat in zip(buckets, gathered):
            for i, leaf in zip(b.indices, unpack_bucket(flat, b)):
                out_leaves[i] = leaf
        new_params = jax.tree.unflatten(p_treedef, out_leaves)

        out_metrics = {
            "loss": jax.lax.psum(loss, "data") * inv_R,
            **{k: jax.lax.psum(v, "data") * inv_R for k, v in metrics.items()},
            "grad_norm": norm_cell[0],
        }
        return (new_params, step, tuple(new_mu), tuple(new_nu),
                tuple(new_err) if compress else (), out_metrics)

    def train_step(params, opt_state: OptState, batch):
        rep = lambda tree: jax.tree.map(lambda _: P(), tree)
        flat_spec = tuple(P("data") for _ in buckets)
        err_spec = flat_spec if compress else ()
        batch_spec = jax.tree.map(lambda _: P("data"), batch)
        # P() is a pytree-prefix spec for the replicated metrics dict
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(rep(params), P(), flat_spec, flat_spec, err_spec, batch_spec),
            out_specs=(rep(params), P(), flat_spec, flat_spec, err_spec, P()),
            check_vma=False,
        )
        new_params, step, mu, nu, err, metrics = fn(
            params, opt_state.step, opt_state.mu, opt_state.nu,
            opt_state.err, batch,
        )
        new_opt = OptState(step=step, mu=mu, nu=nu, err=err)
        metrics = {**metrics, "lr": lr_at_step(step, ocfg)}
        return new_params, new_opt, metrics

    return train_step


def make_eval_step(cfg, recipe):
    def eval_step(params, batch):
        with use_recipe(recipe):
            loss, metrics = lm.loss_fn(params, batch, cfg)
        return {"loss": loss, **metrics}

    return eval_step


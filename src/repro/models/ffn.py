"""Feed-forward family: SwiGLU, GELU MLP, and top-k MoE (with optional
parallel dense residual branch, for Arctic).

MoE dispatch modes
------------------
All three modes share the router (softmax top-k, renormalized gates) and the
capacity-based slotting (MegaBlocks-style: slot = expert-base + running
per-expert counter; overflow drops, GShard aux loss).  They differ in *where
the routed tokens go*:

``dense`` (default, :func:`moe_ffn` with ``groups<=1``)
    One global (E*C, m) scatter buffer, replicated over the mesh unless the
    recipe can shard ``e`` over ``model``.  The running-counter cumsum spans
    every token, so the dispatch scatter crosses the ``data`` axis.  Decode
    (S == 1) always takes this mode, dropless (C = T).
``grouped`` (``groups > 1``, GShard-style)
    Tokens split into G groups along batch, each with its own capacity and
    slot counter; buffers keep G on the batch axes so the scatter is
    shard-local.  Selected by ``cfg.moe_groups`` (set to the data-parallel
    degree).  Every rank still *computes* all E experts on its group's
    buffer — expert weights shard over ``model`` but the token buffer is
    replicated along it.
``expert-parallel`` (``dispatch="ep"``, :func:`moe_expert_parallel`)
    True expert parallelism on the comm layer: experts shard over the
    ``model`` grid dim in a ragged ceil-split
    (:func:`repro.models.sharding.ragged_expert_extents` — ``E`` need NOT
    divide the axis), tokens shard over (``data``, ``model``) shards, and
    the per-(rank, expert) counts table — the ``MPI_Alltoallv`` counts —
    drives a ragged :func:`repro.core.collectives.all_to_allv_start`
    dispatch to the owner ranks, expert GEMMs on *resident tokens only*
    (:func:`repro.core.collectives.rank_map`), and the inverse
    ``all_to_allv`` combine back to the token owners.  The two a2a legs are
    scheduled by a declared :func:`repro.core.plan.dispatch` comm plan,
    double-buffered over expert groups so both legs overlap the expert
    GEMMs (``dryrun --moe`` proves 0 serialized collectives).  Selected by
    ``cfg.moe_dispatch = "ep"`` when an active recipe provides a >1
    ``model`` axis and the token grid divides (falls back to grouped/dense
    otherwise, with a warning).

Wire accounting: the a2a legs move uniform padded-capacity blocks (the wire
bytes) whose valid payload is the counts table (:func:`moe_comm_model` —
valid < wire under skew, and strictly below the dense modes' full-buffer
replication whenever tokens route sparsely).
"""
from __future__ import annotations

import dataclasses
import warnings

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.collectives import (
    DistBag,
    all_to_allv_start,
    dist_sharding,
    grid_extents,
    rank_map,
)
from repro.core.dist import mpi_cart_traverser
from repro.core.layout import scalar, vector
from repro.core.plan import dispatch as dispatch_plan, intent_of
from repro.core.traverser import traverser

from .module import pspec
from .sharding import current_recipe, ragged_expert_extents, shard_act

# ----------------------------------------------------------------- dense ----

def swiglu_specs(d_model: int, d_ff: int, dtype=jnp.float32):
    return {
        "w_gate": pspec(("m", d_model), ("f", d_ff), dtype=dtype, fan_in=("m",)),
        "w_up": pspec(("m", d_model), ("f", d_ff), dtype=dtype, fan_in=("m",)),
        "w_down": pspec(("f", d_ff), ("m", d_model), dtype=dtype, fan_in=("f",)),
    }


def swiglu(p, x):
    g = shard_act(jnp.einsum("bsm,mf->bsf", x, p["w_gate"].astype(x.dtype)), "ffn_h")
    u = shard_act(jnp.einsum("bsm,mf->bsf", x, p["w_up"].astype(x.dtype)), "ffn_h")
    h = jax.nn.silu(g) * u
    return shard_act(jnp.einsum("bsf,fm->bsm", h, p["w_down"].astype(x.dtype)), "hidden")


def gelu_mlp_specs(d_model: int, d_ff: int, dtype=jnp.float32):
    return {
        "w_in": pspec(("m", d_model), ("f", d_ff), dtype=dtype, fan_in=("m",)),
        "w_out": pspec(("f", d_ff), ("m", d_model), dtype=dtype, fan_in=("f",)),
        "b_in": pspec(("f", d_ff), dtype=dtype, init="zeros"),
        "b_out": pspec(("m", d_model), dtype=dtype, init="zeros"),
    }


def gelu_mlp(p, x):
    h = shard_act(jnp.einsum("bsm,mf->bsf", x, p["w_in"].astype(x.dtype)) + p["b_in"].astype(x.dtype), "ffn_h")
    h = jax.nn.gelu(h)
    return shard_act(jnp.einsum("bsf,fm->bsm", h, p["w_out"].astype(x.dtype)) + p["b_out"].astype(x.dtype), "hidden")


# ------------------------------------------------------------------- MoE ----

def moe_specs(d_model: int, d_ff: int, n_experts: int, *, dense_residual: bool = False, dtype=jnp.float32):
    s = {
        "router": pspec(("m", d_model), ("e", n_experts), dtype=dtype, scale=0.02),
        "w_gate": pspec(("e", n_experts), ("m", d_model), ("f", d_ff), dtype=dtype, fan_in=("m",)),
        "w_up": pspec(("e", n_experts), ("m", d_model), ("f", d_ff), dtype=dtype, fan_in=("m",)),
        "w_down": pspec(("e", n_experts), ("f", d_ff), ("m", d_model), dtype=dtype, fan_in=("f",)),
    }
    if dense_residual:
        s["residual"] = swiglu_specs(d_model, d_ff, dtype)
    return s


def moe_ffn(p, x, *, n_experts: int, top_k: int = 2, capacity_factor: float = 1.25,
            aux_loss_weight: float = 0.01, groups: int = 0, dispatch: str = "auto"):
    """x (B,S,m) -> (y (B,S,m), aux_loss scalar).

    Capacity C = ceil(top_k * T / E * capacity_factor); overflowing tokens
    are dropped (standard Switch/GShard semantics).  Aux loss is the GShard
    load-balancing loss.

    ``groups > 1`` switches to grouped dispatch (GShard-style): tokens split
    into G groups along batch, each with its own capacity and slot counter.
    With G = the data-parallel degree the running-counter cumsum and the
    dispatch scatter become shard-local (no cross-``data`` collective); the
    only cross-device movement left is the expert-parallel exchange (§Perf).

    ``dispatch="ep"`` requests the expert-parallel path
    (:func:`moe_expert_parallel`): experts shard over the ``model`` axis and
    tokens move as overlapped ragged all-to-alls.  When the active recipe
    cannot host it (no mesh, model axis of 1, decode, non-dividing token
    grid) it falls back here with a warning.  Capacity there is *per expert
    per token shard* (the static a2a counts table), so drop behavior under
    overflow differs from the global-capacity dense path; with
    non-overflowing routing both compute the same tokens.
    """
    B, S, m = x.shape
    E = n_experts
    T = B * S
    if dispatch not in ("auto", "ep"):
        raise ValueError(f"moe_ffn: unknown dispatch {dispatch!r} (have 'auto', 'ep')")
    if dispatch == "ep":
        why = _ep_ineligible(current_recipe(), B, S)
        if why is None:
            return moe_expert_parallel(
                p, x, n_experts=n_experts, top_k=top_k,
                capacity_factor=capacity_factor, aux_loss_weight=aux_loss_weight)
        warnings.warn(
            f"moe_ffn: dispatch='ep' requested but {why}; falling back to the "
            "dense/grouped capacity dispatch", stacklevel=2)
    if groups and groups > 1 and S > 1 and B % groups == 0:
        return _moe_grouped(p, x, n_experts=n_experts, top_k=top_k,
                            capacity_factor=capacity_factor,
                            aux_loss_weight=aux_loss_weight, groups=groups)
    if S == 1:
        # decode: dropless (C = T lets any routing fit) — serving must not
        # silently drop tokens; the buffers are tiny at decode batch sizes
        C = T
    else:
        C = int(max(top_k, round(top_k * T / E * capacity_factor)))
    xt = x.reshape(T, m)

    logits = jnp.einsum("tm,me->te", xt, p["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)  # (T, k)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (GShard): E * sum_e f_e * P_e
    me = probs.mean(axis=0)  # mean router prob per expert
    ce = jnp.zeros((E,), jnp.float32).at[gate_idx[:, 0]].add(1.0) / T  # top-1 load
    aux = E * jnp.sum(me * ce) * aux_loss_weight

    # slot assignment: running per-expert counter over (T, k) choices
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)  # (T, k, E)
    flat = onehot.reshape(T * top_k, E)
    pos = jnp.cumsum(flat, axis=0) - flat  # (T*k, E) position before this choice
    pos = (pos * flat).sum(-1).reshape(T, top_k)  # (T, k)
    keep = pos < C
    slot = gate_idx * C + jnp.minimum(pos, C - 1)  # (T, k)

    # dispatch: scatter-add tokens into the (E*C, m) expert buffer
    buf = jnp.zeros((E * C, m), x.dtype)
    w = jnp.where(keep, 1.0, 0.0).astype(x.dtype)  # dispatch weight (drop overflow)
    buf = buf.at[slot.reshape(-1)].add((xt[:, None, :] * w[..., None]).reshape(T * top_k, m))
    be = shard_act(buf.reshape(E, C, m), "moe_buf")

    # expert FFNs, batched over e
    g = jnp.einsum("ecm,emf->ecf", be, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecm,emf->ecf", be, p["w_up"].astype(x.dtype))
    h = jax.nn.silu(g) * u
    ye = shard_act(jnp.einsum("ecf,efm->ecm", h, p["w_down"].astype(x.dtype)), "moe_buf")  # (E, C, m)

    # combine: gather each choice's slot, weight by gate
    yt = ye.reshape(E * C, m)[slot.reshape(-1)].reshape(T, top_k, m)
    comb = (gate_vals.astype(x.dtype) * w)[..., None]
    y = (yt * comb).sum(axis=1).reshape(B, S, m)

    if "residual" in p:
        y = y + swiglu(p["residual"], x)
    return y, aux


def _moe_grouped(p, x, *, n_experts: int, top_k: int, capacity_factor: float,
                 aux_loss_weight: float, groups: int):
    """Grouped-dispatch MoE: per-group capacity, shard-local slot assignment.

    Shapes: tokens (G, Tg, m); buffers (G, E, Cg, m).  The buffer keeps G on
    the batch/data axes (recipe kind 'moe_buf_g'), so the scatter-add that
    builds it is local to each data shard; experts then run batched over
    (G, E) with expert weights sharded over ``model``.
    """
    B, S, m = x.shape
    E = n_experts
    G = groups
    T = B * S
    Tg = T // G
    Cg = int(max(top_k, round(top_k * Tg / E * capacity_factor)))
    xg = x.reshape(G, Tg, m)

    logits = jnp.einsum("gtm,me->gte", xg, p["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (G, Tg, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)  # (G, Tg, k)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    # aux loss over the whole batch (same statistic as ungrouped)
    me = probs.reshape(T, E).mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[gate_idx[..., 0].reshape(-1)].add(1.0) / T
    aux = E * jnp.sum(me * ce) * aux_loss_weight

    # per-group slot assignment: cumsum runs over Tg only (shard-local)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)  # (G, Tg, k, E)
    flat = onehot.reshape(G, Tg * top_k, E)
    pos = jnp.cumsum(flat, axis=1) - flat
    pos = (pos * flat).sum(-1).reshape(G, Tg, top_k)
    keep = pos < Cg
    slot = gate_idx * Cg + jnp.minimum(pos, Cg - 1)  # (G, Tg, k)

    w = jnp.where(keep, 1.0, 0.0).astype(x.dtype)
    contrib = (xg[:, :, None, :] * w[..., None]).reshape(G, Tg * top_k, m)

    def scatter_group(buf_rows, slots, vals):
        return buf_rows.at[slots].add(vals)

    buf = jax.vmap(scatter_group)(
        jnp.zeros((G, E * Cg, m), x.dtype), slot.reshape(G, Tg * top_k), contrib
    )
    be = shard_act(buf.reshape(G, E, Cg, m), "moe_buf_g")

    g_h = jnp.einsum("gecm,emf->gecf", be, p["w_gate"].astype(x.dtype))
    u_h = jnp.einsum("gecm,emf->gecf", be, p["w_up"].astype(x.dtype))
    h = jax.nn.silu(g_h) * u_h
    ye = shard_act(jnp.einsum("gecf,efm->gecm", h, p["w_down"].astype(x.dtype)), "moe_buf_g")

    yt = jax.vmap(lambda rows, slots: rows[slots])(
        ye.reshape(G, E * Cg, m), slot.reshape(G, Tg * top_k)
    ).reshape(G, Tg, top_k, m)
    comb = (gate_vals.astype(x.dtype) * w)[..., None]
    y = (yt * comb).sum(axis=2).reshape(B, S, m)

    if "residual" in p:
        y = y + swiglu(p["residual"], x)
    return y, aux


# ------------------------------------------------- expert-parallel MoE ----
# Declared overlap intent of the dispatch comm plan — the contract the
# `dryrun --moe` gate verifies against the compiled HLO.
MOE_DISPATCH_PLAN_INTENT = intent_of("dispatch")


def _ep_ineligible(recipe, B: int, S: int) -> str | None:
    """Why the expert-parallel path cannot run under ``recipe`` (None = can)."""
    if recipe is None:
        return "no active sharding recipe"
    mesh = recipe.mesh
    if "model" not in mesh.axis_names or mesh.shape["model"] <= 1:
        return "recipe has no model axis of size > 1 to shard experts over"
    if not recipe.batch_axes:
        return "recipe has no data/pod axes to shard tokens over"
    if S == 1:
        return "decode (S == 1) stays on the dense dropless path"
    R = mesh.shape["model"]
    D = 1
    for a in recipe.batch_axes:
        D *= mesh.shape[a]
    if B % D or S % R:
        return (f"token grid (B={B}, S={S}) does not divide the "
                f"(data={D}, model={R}) mesh")
    return None


def moe_ep_counts(E: int, tokens_per_shard: int, top_k: int,
                  capacity_factor: float) -> tuple[int, ...]:
    """Balanced static counts table: per-expert capacity *per token shard*
    (the ``MPI_Alltoallv`` sendcounts each source rank contributes)."""
    c = int(max(1, round(top_k * tokens_per_shard * capacity_factor / E)))
    return (c,) * E


@dataclasses.dataclass(frozen=True)
class _EpGroup:
    """One plan step: a contiguous slice of every rank's local expert range."""
    lo: int               # local expert index range [lo, hi) on every rank
    hi: int
    gsz: int              # hi - lo (expert slots batched per GEMM)
    gbase: int            # first packed row of this group in the scatter buffer
    Sg: int               # routed rows per source shard (= sum of se)
    se: tuple[int, ...]   # dispatch split extents: rows for each dest rank
    cap_s: int            # wire capacity per (source, dest) block = max(se)
    c_max: int            # max per-expert count in this group (GEMM row cap)
    fwd: np.ndarray       # (R, gsz*R*c_max) arrived-row gather table (-1 = pad)
    inv: np.ndarray       # (R, R*cap_s) GEMM-output repack table (-1 = pad)


@dataclasses.dataclass(frozen=True)
class _EpSchedule:
    E: int
    R: int
    cap_e: int
    e_exts: tuple[int, ...]
    counts: tuple[int, ...]
    Q: int                        # total packed rows per source shard
    comb_base: np.ndarray         # (E,) packed-row base per expert
    groups: tuple[_EpGroup, ...]  # nonempty groups only, in packed order


def moe_ep_schedule(E: int, R: int, counts, n_groups: int) -> _EpSchedule:
    """Host-side plan of the expert-parallel exchange.

    Experts shard contiguously over the R model ranks
    (:func:`ragged_expert_extents`); each rank's local range splits into
    ``n_groups`` plan steps.  Rows pack in (group, dest rank, local expert,
    slot) order, so one group is a contiguous static slice of the scatter
    buffer and the combine legs' outputs concatenate back into exactly that
    order.  ``counts[e]`` may be zero (zero-token experts ride through as
    zero split extents); groups whose total is zero are dropped from the
    step list entirely.
    """
    from repro.core.dims import ceil_div

    cap_e, e_exts = ragged_expert_extents(E, R)
    n_groups = max(1, min(int(n_groups), cap_e))
    cap_g = ceil_div(cap_e, n_groups)
    counts = tuple(int(c) for c in counts)
    if len(counts) != E:
        raise ValueError(f"moe_ep_schedule: {len(counts)} counts for {E} experts")
    if min(counts) < 0:
        raise ValueError("moe_ep_schedule: negative counts")

    comb_base = np.zeros((E,), np.int64)
    groups: list[_EpGroup] = []
    off = 0
    for gi in range(n_groups):
        lo, hi = gi * cap_g, min((gi + 1) * cap_g, cap_e)
        if lo >= hi:
            continue
        gsz = hi - lo
        gbase = off
        se = []
        c_max = 0
        for j in range(R):
            sj = 0
            for l in range(lo, min(hi, e_exts[j])):
                e = j * cap_e + l
                comb_base[e] = off
                off += counts[e]
                sj += counts[e]
                c_max = max(c_max, counts[e])
            se.append(sj)
        Sg = off - gbase
        if Sg == 0:
            continue
        cap_s = max(se)
        fwd = np.full((R, gsz, R, c_max), -1, np.int64)
        inv = np.full((R, R, cap_s), -1, np.int64)
        for j in range(R):
            rowbase = 0
            for lrel in range(gsz):
                l = lo + lrel
                if l >= e_exts[j]:
                    continue
                e = j * cap_e + l
                for c in range(counts[e]):
                    for r in range(R):
                        fwd[j, lrel, r, c] = r * cap_s + rowbase + c
                        inv[j, r, rowbase + c] = (lrel * R + r) * c_max + c
                rowbase += counts[e]
        groups.append(_EpGroup(
            lo=lo, hi=hi, gsz=gsz, gbase=gbase, Sg=Sg, se=tuple(se),
            cap_s=cap_s, c_max=c_max,
            fwd=fwd.reshape(R, gsz * R * c_max).astype(np.int32),
            inv=inv.reshape(R, R * cap_s).astype(np.int32),
        ))
    return _EpSchedule(E=E, R=R, cap_e=cap_e, e_exts=e_exts, counts=counts,
                       Q=off, comb_base=comb_base, groups=tuple(groups))


def _topk_sharded(probs, k: int):
    """Top-k along the last axis as k masked argmax rounds.

    Bit-identical selection to :func:`jax.lax.top_k` (ties break to the
    lowest index either way), but the SPMD partitioner replicates the TopK
    custom call even when only batch dims are sharded — argmax +
    ``take_along_axis`` partition as plain reductions/gathers, so the
    routing tensors stay on their (data, model) shards."""
    vals, idxs = [], []
    cur = probs
    for _ in range(k):
        i = jnp.argmax(cur, axis=-1)
        vals.append(jnp.take_along_axis(cur, i[..., None], axis=-1)[..., 0])
        idxs.append(i)
        hit = jax.nn.one_hot(i, probs.shape[-1], dtype=jnp.bool_)
        cur = jnp.where(hit, -jnp.inf, cur)
    return jnp.stack(vals, axis=-1), jnp.stack(idxs, axis=-1)


def moe_comm_model(sched: _EpSchedule, *, d_model: int, itemsize: int,
                   dense_capacity: int | None = None) -> dict:
    """Modeled a2a bytes, per the HLO walker's per-instruction convention.

    Each plan step emits one dispatch and one combine ``all-to-all``
    instruction whose per-shard result holds R padded ``(cap_s, m)`` blocks
    — that is the *wire*; the *valid* payload is the counts table
    (``Sg`` routed rows per shard per leg).  ``dense_capacity`` (the dense
    path's global C) adds the replication cost the dense modes pay instead:
    every model rank materializes the full (E*C, m) buffer, i.e. an
    (R-1)/R-fraction all-gather out and back per sub-communicator rank.
    """
    wire = sum(2 * sched.R * g.cap_s * d_model * itemsize for g in sched.groups)
    valid = sum(2 * g.Sg * d_model * itemsize for g in sched.groups)
    out = {
        "wire_bytes": wire,
        "valid_bytes": valid,
        "valid_fractions": {"all-to-all": (valid / wire) if wire else 1.0},
    }
    if dense_capacity is not None:
        out["dense_replication_bytes"] = (
            2 * (sched.R - 1) * sched.E * dense_capacity * d_model * itemsize)
    return out


def moe_expert_parallel(p, x, *, n_experts: int, top_k: int = 2,
                        capacity_factor: float = 1.25,
                        aux_loss_weight: float = 0.01, recipe=None,
                        n_groups: int = 0, counts=None,
                        double_buffer: bool = True, merge: bool = True):
    """Expert-parallel MoE on the comm layer (see module docstring).

    Tokens reshape to (D, R, Tl, m) shards over (data, model); the router
    and slot assignment run shard-locally against the static ``counts``
    table (per-expert capacity per source shard — the ``MPI_Alltoallv``
    counts; zero counts allowed).  Per expert group the packed rows
    dispatch via :func:`all_to_allv_start` to the owning model ranks,
    :func:`rank_map` runs the expert GEMMs on resident tokens only, and the
    combine a2a returns them — all scheduled by a :func:`dispatch` comm
    plan (double-buffered over groups; ``double_buffer=False`` is the
    bit-identical blocking interpretation).

    ``merge=False`` returns ``y`` still in split form (D, R, Tl, m) — the
    dry-run gate uses it so the boundary reshard of the merge cannot
    pollute the a2a overlap/byte accounting.
    """
    r = recipe or current_recipe()
    B, S, m = x.shape
    why = _ep_ineligible(r, B, S)
    if why:
        raise ValueError(f"moe_expert_parallel: {why}")
    mesh = r.mesh
    E = n_experts
    R = int(mesh.shape["model"])
    bax = tuple(r.batch_axes)
    D = 1
    for a in bax:
        D *= int(mesh.shape[a])
    Bd, Sr = B // D, S // R
    Tl = Bd * Sr
    if counts is None:
        counts = moe_ep_counts(E, Tl, top_k, capacity_factor)
    cap_e, _ = ragged_expert_extents(E, R)
    if not n_groups:
        n_groups = min(2, cap_e)
    sched = moe_ep_schedule(E, R, counts, n_groups)
    if not sched.groups:
        raise ValueError("moe_expert_parallel: all-zero counts table")
    Bspec = bax if len(bax) > 1 else bax[0]

    def cons(a, *entries):
        return jax.lax.with_sharding_constraint(a, NamedSharding(mesh, P(*entries)))

    # token split: (B, S, m) -> (D, R, Tl, m) shards — a local slice of the
    # replicated (or already seq-sharded) residual stream on every rank
    xg = x.reshape(D, Bd, R, Sr, m).transpose(0, 2, 1, 3, 4).reshape(D, R, Tl, m)
    xg = cons(xg, Bspec, "model", None, None)

    logits = jnp.einsum("drtm,me->drte", xg, p["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (D, R, Tl, E)
    gate_vals, gate_idx = _topk_sharded(probs, top_k)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    # shard-preserving reductions (lower to all-reduces, never all-gathers):
    # reshape(T, E) here would merge the sharded token dims and GSPMD would
    # replicate the whole routing tensor before top_k
    T = B * S
    me = probs.sum(axis=(0, 1, 2)) / T
    ce = jax.nn.one_hot(gate_idx[..., 0], E, dtype=jnp.float32).sum(axis=(0, 1, 2)) / T
    aux = E * jnp.sum(me * ce) * aux_loss_weight

    # shard-local slot assignment against the packed static counts table
    counts_arr = jnp.asarray(sched.counts, jnp.int32)
    base_arr = jnp.asarray(sched.comb_base, jnp.int32)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)  # (D, R, Tl, k, E)
    flat = onehot.reshape(D, R, Tl * top_k, E)
    pos = jnp.cumsum(flat, axis=2) - flat
    pos = (pos * flat).sum(-1).reshape(D, R, Tl, top_k)
    cnt_k = counts_arr[gate_idx]
    keep = pos < cnt_k
    slot = base_arr[gate_idx] + jnp.minimum(pos, jnp.maximum(cnt_k - 1, 0))
    w = jnp.where(keep, 1.0, 0.0).astype(x.dtype)

    # double vmap over (D, R) — merging the two sharded dims into one D*R
    # axis would defeat GSPMD's propagation and replicate the routing state
    contrib = (xg[:, :, :, None, :] * w[..., None]).reshape(D, R, Tl * top_k, m)
    buf = jax.vmap(jax.vmap(lambda b, s_, v: b.at[s_].add(v)))(
        jnp.zeros((D, R, sched.Q, m), x.dtype),
        slot.reshape(D, R, Tl * top_k), contrib)
    buf = cons(buf, Bspec, "model", None, None)

    dt = mpi_cart_traverser(
        {"D": bax, "M": ("model",)},
        traverser(scalar(x.dtype) ^ vector("D", D) ^ vector("M", R)), mesh)
    in_ext = grid_extents(dt, ("D", "M"), {"M": ("r", (1,) * R)})

    def mbag(arr, tile):
        data = jax.lax.with_sharding_constraint(arr, dist_sharding(dt, tile, rank_dim="M"))
        return DistBag(data, tile, dt, ("M",))

    # expert weights: pad E -> R*cap_e zero slots and slice each group's
    # (R, gsz, ...) panel, sharded over the model axis only (data-replicated)
    f = p["w_gate"].shape[-1]
    padE = R * cap_e - E

    def wpad(wt):
        wt = jnp.pad(wt.astype(x.dtype), ((0, padE),) + ((0, 0),) * (wt.ndim - 1))
        return wt.reshape(R, cap_e, *wt.shape[1:])

    wg_full, wu_full, wd_full = wpad(p["w_gate"]), wpad(p["w_up"]), wpad(p["w_down"])

    per_group = []
    for g in sched.groups:
        in_tile = scalar(x.dtype) ^ vector("em", m) ^ vector("q", g.Sg) ^ vector("r", 1)
        out_tile = scalar(x.dtype) ^ vector("em", m) ^ vector("q", g.cap_s) ^ vector("r", R)
        up_tile = scalar(x.dtype) ^ vector("wf", f) ^ vector("wm", m) ^ vector("we", g.gsz)
        dn_tile = scalar(x.dtype) ^ vector("wm", m) ^ vector("wf", f) ^ vector("we", g.gsz)
        per_group.append({
            "g": g,
            "in_tile": in_tile,
            "out_tile": out_tile,
            "wg": mbag(jax.lax.slice_in_dim(wg_full, g.lo, g.hi, axis=1), up_tile),
            "wu": mbag(jax.lax.slice_in_dim(wu_full, g.lo, g.hi, axis=1), up_tile),
            "wd": mbag(jax.lax.slice_in_dim(wd_full, g.lo, g.hi, axis=1), dn_tile),
            "fwd": mbag(jnp.asarray(g.fwd),
                        scalar(np.int32) ^ vector("fi", g.gsz * R * g.c_max)),
            "inv": mbag(jnp.asarray(g.inv),
                        scalar(np.int32) ^ vector("ii", R * g.cap_s)),
            "out_ext": grid_extents(dt, ("D", "M"), {"M": ("q", g.se)}),
        })

    def transfer(state, s):
        pg = per_group[s]
        g = pg["g"]
        blk = jax.lax.slice_in_dim(state, g.gbase, g.gbase + g.Sg, axis=2)
        data = cons(blk.reshape(D, R, 1, g.Sg, m), Bspec, "model", None, None, None)
        db = DistBag(data, pg["in_tile"], dt, ("D", "M"), extents=in_ext)
        return all_to_allv_start(db, pg["out_tile"], split_dim="q", concat_dim="r",
                                 split_extents=g.se, rank_dim="M")

    def compute(carry, arrived, s):
        pg = per_group[s]
        g = pg["g"]
        gsz, c_max, cap_s = g.gsz, g.c_max, g.cap_s

        def gemm(rank, xb, fb, ib, wgb, wub, wdb):
            rows = xb.data.reshape(R * cap_s, m)
            xe = jnp.take(rows, fb.data, axis=0, mode="fill", fill_value=0)
            xe = xe.reshape(gsz, R * c_max, m)
            gh = jnp.einsum("ecm,emf->ecf", xe, wgb.data)
            uh = jnp.einsum("ecm,emf->ecf", xe, wub.data)
            ye = jnp.einsum("ecf,efm->ecm", jax.nn.silu(gh) * uh, wdb.data)
            out = jnp.take(ye.reshape(gsz * R * c_max, m), ib.data, axis=0,
                           mode="fill", fill_value=0)
            return out.reshape(R, cap_s, m)

        return rank_map(gemm, dt, arrived, pg["fwd"], pg["inv"],
                        pg["wg"], pg["wu"], pg["wd"],
                        out_tile_layout=pg["out_tile"], rank_dim=("D", "M"),
                        out_extents=pg["out_ext"])

    def combine(res, s):
        pg = per_group[s]
        return all_to_allv_start(res, pg["in_tile"], split_dim="r", concat_dim="q",
                                 split_extents=(1,) * R, rank_dim="M")

    def epilogue(done, state):
        parts = [d.data.reshape(D, R, pg["g"].Sg, m)
                 for pg, d in zip(per_group, done)]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=2)

    plan = dispatch_plan(len(per_group), transfer=transfer, compute=compute,
                         combine=combine, epilogue=epilogue)
    routed = plan.run(buf, None, double_buffer=double_buffer)  # (D, R, Q, m)
    routed = cons(routed, Bspec, "model", None, None)

    yt = jax.vmap(jax.vmap(lambda rows, s_: rows[s_]))(
        routed, slot.reshape(D, R, Tl * top_k)
    ).reshape(D, R, Tl, top_k, m)
    comb = (gate_vals.astype(x.dtype) * w)[..., None]
    y = cons((yt * comb).sum(axis=3), Bspec, "model", None, None)  # (D, R, Tl, m)
    if not merge:
        return y, aux

    ym = y.reshape(D, R, Bd, Sr, m).transpose(0, 2, 1, 3, 4).reshape(B, S, m)
    ym = shard_act(ym, "hidden")
    if "residual" in p:
        ym = ym + swiglu(p["residual"], x)
    return ym, aux

"""Explicit tensor-parallel decode on the comm layer: shard_map + comm plans.

The GSPMD decode path (``models/lm.decode_step`` under a recipe) lets XLA
place every collective.  This module is the serving engine's *distributed
decode step* built the other way around — the way the rest of the comm layer
works: the program says exactly which collective moves, when it is issued,
and which compute hides it, using the shard-level non-blocking twins
(:func:`repro.core.p2p.shard_all_reduce_start` /
``shard_all_gather_start``) on the shared :class:`repro.core.request.Pending`
request path, scheduled by a declared :func:`repro.core.plan.stagger` comm
plan.

Per decode step and layer, the batch is split into ``microbatches``
independent row groups.  Each microbatch's attention (and FFN) produces a
*partial* output on its rank's head (or ffn) shard and issues its
tensor-parallel ``Iallreduce``; because the microbatches are mutually
independent, microbatch ``i``'s reduction completes behind microbatch
``i+1``'s compute — the continuous-batching analogue of the SUMMA ring's
issue-before/wait-after window, and the schedule the ``--serve`` dry run
proves serializes nothing.  With ``microbatches=1`` the same program has no
sibling compute and every reduction lands on the critical path — the
negative control.

The same step runs an admission-time prefill chunk (S > 1 tokens per row),
so a serving engine keeps its weights and KV cache in this layout for both
step kinds (:func:`place_tp`) and no device holds the whole model.

Scope: the attention families with plain GQA blocks (``dense``/``audio``),
with or without QKV biases (bias shards ride the head/KV-group shards and
are added between each projection and rope, as in the single-host block);
heads, KV groups, FFN hidden and vocab must divide the ``model`` axis, batch
slots must divide ``data`` x ``microbatches``.  MoE blocks are the one
remaining exclusion — the engine falls back to the single-host path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.p2p import shard_all_gather_start, shard_all_reduce_start
from repro.core.plan import intent_of, stagger
from repro.models import lm
from repro.models.attention import KVCache, apply_rope, attention_decode, rope_angles
from repro.models.blocks import rmsnorm

__all__ = ["make_tp_decode_step", "tp_decode_specs", "place_tp", "DECODE_TP_PLAN_INTENT"]

# declared overlap intent of the decode schedule, consumed by the --serve
# dry run's plan/HLO agreement gate
DECODE_TP_PLAN_INTENT = intent_of("stagger")


def _check(cfg, mesh, slots: int, microbatches: int) -> None:
    if cfg.family not in ("dense", "audio"):
        raise ValueError(f"tp decode supports dense/audio families, not {cfg.family!r}")
    if cfg.n_experts:
        raise ValueError("tp decode: MoE blocks not supported")
    for name in ("data", "model"):
        if name not in mesh.shape:
            raise ValueError(f"tp decode needs a (data, model) mesh, missing {name!r}")
    msize = mesh.shape["model"]
    for label, n in (("n_heads", cfg.n_heads), ("n_kv", cfg.n_kv),
                     ("d_ff", cfg.d_ff), ("vocab_padded", cfg.vocab_padded)):
        if n % msize:
            raise ValueError(f"tp decode: {label}={n} must divide model axis {msize}")
    dsize = mesh.shape["data"]
    if slots % dsize or (slots // dsize) % microbatches:
        raise ValueError(
            f"tp decode: {slots} slots must split over data={dsize} x "
            f"microbatches={microbatches}"
        )


def tp_decode_specs(cfg, *, stacked: bool = True):
    """PartitionSpec trees (params, cache k/v, cache length) for the explicit
    TP decode layout: heads/KV-groups/FFN-hidden/vocab over ``model``, batch
    slots over ``data``, everything else replicated."""
    from jax.sharding import PartitionSpec as P

    lead = (None,) if stacked else ()
    attn = {
        "wq": P(*lead, None, "model", None),
        "wk": P(*lead, None, "model", None),
        "wv": P(*lead, None, "model", None),
        "wo": P(*lead, "model", None, None),
    }
    if cfg.qkv_bias:
        # biases ride the head/KV-group shards of their projections
        attn["bq"] = P(*lead, "model", None)
        attn["bk"] = P(*lead, "model", None)
        attn["bv"] = P(*lead, "model", None)
    if cfg.ffn_kind == "gelu":
        ffn = {"w_in": P(*lead, None, "model"), "w_out": P(*lead, "model", None),
               "b_in": P(*lead, "model"), "b_out": P(*lead, None)}
    else:
        ffn = {"w_gate": P(*lead, None, "model"), "w_up": P(*lead, None, "model"),
               "w_down": P(*lead, "model", None)}
    params = {
        "final_norm": P(None),
        "blocks": {"ln1": P(*lead, None), "ln2": P(*lead, None),
                   "attn": attn, "ffn": ffn},
    }
    if cfg.input_kind in ("tokens", "tokens+image"):
        params["embed"] = P("model", None)
    if not cfg.tie_embeddings:
        params["lm_head"] = P(None, "model")
    kv = P(*lead, "data", "model", None, None)
    return params, kv, P(*lead, "data")


def place_tp(cfg, mesh, params, state):
    """Commit ``params`` and a stacked :class:`repro.models.lm.DecodeState`
    to the TP layout of :func:`tp_decode_specs`: every device then holds
    only its shard, and a step reads the weights and the cache where they
    live instead of resharding them."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    p_specs, kv_spec, len_spec = tp_decode_specs(cfg)

    def put(spec, x):
        return jax.device_put(x, NamedSharding(mesh, spec))

    params = jax.tree.map(put, p_specs, params, is_leaf=lambda s: isinstance(s, P))
    caches = state.caches
    state = lm.DecodeState(
        caches=KVCache(put(kv_spec, caches.k), put(kv_spec, caches.v),
                       put(len_spec, caches.length)),
        positions=put(P("data"), state.positions),
    )
    return params, state


def make_tp_decode_step(cfg, mesh, *, slots: int, microbatches: int = 2,
                        attn_impl: str | None = None):
    """Build ``step(params, state, batch, counts) -> (logits, new_state)``.

    ``state`` is the stacked :class:`repro.models.lm.DecodeState`;
    ``batch`` holds ``tokens`` (B, S) or ``embeds`` (B, S, m); ``counts``
    (B,) int32 says how many of the S tokens are valid per slot, 0 for an
    idle slot — idle rows' cache writes are masked out and positions
    advance by the count (the per-row semantics of the single-host
    ``lm.decode_step``).  S == 1 is a decode step; S > 1 a whole-prompt
    prefill chunk.  ``logits`` (B, 1, vocab) are those of each row's last
    valid token, like a prefill ``lm.decode_step``'s.

    ``attn_impl`` picks the per-layer attention kernel under the stagger
    plan (see ``models/attention.py``'s dispatch table): ``"pallas"`` /
    ``"interpret"`` run the flash-decoding kernel inside each
    microbatch's compute stage, ``None`` resolves per backend, ``"jnp"``
    keeps the dense jnp path.
    """
    _check(cfg, mesh, slots, microbatches)
    msize = mesh.shape["model"]
    dsize = mesh.shape["data"]
    mb = microbatches
    L = cfg.n_layers
    tokens_in = cfg.input_kind != "embeds"
    act_dt = cfg.act_dtype

    from jax.sharding import PartitionSpec as P

    p_specs, kv_spec, len_spec = tp_decode_specs(cfg)
    in_batch = P("data", None) if tokens_in else P("data", None, None)
    def body(params, k_all, v_all, length_all, positions, inputs, counts):
        midx = jax.lax.axis_index("model")
        active = counts > 0
        Bl = positions.shape[0]
        bm = Bl // mb
        S = inputs.shape[1]
        pos2d = positions[:, None] + jnp.arange(S, dtype=positions.dtype)[None, :]

        # ---- embed: local vocab-shard gather + masked Iallreduce ----
        if tokens_in:
            vl = cfg.vocab_padded // msize
            table = params["embed"].astype(act_dt)
            loc = inputs - midx * vl
            ok = (loc >= 0) & (loc < vl)
            e = jnp.take(table, jnp.clip(loc, 0, vl - 1), axis=0)
            e = jnp.where(ok[..., None], e, jnp.zeros((), act_dt))
            # each token's row lives on exactly one rank: the psum is a pure
            # routing gather (one nonzero addend) — bitwise the oracle lookup
            x = shard_all_reduce_start(e, "model").wait()
        else:
            x = (inputs.astype(act_dt)
                 + lm._sinusoidal(pos2d, cfg.d_model).astype(act_dt))

        rows = [slice(s * bm, (s + 1) * bm) for s in range(mb)]
        xs = [x[r] for r in rows]
        a_mb = [active[r] for r in rows]
        c_mb = [counts[r] for r in rows]
        p_mb = [pos2d[r] for r in rows]
        st_mb = [positions[r] for r in rows]

        def masked_update(cache, new, length, act_rows):
            size = cache.shape[2]

            def row(c, n, p):
                return jax.lax.dynamic_update_slice(c, n, (0, p, 0))

            upd = jax.vmap(row)(cache, new.astype(cache.dtype), length % size)
            return jnp.where(act_rows[:, None, None, None], upd, cache)

        new_k_layers, new_v_layers = [], []
        # every stage's all-reduces start behind the previous stage's last
        # one (one communicator's issue order), so no compiler combines a
        # reduction with an independent one of another stage
        last = None
        blocks = params["blocks"]
        for l in range(L):
            ln1 = blocks["ln1"][l]
            ln2 = blocks["ln2"][l]
            wq = blocks["attn"]["wq"][l]
            wk = blocks["attn"]["wk"][l]
            wv = blocks["attn"]["wv"][l]
            wo = blocks["attn"]["wo"][l]
            if cfg.qkv_bias:
                bq = blocks["attn"]["bq"][l]
                bk = blocks["attn"]["bk"][l]
                bv = blocks["attn"]["bv"][l]
            else:
                bq = bk = bv = None
            new_k_l: list = [None] * mb
            new_v_l: list = [None] * mb

            def attn_compute(_c, _s, s, l=l, ln1=ln1, wq=wq, wk=wk, wv=wv, wo=wo,
                             bq=bq, bk=bk, bv=bv, new_k_l=new_k_l, new_v_l=new_v_l):
                xi = xs[s]
                xn = rmsnorm(ln1, xi)
                q = jnp.einsum("bsm,mhd->bhsd", xn, wq.astype(xi.dtype))
                k = jnp.einsum("bsm,mgd->bgsd", xn, wk.astype(xi.dtype))
                v = jnp.einsum("bsm,mgd->bgsd", xn, wv.astype(xi.dtype))
                if bq is not None:
                    # local head/group shard of the bias, added between the
                    # projection and rope, as in models/attention.py's
                    # gqa_attention
                    q = q + bq.astype(xi.dtype)[None, :, None, :]
                    k = k + bk.astype(xi.dtype)[None, :, None, :]
                    v = v + bv.astype(xi.dtype)[None, :, None, :]
                cos, sin = rope_angles(p_mb[s], cfg.head_dim, cfg.rope_theta)
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
                length = length_all[l][rows[s]]
                nk = masked_update(k_all[l][rows[s]], k, length, a_mb[s])
                nv = masked_update(v_all[l][rows[s]], v, length, a_mb[s])
                new_k_l[s] = nk
                new_v_l[s] = nv
                o = attention_decode(q, nk, nv, length + c_mb[s],
                                     q_start=st_mb[s], impl=attn_impl)
                # local head shard's partial projection — the transfer stage
                # issues its Iallreduce; the next microbatch's math hides it.
                # Partials stay f32 through the reduction and are rounded to
                # the activation dtype once, post-psum: splitting the dot
                # across ranks then only perturbs f32-level accumulation
                # order ahead of the one rounding the single-host dot has.
                # (The
                # TPU compiler folds that convert into a bf16-typed
                # all-reduce that still sums in f32: on a v5e its result
                # equals the exact sum rounded once, element for element.)
                return jnp.einsum("bhsd,hdm->bsm", o, wo.astype(xi.dtype),
                                  preferred_element_type=jnp.float32)

            attn_done = stagger(
                mb,
                transfer=lambda part, s: shard_all_reduce_start(part, "model"),
                compute=attn_compute,
                epilogue=lambda done, _s: [d.astype(act_dt) for d in done],
            ).run(None, None, double_buffer=True, after=last)
            xs = [xs[s] + attn_done[s] for s in range(mb)]

            ffn = blocks["ffn"]
            if cfg.ffn_kind == "gelu":
                w_in = ffn["w_in"][l]
                w_out = ffn["w_out"][l]
                b_in = ffn["b_in"][l]
                b_out = ffn["b_out"][l]

                def ffn_compute(_c, _s, s, ln2=ln2, w_in=w_in, w_out=w_out, b_in=b_in):
                    xn = rmsnorm(ln2, xs[s])
                    h = jnp.einsum("bsm,mf->bsf", xn, w_in.astype(xn.dtype))
                    h = jax.nn.gelu(h + b_in.astype(xn.dtype))
                    return jnp.einsum("bsf,fm->bsm", h, w_out.astype(xn.dtype),
                                      preferred_element_type=jnp.float32)

                def ffn_epilogue(done, _s, b_out=b_out):
                    # round the f32-reduced sum once, then add the replicated
                    # output bias in the activation dtype — the oracle's order
                    return [d.astype(act_dt) + b_out.astype(act_dt)
                            for d in done]
            else:
                w_gate = ffn["w_gate"][l]
                w_up = ffn["w_up"][l]
                w_down = ffn["w_down"][l]

                def ffn_compute(_c, _s, s, ln2=ln2, w_gate=w_gate, w_up=w_up, w_down=w_down):
                    xn = rmsnorm(ln2, xs[s])
                    g = jnp.einsum("bsm,mf->bsf", xn, w_gate.astype(xn.dtype))
                    u = jnp.einsum("bsm,mf->bsf", xn, w_up.astype(xn.dtype))
                    h = jax.nn.silu(g) * u
                    return jnp.einsum("bsf,fm->bsm", h, w_down.astype(xn.dtype),
                                      preferred_element_type=jnp.float32)

                def ffn_epilogue(done, _s):
                    return [d.astype(act_dt) for d in done]

            ffn_done = stagger(
                mb,
                transfer=lambda part, s: shard_all_reduce_start(part, "model"),
                compute=ffn_compute,
                epilogue=ffn_epilogue,
            ).run(None, None, double_buffer=True, after=attn_done[-1])
            xs = [xs[s] + ffn_done[s] for s in range(mb)]
            last = ffn_done[-1]

            new_k_layers.append(jnp.concatenate(new_k_l, axis=0))
            new_v_layers.append(jnp.concatenate(new_v_l, axis=0))

        x = jnp.concatenate(xs, axis=0)
        # only each row's last valid token predicts the next one
        x = x[jnp.arange(Bl), jnp.maximum(counts - 1, 0)][:, None]
        xn = rmsnorm(params["final_norm"], x)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        # vocab-sharded head: the contraction dim is replicated, so each
        # rank's logit columns are full dots, as in lm_logits
        logits_loc = jnp.einsum("bsm,mv->bsv", xn, head.astype(xn.dtype))
        # terminal Iallgather of the local vocab shards (rank-ordered)
        logits = shard_all_gather_start(logits_loc, "model", axis=2).wait()

        new_k = jnp.stack(new_k_layers)
        new_v = jnp.stack(new_v_layers)
        new_len = length_all + counts[None, :]
        return logits, new_k, new_v, new_len, positions + counts

    out_specs = (P("data", None, None), kv_spec, kv_spec, len_spec, P("data"))
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(p_specs, kv_spec, kv_spec, len_spec, P("data"), in_batch, P("data")),
        out_specs=out_specs,
        check_vma=False,
    )

    def step(params, state, batch, counts):
        caches = state.caches
        inputs = batch["tokens"] if tokens_in else batch["embeds"]
        logits, nk, nv, nlen, npos = fn(
            params, caches.k, caches.v, caches.length, state.positions,
            inputs, counts.astype(jnp.int32),
        )
        new_state = lm.DecodeState(caches=KVCache(nk, nv, nlen), positions=npos)
        return logits, new_state

    return step

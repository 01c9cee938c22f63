"""Attention family: GQA (RoPE, optional QKV bias), MLA, cross-attention.

Attention kernel dispatch
-------------------------
Every hot attention path dispatches through :mod:`repro.kernels.ops` to a
Pallas kernel on TPU and a jnp form elsewhere:

==========  ===============================  ================================
Path        TPU (default)                    CPU/GPU (default)
==========  ===============================  ================================
seq         ``flash_attention_pallas``       ``blockwise_attention_ref``
(train/     (blockwise online softmax,       (same math, jnp ``lax.scan``
prefill)    KV-block grid axis)              over KV blocks)
ring step   ``flash_attention_carry_pallas`` jnp online-softmax merge
(sp_ring)   — one ``pallas_call`` per held   (the ``impl="jnp"`` reference
            KV block, ``(acc, m, l)`` carry  and interpret-mode oracle)
            threaded across ring steps
decode      ``flash_decode_pallas``          jnp dense streaming attention
(serving)   (KV-block grid, online softmax   (probabilities rounded to the
            in VMEM, per-slot masks)         cache dtype before p@v)
==========  ===============================  ================================

Overrides: ``attn_impl=`` on the model-facing ops (and ``impl=`` on
:func:`attention_seq` / :func:`attention_decode` / the ring internals)
selects ``"pallas"`` (compiled), ``"interpret"`` (Pallas interpret mode —
the CPU correctness oracle for the kernels, used by the dry-run gates'
``--attn-impl interpret``), or ``"jnp"``/``"ref"`` (the pure-jnp forms).
``None`` resolves per backend as above.  Within each path the variants
agree: ring carry-chains are bitwise-equal to single-shot flash at f32, and
the decode kernel stays within a stated tolerance of the jnp form.

The ring and decode structure around the kernels:
  * ``seq`` under a sequence-parallel ``sp_ring`` recipe becomes
    :func:`ring_attention_seq`: the KV blocks rotate around the ``model``
    mesh axis with the non-blocking ``shard_ring_shift_start`` issued
    *before* each step's local attention (double-buffered, exactly like the
    SUMMA ring), so the transfer overlaps the step's math.
  * ``decode`` reads the whole cache per new token; with the cache-seq dim
    sharded over ``model``, XLA merges the partial softmaxes across devices
    (distributed flash-decoding) above whichever local kernel ran.

All weights are declared via :func:`repro.models.module.pspec` with named
dims — sharding recipes bind them to mesh axes elsewhere.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.core.p2p import shard_ring_shift_start
from repro.core.plan import intent_of, ring
from repro.kernels import ops
from .module import pspec
from .sharding import _fit_spec, current_recipe, shard_act

# ------------------------------------------------------------------ RoPE ----

def rope_angles(positions, dim: int, theta: float = 10000.0):
    """positions (...,) int32 -> cos/sin (..., dim/2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x (..., S, D even); cos/sin (S, D/2) — shared angles — or (B, S, D/2)
    for per-row positions (continuous batching: every slot rotates at its own
    absolute position)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    if cos.ndim == 2:
        shape = [1] * (x.ndim - 2) + list(cos.shape)
    else:  # batched (B, S, D/2): broadcast over the head dims between B and S
        shape = [cos.shape[0]] + [1] * (x.ndim - cos.ndim) + list(cos.shape[1:])
    c = cos.reshape(shape)
    s = sin.reshape(shape)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


# ------------------------------------------------------------ param specs ----

def gqa_specs(d_model: int, n_heads: int, n_kv: int, head_dim: int, *, qkv_bias: bool = False, dtype=jnp.float32):
    s = {
        "wq": pspec(("m", d_model), ("h", n_heads), ("d", head_dim), dtype=dtype, fan_in=("m",)),
        "wk": pspec(("m", d_model), ("g", n_kv), ("d", head_dim), dtype=dtype, fan_in=("m",)),
        "wv": pspec(("m", d_model), ("g", n_kv), ("d", head_dim), dtype=dtype, fan_in=("m",)),
        "wo": pspec(("h", n_heads), ("d", head_dim), ("m", d_model), dtype=dtype, fan_in=("h", "d")),
    }
    if qkv_bias:
        s["bq"] = pspec(("h", n_heads), ("d", head_dim), dtype=dtype, init="zeros")
        s["bk"] = pspec(("g", n_kv), ("d", head_dim), dtype=dtype, init="zeros")
        s["bv"] = pspec(("g", n_kv), ("d", head_dim), dtype=dtype, init="zeros")
    return s


def mla_specs(d_model: int, n_heads: int, *, q_rank: int, kv_rank: int, d_nope: int, d_rope: int, d_v: int, dtype=jnp.float32):
    return {
        "wdq": pspec(("m", d_model), ("q", q_rank), dtype=dtype, fan_in=("m",)),
        "wuq": pspec(("q", q_rank), ("h", n_heads), ("c", d_nope + d_rope), dtype=dtype, fan_in=("q",)),
        "wdkv": pspec(("m", d_model), ("k", kv_rank), dtype=dtype, fan_in=("m",)),
        "wkr": pspec(("m", d_model), ("r", d_rope), dtype=dtype, fan_in=("m",)),
        "wuk": pspec(("k", kv_rank), ("h", n_heads), ("n", d_nope), dtype=dtype, fan_in=("k",)),
        "wuv": pspec(("k", kv_rank), ("h", n_heads), ("w", d_v), dtype=dtype, fan_in=("k",)),
        "wo": pspec(("h", n_heads), ("w", d_v), ("m", d_model), dtype=dtype, fan_in=("h", "w")),
        "q_norm": pspec(("q", q_rank), dtype=dtype, init="ones"),
        "kv_norm": pspec(("k", kv_rank), dtype=dtype, init="ones"),
    }


# ------------------------------------------------------------------ cores ----

def attention_seq(q, k, v, *, causal: bool = True, impl: str | None = None, block: int = 512,
                  mixed: bool | None = None):
    """q (B,H,S,D), k/v (B,G,S,D) — full-sequence blockwise attention."""
    return ops.flash_attention(q, k, v, causal=causal, impl=impl, bq=block, bk=block, mixed=mixed)


# ------------------------------------------------------- ring attention ----

# declared overlap intent of the attention ring's comm plan, consumed by the
# sp_ring dry run's plan/HLO agreement gate
RING_ATTENTION_PLAN_INTENT = intent_of("ring")


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool, double_buffer: bool,
                          valid_len: int | None = None, impl: str | None = None,
                          block: int = 512):
    """Per-device body of the sequence-parallel attention ring.

    ``q`` (B,H,Sl,D) and ``k``/``v`` (B,G,Sl,D) are the *local* seq chunks of
    rank ``r`` on the ``axis_name`` ring (R ranks, global S = R*Sl, chunks
    contiguous in rank order).  Each of R steps computes blockwise
    online-softmax attention of the resident Q chunk against the currently
    held KV block, exactly the flash-attention merge but with the block axis
    unrolled over *devices* instead of VMEM tiles; meanwhile the next KV
    block is already in flight.  The rotation is a declared
    :func:`repro.core.plan.ring` comm plan: the planner issues
    ``shard_ring_shift_start`` (the ``MPI_Isend``/``Irecv`` analogue)
    *before* the step's local attention and completes it with
    ``Pending.wait`` after, exactly like the double-buffered SUMMA ring
    issues its panel rotation before the local GEMM.
    ``double_buffer=False`` keeps the blocking interpretation of the same
    plan — numerically bit-identical, the reference variant.

    The per-step local attention dispatches on ``impl``: ``"pallas"`` /
    ``"interpret"`` run one carry-state ``pallas_call``
    (:func:`repro.kernels.flash_attention.flash_attention_carry_pallas`) per
    held KV block, threading the running ``(acc, m, l)`` across ring steps
    — the per-step causal offset rides in via scalar prefetch since
    ``axis_index`` is traced; ``"jnp"`` (the non-TPU default) keeps the jnp
    online-softmax merge below as the reference.  The two agree bitwise at
    the carry level per construction of the kernel (and the kernel's
    R-step chain equals single-shot flash bitwise at f32).

    ``valid_len`` enables *ragged* sequence shards (S % R != 0): the global
    sequence is padded to R * Sl and positions >= valid_len are masked out
    of every score block — the zero-padded KV rides the ring at capacity
    (uniform wire datatype, like every ragged DistBag transfer) while the
    online-softmax only ever normalizes over valid keys.  Rows beyond
    valid_len are garbage and sliced off by the caller.
    """
    R = jax.lax.psum(1, axis_name)  # static ring size
    me = jax.lax.axis_index(axis_name)
    B, Hq, Sl, D = q.shape
    G = k.shape[1]
    rep = Hq // G
    scale = D ** -0.5
    impl = impl or ("pallas" if jax.default_backend() == "tpu" else "jnp")

    if impl not in ("jnp", "ref"):
        # carry-state flash kernel: one pallas_call per ring step over the
        # resident Q chunk vs the held KV block, (acc, m, l) threaded across
        # steps instead of re-merged in jnp
        bq_ = min(block, Sl)

        def compute_k(acc, kv, s):
            kb, vb = kv
            # after s hops of +1, rank r holds the KV block of rank (r-s)%R
            return ops.flash_attention_carry(
                q, kb, vb, acc,
                q_offset=me * Sl, k_offset=((me - s) % R) * Sl,
                valid_len=valid_len, causal=causal, scale=scale,
                impl=impl, bq=bq_, bk=bq_,
            )

        acc0 = (
            jnp.zeros((B, Hq, Sl, D), jnp.float32),
            jnp.full((B, Hq, Sl), -1e30, jnp.float32),
            jnp.zeros((B, Hq, Sl), jnp.float32),
        )
        plan = ring(
            R,
            transfer=lambda kv, s: shard_ring_shift_start(kv, axis_name, 1),
            compute=compute_k,
            epilogue=lambda acc, kv: (
                acc[0] / jnp.where(acc[2] == 0.0, 1.0, acc[2])[..., None]
            ).astype(q.dtype),
        )
        return plan.run((k, v), acc0, double_buffer=double_buffer)

    qg = q.reshape(B, G, rep, Sl, D)
    q_pos = me * Sl + jnp.arange(Sl)

    # online-softmax accumulators, f32 like the flash kernel
    o = jnp.zeros((B, G, rep, Sl, D), jnp.float32)
    m = jnp.full((B, G, rep, Sl), -1e30, jnp.float32)
    l = jnp.zeros((B, G, rep, Sl), jnp.float32)

    def compute(acc, kv, s):
        o, m, l = acc
        kb, vb = kv
        # after s hops of +1, rank r holds the KV block of rank (r - s) % R
        k_pos = ((me - s) % R) * Sl + jnp.arange(Sl)
        sc = jnp.einsum("bgrqd,bgkd->bgrqk", qg, kb,
                        preferred_element_type=jnp.float32) * scale
        mask = None
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
        if valid_len is not None:
            pad_mask = k_pos[None, :] < valid_len
            mask = pad_mask if mask is None else (mask & pad_mask)
        if mask is not None:
            sc = jnp.where(mask[None, None, None], sc, -1e30)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bgrqk,bgkd->bgrqd", p, vb.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return (o, m_new, l)

    # same declared schedule as the SUMMA rings: the planner issues each
    # step's KV rotation before the local attention and waits after it
    plan = ring(
        R,
        transfer=lambda kv, s: shard_ring_shift_start(kv, axis_name, 1),
        compute=compute,
        epilogue=lambda acc, kv: (
            acc[0] / acc[2][..., None]
        ).reshape(B, Hq, Sl, D).astype(q.dtype),
    )
    return plan.run((k, v), (o, m, l), double_buffer=double_buffer)


def ring_attention_seq(q, k, v, *, mesh, axis_name: str = "model", q_spec=None,
                       kv_spec=None, causal: bool = True, double_buffer: bool = True,
                       slice_output: bool = True, impl: str | None = None,
                       block: int = 512):
    """Sequence-parallel ring attention over the ``axis_name`` mesh axis.

    The distributed twin of :func:`attention_seq`: q (B,H,S,D) and k/v
    (B,G,S,D) with the seq dim sharded over ``axis_name`` in contiguous
    rank-order chunks; per step each rank moves only its (B,G,S/R,D) KV
    block instead of all-gathering O(S) K/V up front, and the rotation
    overlaps the local math (see :func:`_ring_attention_local`).  ``q_spec``
    / ``kv_spec`` default to seq-sharded-over-``axis_name`` with everything
    else replicated; pass the recipe's specs to keep batch dims sharded.

    Sequence lengths that do NOT divide the ring run as *ragged* seq shards
    (:func:`repro.models.sharding.ragged_seq_extents`): the sequence is
    zero-padded to R equal capacity chunks — the trailing ranks hold short
    (possibly empty) valid blocks — the padded key positions are masked out
    of every score, and the padded output rows are sliced off.  The wire
    still moves uniform capacity blocks, exactly like every ragged DistBag
    transfer.
    """
    from jax.sharding import PartitionSpec as P

    from .sharding import ragged_seq_extents

    R = mesh.shape[axis_name]
    S = q.shape[2]
    if k.shape[2] != S:
        raise ValueError(f"ring attention needs matching q/kv seq lens, got {S} vs {k.shape[2]}")
    valid_len = None
    if S % R:
        cap, _ = ragged_seq_extents(S, R)
        Sp = R * cap
        pad = [(0, 0), (0, 0), (0, Sp - S), (0, 0)]
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))
        valid_len = S
    if q_spec is None:
        q_spec = P(None, None, axis_name, None)
    if kv_spec is None:
        kv_spec = P(None, None, axis_name, None)
    q_spec = _fit_spec(q_spec, tuple(q.shape), mesh)
    kv_spec = _fit_spec(kv_spec, tuple(k.shape), mesh)

    def body(ql, kl, vl):
        return _ring_attention_local(ql, kl, vl, axis_name=axis_name,
                                     causal=causal, double_buffer=double_buffer,
                                     valid_len=valid_len, impl=impl, block=block)

    # check_vma=False: pallas_call has no varying-axes rule (harmless here —
    # every output is plainly seq-sharded like q)
    out = jax.shard_map(body, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
                        out_specs=q_spec, check_vma=False)(q, k, v)
    # ``slice_output=False`` hands the padded (B,H,R*cap,D) output back to the
    # caller so the pad slice can ride *through* the per-position output
    # projection and land terminal (nothing downstream), instead of sitting
    # between the ring and the projection where GSPMD reshards it with a
    # serialized all-gather (the carried-over boundary-reshard bug).
    return out[:, :, :S] if (valid_len is not None and slice_output) else out


def _ring_applicable(recipe, q, k) -> bool:
    """The sp ring runs when the recipe asks for it and the shapes ring: a
    >1-sized model axis.  Seq lengths that don't divide the ring are fine —
    they run as ragged shards (padded capacity chunks + masked scores)."""
    if recipe is None or not getattr(recipe, "sp_ring", False) or recipe.attn_mode != "sp":
        return False
    if "model" not in recipe.mesh.shape:
        return False
    R = recipe.mesh.shape["model"]
    S = q.shape[2]
    return R > 1 and S >= 1 and k.shape[2] == S and q.shape[1] % k.shape[1] == 0


def attention_decode(q, k_cache, v_cache, cache_len, *, q_start=None,
                     impl: str | None = None, block: int = 512):
    """q (B,H,S,D) new queries; caches (B,G,T,D); positions >= cache_len are
    masked.  ``q_start`` (B,) is each row's first query position: query
    ``j`` of row ``b`` sits at ``q_start[b] + j`` and sees cache slot ``t``
    iff ``t <= q_start[b] + j`` — the causal mask *within* a multi-token
    chunk (whole-prompt prefill) and the per-slot mask under continuous
    batching, where each batch row sits at its own position.  With S == 1
    and uniform starts this reduces to the classic single-token decode
    mask.

    Reading the whole cache is the roofline minimum for decode; softmax
    reductions over a sharded cache-seq dim become the distributed
    flash-decoding merge under GSPMD above whichever local impl ran.
    ``impl`` dispatch (see the module docstring's table): ``"pallas"`` /
    ``"interpret"`` run the flash-decoding Pallas kernel
    (:func:`repro.kernels.flash_decode.flash_decode_pallas`, KV-block grid +
    online softmax); ``"jnp"``/``"ref"`` (the non-TPU default) keep the
    dense jnp path below.
    """
    B, Hq, S, D = q.shape
    _, G, T, _ = k_cache.shape
    rep = Hq // G
    impl = impl or ("pallas" if jax.default_backend() == "tpu" else "jnp")
    if impl not in ("jnp", "ref"):
        return ops.flash_decode(q, k_cache, v_cache, cache_len,
                                q_start=q_start, impl=impl, bk=block)
    # the cache streams stay in their storage dtype (bf16); scores and the
    # p@v contraction accumulate in f32 — reading the cache IS the decode
    # roofline term, so it is never widened in HBM
    qg = q.reshape(B, G, rep, S, D)
    s = jnp.einsum("bgrqd,bgsd->bgrqs", qg, k_cache, preferred_element_type=jnp.float32)
    s = s * (D ** -0.5)
    # ring-buffer aware: once length exceeds the cache size (windowed cache),
    # every slot is valid
    valid = jnp.minimum(cache_len.reshape(B, 1, 1, 1, 1), T)
    mask = jnp.arange(T)[None, None, None, None, :] < valid
    if q_start is not None:
        q_pos = q_start.reshape(B, 1) + jnp.arange(S)[None, :]
        mask = mask & (
            jnp.arange(T)[None, None, None, None, :]
            <= q_pos.reshape(B, 1, 1, S, 1)
        )
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # the probabilities round to the cache dtype *before* the p@v
    # contraction
    p = p.astype(v_cache.dtype)
    o = jnp.einsum("bgrqs,bgsd->bgrqd", p, v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Hq, S, D).astype(q.dtype)


# ---------------------------------------------------------------- GQA op ----

class KVCache(NamedTuple):
    k: jax.Array  # (B, G, S, D)
    v: jax.Array  # (B, G, S, D)
    length: jax.Array  # (B,) int32


def gqa_attention(p, x, *, n_heads: int, n_kv: int, head_dim: int, rope_theta: float = 10000.0,
                  positions=None, cache: KVCache | None = None, causal: bool = True,
                  attn_impl: str | None = None, block: int = 512, attn_mixed: bool | None = None,
                  sp_ring_double_buffer: bool = True, new_counts=None, prefill: bool = False,
                  layer: tuple = (), slot=None):
    """x (B,S,m) -> (B,S,m).  ``cache`` switches to decode mode.

    Decode accepts multi-token chunks (S >= 1) and *per-row* state:
    ``positions`` may be (B,S) absolute positions (each slot rotates RoPE and
    masks causally at its own offset) and ``new_counts`` (B,) says how many
    of the chunk's S tokens are valid per row — the per-request extents of
    continuous batching.  Rows advance their cache length by their own count,
    and count-0 rows keep their cache bytes (:func:`write_positions`).
    ``cache.k``/``cache.v`` may be stacked over layers, with ``layer`` the
    leading indices of this layer: the new K/V are written into the stack in
    place and the returned cache holds the whole stack (see
    ``repro.models.lm.decode_step``); given ``slot``, the chunk's rows are
    the cache slots from ``slot`` on and ``cache.length`` holds theirs alone
    (:func:`write_positions`).  ``prefill=True`` marks a whole-prompt
    chunk whose active rows all start at position 0; under an ``sp_ring``
    recipe that chunk runs the ring-attention plan (sequence-parallel batched
    prefill) while the K/V writes fill the cache.

    Under an active ``sp_ring`` recipe the seq path runs
    :func:`ring_attention_seq` (double-buffered KV rotation over the
    ``model`` axis; ``sp_ring_double_buffer=False`` selects the blocking
    reference variant, bit-identical at f32)."""
    B, S, _ = x.shape
    q = shard_act(jnp.einsum("bsm,mhd->bhsd", x, p["wq"].astype(x.dtype)), "q")
    k = shard_act(jnp.einsum("bsm,mgd->bgsd", x, p["wk"].astype(x.dtype)), "kv")
    v = shard_act(jnp.einsum("bsm,mgd->bgsd", x, p["wv"].astype(x.dtype)), "kv")
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)[None, :, None, :]
        k = k + p["bk"].astype(x.dtype)[None, :, None, :]
        v = v + p["bv"].astype(x.dtype)[None, :, None, :]
    if positions is None:
        positions = jnp.arange(S)
    cos, sin = rope_angles(positions, head_dim, rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    recipe = current_recipe()
    if cache is not None:
        adv = S if new_counts is None else new_counts
        active = None if new_counts is None else new_counts > 0
        start = cache.length % cache.k.shape[-2]
        ks = write_positions(cache.k, k, layer, start, active, axis=1, slot=slot)
        vs = write_positions(cache.v, v, layer, start, active, axis=1, slot=slot)
        kc = shard_act(layer_view(ks, layer, slot, B), "cache_kv")
        vc = shard_act(layer_view(vs, layer, slot, B), "cache_kv")
        new_len = cache.length + adv
        new_cache = KVCache(ks, vs, new_len)
        if prefill and _ring_applicable(recipe, q, k):
            # whole-prompt prefill chunk: active rows start at position 0, so
            # the chunk's causal attention IS full attention over the prompt
            # — run the sequence-parallel ring plan on the fresh Q/K/V while
            # the writes above fill the cache for the decode steps to stream.
            o = ring_attention_seq(
                q, k, v, mesh=recipe.mesh, axis_name="model",
                q_spec=recipe.spec("q"), kv_spec=recipe.spec("kv"),
                causal=causal, double_buffer=sp_ring_double_buffer,
                slice_output=False, impl=attn_impl, block=block,
            )
            o = shard_act(o, "attn_out")
            out = jnp.einsum("bhsd,hdm->bsm", o, p["wo"].astype(x.dtype))
            # project on the padded seq (the einsum is per-position, so valid
            # rows are bitwise unchanged) and slice last: the ragged pad
            # slice is terminal instead of a mid-graph reshard.
            return shard_act(out, "hidden")[:, :S], new_cache
        # a decode chunk's positions are each row's start + arange(S)
        q_start = positions[:, 0] if getattr(positions, "ndim", 1) == 2 else None
        o = attention_decode(q, kc, vc, new_len, q_start=q_start,
                             impl=attn_impl, block=block)
        out = jnp.einsum("bhsd,hdm->bsm", o, p["wo"].astype(x.dtype))
        return shard_act(out, "hidden"), new_cache
    if _ring_applicable(recipe, q, k):
        o = ring_attention_seq(
            q, k, v, mesh=recipe.mesh, axis_name="model",
            q_spec=recipe.spec("q"), kv_spec=recipe.spec("kv"),
            causal=causal, double_buffer=sp_ring_double_buffer,
            slice_output=False, impl=attn_impl, block=block,
        )
        o = shard_act(o, "attn_out")
        out = jnp.einsum("bhsd,hdm->bsm", o, p["wo"].astype(x.dtype))
        # ragged boundary-reshard fix: the pad slice rides through the
        # per-position output projection and lands terminal — nothing
        # downstream consumes it, so GSPMD has no reshard to serialize.
        # (Dividing lengths return unpadded and the slice is a no-op.)
        return shard_act(out, "hidden")[:, :S], None
    o = shard_act(attention_seq(q, k, v, causal=causal, impl=attn_impl, block=block, mixed=attn_mixed), "attn_out")
    return shard_act(jnp.einsum("bhsd,hdm->bsm", o, p["wo"].astype(x.dtype)), "hidden"), None


def write_positions(stack, new, layer, start, active, *, axis: int, slot=None):
    """Write each row's S new positions into one layer of a cache leaf.

    ``stack`` is (*L, B, *row): the leaf stacked over layers, ``layer`` the
    leading indices of this layer (``()`` for a leaf of one layer).  ``new``
    is (R, *chunk), with the chunk's S positions on ``axis`` of a row.  Row
    ``b`` goes to cache slot ``b``, or to ``slot + b`` given ``slot`` (a
    traced int32 scalar: the rows are a run of the B slots).  It lands at
    ``start[b]`` on that axis: its own position, so slots at different
    lengths never clobber each other, and ``length % size`` gives a
    windowed cache its ring buffer.

    One ``dynamic_update_slice`` per row, unrolled over the static batch:
    XLA then updates a donated (or scan-carried) stack in place.  A vmapped
    update, a scatter or a scan over rows makes it copy the whole stack.
    Rows with ``active[b]`` False write back the S positions they already
    hold, read at the same start; both ops clamp a start that would run past
    the end alike, so such a row keeps its bytes even then.

    The held slice is pinned to the default layout.  Left free, the TPU
    compiler lays the whole scan-carried stack out to suit that small read
    (positions major to heads) and converts the donated cache into and out
    of that layout: two whole copies of it every step."""
    new = new.astype(stack.dtype)
    lead = len(layer)
    for b in range(new.shape[0]):
        at = [*layer, b if slot is None else slot + b] + [0] * (new.ndim - 1)
        at[lead + 1 + axis] = start[b]
        upd = new[b].reshape((1,) * (lead + 1) + new.shape[1:])
        if active is not None:
            held = jax.lax.dynamic_slice(stack, at, upd.shape)
            held = with_layout_constraint(held, Layout(tuple(range(held.ndim))))
            upd = jnp.where(active[b], upd, held)
        stack = jax.lax.dynamic_update_slice(stack, upd, at)
    return stack


def layer_view(stack, layer, slot=None, rows: int = 1):
    """The one layer ``layer`` (leading indices) of a stacked cache leaf;
    given ``slot``, only its ``rows`` slots from ``slot`` on."""
    for i in layer:
        stack = jax.lax.dynamic_index_in_dim(stack, i, keepdims=False)
    if slot is not None:
        stack = jax.lax.dynamic_slice_in_dim(stack, slot, rows)
    return stack


# ---------------------------------------------------------------- MLA op ----

class MLACache(NamedTuple):
    c: jax.Array  # (B, S, kv_rank) compressed latent
    kr: jax.Array  # (B, S, d_rope) shared rope key
    length: jax.Array


def _rms(x, w, eps=1e-6):
    v = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(v + eps)).astype(x.dtype) * w.astype(x.dtype)


def mla_attention(p, x, *, n_heads: int, d_nope: int, d_rope: int, d_v: int, rope_theta: float = 10000.0,
                  positions=None, cache: MLACache | None = None, attn_impl: str | None = None,
                  block: int = 512, attn_mixed: bool | None = None, new_counts=None,
                  prefill: bool = False, layer: tuple = (), slot=None):
    """Multi-head Latent Attention (MiniCPM3/DeepSeek-V2 style).

    Train/prefill: decompress per-head K/V and run flash attention.
    Decode: the *absorbed* form — scores against the compressed latent cache
    (the cache layout is (B,S,kv_rank)+(B,S,d_rope): 288 instead of
    2*40*96 = 7680 floats per token — MLA's reason to exist).

    Like :func:`gqa_attention`, decode accepts multi-token chunks with
    per-row (B,S) ``positions`` and (B,) ``new_counts``: the absorbed scores
    mask cache slot ``t`` to ``t <= positions[b, j]``, which makes a
    whole-prompt chunk exact causal prefill straight through the latent
    cache, so ``prefill`` needs no separate branch here (accepted for API
    symmetry).  ``layer`` indexes a cache stacked over layers and ``slot``
    places the rows in it, as there."""
    B, S, _ = x.shape
    cq = _rms(jnp.einsum("bsm,mq->bsq", x, p["wdq"].astype(x.dtype)), p["q_norm"])
    q = jnp.einsum("bsq,qhc->bhsc", cq, p["wuq"].astype(x.dtype))
    q_nope, q_rope = q[..., :d_nope], q[..., d_nope:]
    c = _rms(jnp.einsum("bsm,mk->bsk", x, p["wdkv"].astype(x.dtype)), p["kv_norm"])
    kr = jnp.einsum("bsm,mr->bsr", x, p["wkr"].astype(x.dtype))
    if positions is None:
        positions = jnp.arange(S)
    cos, sin = rope_angles(positions, d_rope, rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    kr = apply_rope(kr[:, None], cos, sin)[:, 0]  # (B,S,r)

    if cache is None:
        k_nope = jnp.einsum("bsk,khn->bhsn", c, p["wuk"].astype(x.dtype))
        v = jnp.einsum("bsk,khw->bhsw", c, p["wuv"].astype(x.dtype))
        k = jnp.concatenate([k_nope, jnp.broadcast_to(kr[:, None], (B, n_heads, S, d_rope))], axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        # v keeps its own head dim (no padding) — both attention impls
        # support dv != dq, so MLA pays for exactly d_v value bytes
        o = attention_seq(qq, k, v, causal=True, impl=attn_impl, block=block, mixed=attn_mixed)
        return jnp.einsum("bhsw,hwm->bsm", o, p["wo"].astype(x.dtype)), None

    # ---- absorbed decode ----
    adv = S if new_counts is None else new_counts
    active = None if new_counts is None else new_counts > 0
    start = cache.length % cache.c.shape[-2]
    cs = write_positions(cache.c, c, layer, start, active, axis=0, slot=slot)
    krs = write_positions(cache.kr, kr, layer, start, active, axis=0, slot=slot)
    cc = shard_act(layer_view(cs, layer, slot, B), "cache_mla")
    krc = shard_act(layer_view(krs, layer, slot, B), "cache_mla")
    new_cache = MLACache(cs, krs, cache.length + adv)
    # absorb W_uk into q: q_abs (B,H,1,k_rank)
    q_abs = jnp.einsum("bhsn,khn->bhsk", q_nope, p["wuk"].astype(x.dtype))
    scale = (d_nope + d_rope) ** -0.5
    s = (
        jnp.einsum("bhsk,btk->bhst", q_abs.astype(jnp.float32), cc.astype(jnp.float32))
        + jnp.einsum("bhsr,btr->bhst", q_rope.astype(jnp.float32), krc.astype(jnp.float32))
    ) * scale
    T = cc.shape[1]
    mask = jnp.arange(T)[None, None, None, :] < (cache.length + adv).reshape(B, 1, 1, 1)
    if getattr(positions, "ndim", 1) == 2:
        # per-row chunk causality: slot t visible to query j iff t <= pos[b,j]
        mask = mask & (
            jnp.arange(T)[None, None, None, :] <= positions.reshape(B, 1, S, 1)
        )
    s = jnp.where(mask, s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhst,btk->bhsk", pr, cc.astype(jnp.float32)).astype(x.dtype)
    o = jnp.einsum("bhsk,khw->bhsw", o_lat, p["wuv"].astype(x.dtype))
    return jnp.einsum("bhsw,hwm->bsm", o, p["wo"].astype(x.dtype)), new_cache


def _pad_last(v, d: int):
    if v.shape[-1] == d:
        return v
    pad = [(0, 0)] * (v.ndim - 1) + [(0, d - v.shape[-1])]
    return jnp.pad(v, pad)


# ------------------------------------------------------- cross-attention ----

def cross_attn_specs(d_model: int, n_heads: int, n_kv: int, head_dim: int, d_enc: int, dtype=jnp.float32):
    return {
        "wq": pspec(("m", d_model), ("h", n_heads), ("d", head_dim), dtype=dtype, fan_in=("m",)),
        "wk": pspec(("x", d_enc), ("g", n_kv), ("d", head_dim), dtype=dtype, fan_in=("x",)),
        "wv": pspec(("x", d_enc), ("g", n_kv), ("d", head_dim), dtype=dtype, fan_in=("x",)),
        "wo": pspec(("h", n_heads), ("d", head_dim), ("m", d_model), dtype=dtype, fan_in=("h", "d")),
        "q_norm": pspec(("d", head_dim), dtype=dtype, init="ones"),
        "k_norm": pspec(("d", head_dim), dtype=dtype, init="ones"),
    }


def cross_attention(p, x, enc, *, n_heads: int, n_kv: int, head_dim: int, attn_impl: str | None = None,
                    block: int = 512, attn_mixed: bool | None = None):
    """x (B,S,m) attends to encoder states enc (B,T,d_enc); non-causal."""
    q = jnp.einsum("bsm,mhd->bhsd", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("btx,xgd->bgtd", enc.astype(x.dtype), p["wk"].astype(x.dtype))
    v = jnp.einsum("btx,xgd->bgtd", enc.astype(x.dtype), p["wv"].astype(x.dtype))
    q = _rms(q, p["q_norm"])
    k = _rms(k, p["k_norm"])
    o = attention_seq(q, k, v, causal=False, impl=attn_impl, block=block, mixed=attn_mixed)
    return jnp.einsum("bhsd,hdm->bsm", o, p["wo"].astype(x.dtype))

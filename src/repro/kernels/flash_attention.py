"""Blockwise (flash) causal attention Pallas kernels — single-shot and
carry-state.

The 32k-token prefill shapes make materialized (S, S) score matrices
infeasible (32k^2 f32 = 4 GiB per head), so blockwise attention with an
online softmax is *required* for the assigned shapes, not an optimization.

TPU adaptation: the grid is (batch, q_heads, q_blocks, kv_blocks) with the KV
block index innermost, so each program sees one (bq, d) query tile and one
(bk, d) KV tile — both streamed HBM->VMEM by the BlockSpec machinery — and
carries the online-softmax state (o, m, l) in VMEM scratch across the kv
iteration.  GQA is handled in the K/V BlockSpec ``index_map`` (query head h
reads KV head ``h // group``) — zero-copy head sharing, the BlockSpec
analogue of the paper's layout-absorbed transfers.

Two entry points share one kernel body (identical arithmetic, so chaining
the carry form over KV chunks reproduces the single-shot form *bitwise*):

* :func:`flash_attention_pallas` — whole-sequence attention, normalized
  output.  Sequence lengths that do not divide the block sizes (or are
  smaller than a block) are padded to block multiples and the padded key
  positions masked inside the kernel, so ragged seq shards
  (``ragged_seq_extents``) use the kernel directly.
* :func:`flash_attention_carry_pallas` — ONE ring step of the
  sequence-parallel attention ring: attention of the resident Q chunk
  against the currently held KV block, threading the running
  ``(acc, m, l)`` online-softmax state through the call instead of
  re-merging in jnp.  The per-step causal offset (``q_offset`` /
  ``k_offset`` — traced, from ``axis_index``) rides in via TPU scalar
  prefetch; ragged padded-key masking uses the static global ``valid_len``.

VMEM budget per program: q (bq, d) + K/V (bk, d) each + acc (bq, d) f32 +
m/l (bq, 128) f32: with bq=bk=512, d=128 that is < 2 MiB << 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas", "flash_attention_carry_pallas"]

NEG_INF = -1e30


def _flash_kernel(
    off_ref, q_ref, k_ref, v_ref, *refs,
    bq: int, bk: int, nkv: int, scale: float, causal: bool,
    kv_stop: int | None, kv_local_stop: int | None,
    has_carry: bool, emit_state: bool,
):
    """Shared body.  ``refs`` is, in order:

    ``[ci_acc, ci_m, ci_l,]`` (when ``has_carry``)
    ``o_acc, o_m, o_l`` (when ``emit_state``) else ``o_out``
    ``acc_sc, m_sc, l_sc`` (VMEM scratch)

    ``off_ref`` holds the (possibly traced) global ``[q_offset, k_offset]``;
    ``kv_stop`` masks *global* key positions ``>= kv_stop`` (the ragged ring
    shard bound), ``kv_local_stop`` masks *local* positions ``>= stop`` (the
    pad-to-block-multiple bound of this call's own KV buffer).
    """
    if has_carry:
        ci_acc, ci_m, ci_l, *refs = refs
    if emit_state:
        o_acc, o_m, o_l, acc_ref, m_ref, l_ref = refs
    else:
        o_out, acc_ref, m_ref, l_ref = refs
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    q_off = off_ref[0]
    k_off = off_ref[1]

    @pl.when(kj == 0)
    def _init():
        if has_carry:
            acc_ref[...] = ci_acc[0, 0].astype(jnp.float32)
            m_ref[...] = ci_m[0, 0]
            l_ref[...] = ci_l[0, 0]
        else:
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    # causal: a block wholly above the diagonal contributes nothing — skip.
    # (With traced offsets this is a predicated no-op rather than a static
    # skip; the predicate is the same, so the two forms stay bitwise equal.)
    diag_ok = (k_off + kj * bk < q_off + (qi + 1) * bq) if causal else kj >= 0

    @pl.when(diag_ok)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
        mask = None
        k_loc = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        k_pos = k_off + k_loc
        if causal:
            q_pos = q_off + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask = q_pos >= k_pos
        if kv_stop is not None:
            m_ = k_pos < kv_stop
            mask = m_ if mask is None else mask & m_
        if kv_local_stop is not None:
            m_ = k_loc < kv_local_stop
            mask = m_ if mask is None else mask & m_
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(kj == nkv - 1)
    def _store():
        if emit_state:
            o_acc[0, 0] = acc_ref[...]
            o_m[0, 0] = m_ref[...]
            o_l[0, 0] = l_ref[...]
        else:
            l = l_ref[:, 0]
            l = jnp.where(l == 0.0, 1.0, l)  # guard fully-masked rows
            o_out[0, 0] = (acc_ref[...] / l[:, None]).astype(o_out.dtype)


def _ceil_to(n: int, b: int) -> int:
    return -(-n // b) * b


def _pad_dim(x, axis: int, to: int):
    if x.shape[axis] == to:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, to - x.shape[axis])
    return jnp.pad(x, pad)


def _specs(bq: int, bk: int, D: int, Dv: int, group: int):
    """BlockSpecs shared by both entry points (index maps take the
    scalar-prefetch ref as a trailing arg and ignore it).  The ``m``/``l``
    carry travels lane-broadcast as (B, Hq, Sq, 128), the layout of its VMEM
    scratch, so its blocks are whole (bq, 128) tiles."""
    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j, off: (b, h, i, 0))
    k_spec = pl.BlockSpec((1, 1, bk, D),
                          lambda b, h, i, j, off, group=group: (b, h // group, j, 0))
    v_spec = pl.BlockSpec((1, 1, bk, Dv),
                          lambda b, h, i, j, off, group=group: (b, h // group, j, 0))
    acc_spec = pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, j, off: (b, h, i, 0))
    ml_spec = pl.BlockSpec((1, 1, bq, 128), lambda b, h, i, j, off: (b, h, i, 0))
    return q_spec, k_spec, v_spec, acc_spec, ml_spec


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "interpret", "scale")
)
def flash_attention_pallas(
    q,  # (B, Hq, Sq, D)
    k,  # (B, Hkv, Skv, D)
    v,  # (B, Hkv, Skv, D)
    *,
    causal: bool = True,
    scale: float | None = None,
    bq: int = 512,
    bk: int = 512,
    interpret: bool = False,
):
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    Dv = v.shape[-1]
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    group = Hq // Hkv
    scale = float(scale if scale is not None else D ** -0.5)
    bq_ = min(bq, Sq)
    bk_ = min(bk, Skv)
    # ragged seq handling: pad to block multiples, mask padded keys in-kernel
    # (padded q rows compute garbage and are sliced off below)
    Sq_p = _ceil_to(Sq, bq_)
    Skv_p = _ceil_to(Skv, bk_)
    q = _pad_dim(q, 2, Sq_p)
    k = _pad_dim(k, 2, Skv_p)
    v = _pad_dim(v, 2, Skv_p)
    nkv = Skv_p // bk_

    kernel = functools.partial(
        _flash_kernel, bq=bq_, bk=bk_, nkv=nkv, scale=scale, causal=causal,
        kv_stop=None, kv_local_stop=(Skv if Skv_p != Skv else None),
        has_carry=False, emit_state=False,
    )
    q_spec, k_spec, v_spec, acc_spec, _ = _specs(bq_, bk_, D, Dv, group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hq, Sq_p // bq_, nkv),
        in_specs=[q_spec, k_spec, v_spec],
        out_specs=acc_spec,
        scratch_shapes=[
            pltpu.VMEM((bq_, Dv), jnp.float32),
            pltpu.VMEM((bq_, 128), jnp.float32),
            pltpu.VMEM((bq_, 128), jnp.float32),
        ],
    )
    offs = jnp.zeros((2,), jnp.int32)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq_p, Dv), q.dtype),
        interpret=interpret,
        name="flash_attention_pallas",
    )(offs, q, k, v)
    return out[:, :, :Sq] if Sq_p != Sq else out


@functools.partial(
    jax.jit,
    static_argnames=("causal", "bq", "bk", "interpret", "scale", "valid_len"),
)
def flash_attention_carry_pallas(
    q,  # (B, Hq, Sq, D) — the resident query chunk
    k,  # (B, Hkv, Skv, D) — the currently held KV block
    v,  # (B, Hkv, Skv, Dv)
    carry=None,  # (acc (B,Hq,Sq,Dv) f32, m (B,Hq,Sq) f32, l (B,Hq,Sq) f32)
    *,
    q_offset=0,  # global position of q[..., 0, :] (traced ok)
    k_offset=0,  # global position of k[..., 0, :] (traced ok)
    valid_len: int | None = None,  # global keys >= valid_len are padding
    causal: bool = True,
    scale: float | None = None,
    bq: int = 512,
    bk: int = 512,
    interpret: bool = False,
):
    """One flash step against a held KV block, carrying ``(acc, m, l)``.

    Returns the updated *unnormalized* state; the caller normalizes
    (``acc / l``) after the last step.  The arithmetic is the single-shot
    kernel's, so chaining R calls over the R KV chunks of a sequence (in
    block order) reproduces :func:`flash_attention_pallas` bitwise at f32.
    Offsets may be traced (``jax.lax.axis_index`` inside ``shard_map``) —
    they enter via scalar prefetch and only feed the in-kernel masks.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    Dv = v.shape[-1]
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    group = Hq // Hkv
    scale = float(scale if scale is not None else D ** -0.5)
    bq_ = min(bq, Sq)
    bk_ = min(bk, Skv)
    Sq_p = _ceil_to(Sq, bq_)
    Skv_p = _ceil_to(Skv, bk_)
    if carry is None:
        acc = jnp.zeros((B, Hq, Sq, Dv), jnp.float32)
        m = jnp.full((B, Hq, Sq), NEG_INF, jnp.float32)
        l = jnp.zeros((B, Hq, Sq), jnp.float32)
    else:
        acc, m, l = carry
    # pad q rows and their carry state to the block multiple; padded rows
    # keep the (0, -inf, 0) init so the chain stays consistent across steps
    q = _pad_dim(q, 2, Sq_p)
    acc = _pad_dim(acc.astype(jnp.float32), 2, Sq_p)
    m = _pad_dim(m.astype(jnp.float32), 2, Sq_p)
    if Sq_p != Sq:
        pad_rows = jnp.arange(Sq_p) >= Sq
        m = jnp.where(pad_rows[None, None], NEG_INF, m)
    l = _pad_dim(l.astype(jnp.float32), 2, Sq_p)
    m = jnp.broadcast_to(m[..., None], m.shape + (128,))
    l = jnp.broadcast_to(l[..., None], l.shape + (128,))
    k = _pad_dim(k, 2, Skv_p)
    v = _pad_dim(v, 2, Skv_p)
    nkv = Skv_p // bk_

    kernel = functools.partial(
        _flash_kernel, bq=bq_, bk=bk_, nkv=nkv, scale=scale, causal=causal,
        kv_stop=valid_len, kv_local_stop=(Skv if Skv_p != Skv else None),
        has_carry=True, emit_state=True,
    )
    q_spec, k_spec, v_spec, acc_spec, ml_spec = _specs(bq_, bk_, D, Dv, group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hq, Sq_p // bq_, nkv),
        in_specs=[q_spec, k_spec, v_spec, acc_spec, ml_spec, ml_spec],
        out_specs=[acc_spec, ml_spec, ml_spec],
        scratch_shapes=[
            pltpu.VMEM((bq_, Dv), jnp.float32),
            pltpu.VMEM((bq_, 128), jnp.float32),
            pltpu.VMEM((bq_, 128), jnp.float32),
        ],
    )
    offs = jnp.stack([
        jnp.asarray(q_offset, jnp.int32), jnp.asarray(k_offset, jnp.int32)
    ])
    acc_o, m_o, l_o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sq_p, Dv), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, Sq_p, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, Sq_p, 128), jnp.float32),
        ],
        # flat operands: offs, q, k, v, acc, m, l — carry updates in place
        input_output_aliases={4: 0, 5: 1, 6: 2},
        interpret=interpret,
        name="flash_attention_carry_pallas",
    )(offs, q, k, v, acc, m, l)
    m_o, l_o = m_o[..., 0], l_o[..., 0]
    if Sq_p != Sq:
        acc_o, m_o, l_o = acc_o[:, :, :Sq], m_o[:, :, :Sq], l_o[:, :, :Sq]
    return acc_o, m_o, l_o

"""Flash-decoding Pallas kernel: serving attention over a length-tracked cache.

Decode reads the whole KV cache to score one (or a few) new tokens — the
roofline term is the cache stream.  The grid is (batch, kv-group, q-block,
kv-block) with the KV block innermost: each program streams one (bk, d) K/V
tile HBM->VMEM and folds it into an online-softmax state ``(acc, m, l)``
held in VMEM scratch across the KV iteration, and the last KV step writes
the normalized output.  Blocks past a row's valid length, or wholly after
the block's last query, skip their math.

Masking matches :func:`repro.models.attention.attention_decode`: cache
positions ``>= min(cache_len, T)`` are invalid (ring-buffer aware), and query
``j`` of row ``b`` sits at position ``q_start[b] + j`` and sees cache slot
``t`` iff ``t <= q_start[b] + j`` — the continuous-batching per-row mask.
Both per-row scalars ride in through TPU scalar prefetch (SMEM), so no block
of the kernel is a sub-tile slice of a per-row vector.  The probabilities
round to the cache dtype before the p@v contraction, as on the jnp
path.

GQA is absorbed in the grid: one program per (batch, kv-group) reads each
K/V tile once for all ``rep = Hq // G`` query heads of the group.  A
single-token step stacks the ``rep`` heads into the rows of one (rep, d)
tile (every row sits at the same position); a multi-token chunk (prefill)
blocks its query rows by ``bq`` and loops over the ``rep`` heads inside the
program, so the score tile stays (bq, bk) however long the chunk is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_decode_pallas"]

NEG_INF = -1e30


def _decode_kernel(len_ref, start_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref,
                   *, heads: int, rows: int, bk: int, nkv: int, T: int,
                   row_step: int, scale: float):
    """``q_ref`` block (1, 1, heads, rows, d); rows of block ``qi`` sit at
    positions ``q_start + qi * rows + row_step * row``."""
    b = pl.program_id(0)
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = jnp.minimum(len_ref[b], T)
    q_first = start_ref[b] + qi * rows
    q_last = q_first + row_step * (rows - 1)
    k_first = kj * bk

    @pl.when((k_first < valid) & (k_first <= q_last))
    def _compute():
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0]
        k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
        q_pos = q_first + row_step * jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0)
        mask = (k_pos < valid) & (k_pos <= q_pos)
        for h in range(heads):
            q = q_ref[0, 0, h].astype(jnp.float32) * scale  # (rows, d)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (rows, bk)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[h][:, 0]
            l_prev = l_ref[h][:, 0]
            m_new = jnp.maximum(m_prev, s.max(axis=1))
            # masked entries contribute exactly 0, so a row that has seen no
            # valid key yet keeps l == 0 (and ends as a zero output)
            p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = jnp.broadcast_to((l_prev * alpha + p.sum(axis=1))[:, None],
                                        l_ref.shape[1:])
            m_ref[h] = jnp.broadcast_to(m_new[:, None], m_ref.shape[1:])
            # probabilities round to the cache dtype before the contraction,
            # like the jnp decode path (there: normalized first; here the
            # normalizer is applied once after the last KV block)
            acc_ref[h] = acc_ref[h] * alpha[:, None] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(kj == nkv - 1)
    def _store():
        for h in range(heads):
            l = l_ref[h][:, 0]
            l = jnp.where(l == 0.0, 1.0, l)  # rows with no visible key
            o_ref[0, 0, h] = (acc_ref[h] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "interpret", "scale"))
def flash_decode_pallas(
    q,  # (B, Hq, S, D) new queries
    k_cache,  # (B, G, T, D)
    v_cache,  # (B, G, T, Dv)
    cache_len,  # (B,) int32
    *,
    q_start=None,  # (B,) int32 position of each row's query 0, or None
    scale: float | None = None,
    bq: int = 512,
    bk: int = 512,
    interpret: bool = False,
):
    """Flash-decoding attention over the cache; returns (B, Hq, S, Dv) in
    q.dtype.  Query ``j`` of row ``b`` sits at ``q_start[b] + j``; with
    ``q_start=None`` there is no intra-chunk mask (only ``cache_len``)."""
    B, Hq, S, D = q.shape
    _, G, T, _ = k_cache.shape
    Dv = v_cache.shape[-1]
    if Hq % G:
        raise ValueError(f"Hq={Hq} not a multiple of G={G}")
    rep = Hq // G
    scale = float(scale if scale is not None else D ** -0.5)
    bk_ = min(bk, T)
    T_p = -(-T // bk_) * bk_
    if T_p != T:
        pad = [(0, 0), (0, 0), (0, T_p - T), (0, 0)]
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)
    nkv = T_p // bk_
    if S == 1:
        # one token per row: the group's heads are the rows of one tile
        heads, rows, row_step, S_p = 1, rep, 0, 1
        qg = q.reshape(B, G, 1, rep, D)
    else:
        heads, rows, row_step = rep, min(bq, S), 1
        S_p = -(-S // rows) * rows
        qg = q.reshape(B, G, rep, S, D)
        if S_p != S:  # padded query rows compute garbage and are sliced off
            qg = jnp.pad(qg, [(0, 0)] * 3 + [(0, S_p - S), (0, 0)])
    lens = cache_len.astype(jnp.int32).reshape(B)
    if q_start is None:
        # any start >= T-1 makes `t <= q_pos` vacuous
        starts = jnp.full((B,), T, jnp.int32)
    else:
        starts = q_start.astype(jnp.int32).reshape(B)

    kernel = functools.partial(
        _decode_kernel, heads=heads, rows=rows, bk=bk_, nkv=nkv, T=T,
        row_step=row_step, scale=scale,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, G, qg.shape[3] // rows, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, heads, rows, D), lambda b, g, i, j, *_: (b, g, 0, i, 0)),
            pl.BlockSpec((1, 1, bk_, D), lambda b, g, i, j, *_: (b, g, j, 0)),
            pl.BlockSpec((1, 1, bk_, Dv), lambda b, g, i, j, *_: (b, g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, heads, rows, Dv),
                               lambda b, g, i, j, *_: (b, g, 0, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((heads, rows, Dv), jnp.float32),
            pltpu.VMEM((heads, rows, 128), jnp.float32),
            pltpu.VMEM((heads, rows, 128), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape[:4] + (Dv,), q.dtype),
        interpret=interpret,
        name="flash_decode_pallas",
    )(lens, starts, qg, k_cache, v_cache)
    if S_p != S:
        o = o[:, :, :, :S]
    return o.reshape(B, Hq, S, Dv)

"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function mirrors the corresponding kernel's semantics exactly, written
with plain jnp ops so it runs anywhere and is obviously correct.  Kernel
tests sweep shapes/dtypes and ``assert_allclose`` against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "gemm_ref",
    "gemm_panel_ref",
    "attention_ref",
    "transpose_ref",
    "blockwise_attention_ref",
    "flash_carry_ref",
    "decode_attention_ref",
]


def gemm_ref(a, b, acc=None, *, majors: str = "I/I/K", out_dtype=None):
    """Reference for :func:`repro.kernels.gemm.gemm_pallas` (same buffer
    conventions: majors = C/A/B major dims; ``acc`` is a previous C buffer in
    output orientation, added in f32)."""
    c_major, a_major, b_major = majors.upper().split("/")
    al = a.T if a_major == "K" else a  # -> logical (i, k)
    bl = b.T if b_major == "J" else b  # -> logical (k, j)
    c = jnp.dot(
        al.astype(jnp.float32), bl.astype(jnp.float32), preferred_element_type=jnp.float32
    )
    if c_major == "J":
        c = c.T
    if acc is not None:
        c = c + acc.astype(jnp.float32)
    return c.astype(out_dtype or a.dtype)


def gemm_panel_ref(a, b, panel, jb, *, majors: str = "I/I/K"):
    """Reference for :func:`repro.kernels.gemm.gemm_panel_pallas`: accumulate
    A @ B into j-block ``jb`` of the partial panel (``jb`` may be traced),
    leaving the other blocks untouched."""
    c_major, a_major, b_major = majors.upper().split("/")
    al = a.T if a_major == "K" else a  # -> logical (i, k)
    bl = b.T if b_major == "J" else b  # -> logical (k, j)
    N = bl.shape[1]
    jb = jnp.asarray(jb, jnp.int32)
    c = jnp.dot(
        al.astype(jnp.float32), bl.astype(jnp.float32), preferred_element_type=jnp.float32
    )
    if c_major == "J":
        start = (jb * N, jnp.zeros_like(jb))
        c = c.T
    else:
        start = (jnp.zeros_like(jb), jb * N)
    cur = jax.lax.dynamic_slice(panel, start, c.shape)
    blk = (c + cur.astype(jnp.float32)).astype(panel.dtype)
    return jax.lax.dynamic_update_slice(panel, blk, start)


def attention_ref(q, k, v, *, causal: bool = True, scale: float | None = None):
    """Dense softmax attention with GQA head sharing; q (B,Hq,S,D), kv (B,Hkv,S,D)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Skv), bool), k=Skv - Sq)
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def blockwise_attention_ref(q, k, v, *, causal: bool = True, scale: float | None = None, block: int = 128, mixed: bool | None = None):
    """Online-softmax blockwise attention in pure jnp (lax.scan over KV
    blocks).  Numerically identical algorithm to the Pallas kernel; also the
    sub-quadratic attention used by the model stack on the CPU dry-run path.

    Mixed precision (bf16 inputs only): the score dot consumes bf16 operands
    with an f32 result, and the probability tile is cast back to bf16 for the
    p@v dot while the (o, m, l) accumulators stay f32 — the flash-attention
    convention.  This halves the dominant HBM streams (k/v tiles in, p tile
    between the two dots) with accumulation precision unchanged.  f32 inputs
    take the all-f32 path (the kernels' bitwise oracle)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    nb = Skv // block
    assert Skv % block == 0, (Skv, block)
    if mixed is None:
        mixed = q.dtype == jnp.bfloat16
    mixed = bool(mixed) and q.dtype == jnp.bfloat16
    qf = q if mixed else q.astype(jnp.float32) * scale

    def body(carry, j):
        o, m, l = carry
        kb = jax.lax.dynamic_slice_in_dim(k, j * block, block, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(v, j * block, block, axis=2)
        if not mixed:
            kb = kb.astype(jnp.float32)
            vb = vb.astype(jnp.float32)
        kb = jnp.repeat(kb, group, axis=1)
        vb = jnp.repeat(vb, group, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb, preferred_element_type=jnp.float32)
        if mixed:
            s = s * scale
        if causal:
            q_pos = (Skv - Sq) + jnp.arange(Sq)[:, None]
            k_pos = j * block + jnp.arange(block)[None, :]
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        pv = p.astype(jnp.bfloat16) if mixed else p
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", pv, vb, preferred_element_type=jnp.float32
        )
        return (o_new, m_new, l_new), None

    o0 = jnp.zeros((B, Hq, Sq, v.shape[-1]), jnp.float32)  # Dv may differ (MLA)
    m0 = jnp.full((B, Hq, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hq, Sq), jnp.float32)
    (o, m, l), _ = jax.lax.scan(body, (o0, m0, l0), jnp.arange(nb))
    l = jnp.where(l == 0.0, 1.0, l)
    return (o / l[..., None]).astype(q.dtype)


def flash_carry_ref(q, k, v, carry=None, *, q_offset=0, k_offset=0,
                    valid_len: int | None = None, causal: bool = True,
                    scale: float | None = None):
    """Reference for one carry-state flash step
    (:func:`repro.kernels.flash_attention.flash_attention_carry_pallas`):
    online-softmax merge of the whole held KV block against the resident Q
    chunk, threading unnormalized ``(acc, m, l)``.  Same math as the jnp
    ring-step merge in ``models.attention._ring_attention_local``, in the
    kernel's (B, Hq, S, ·) head layout."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    Dv = v.shape[-1]
    group = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    if carry is None:
        acc = jnp.zeros((B, Hq, Sq, Dv), jnp.float32)
        m = jnp.full((B, Hq, Sq), -1e30, jnp.float32)
        l = jnp.zeros((B, Hq, Sq), jnp.float32)
    else:
        acc, m, l = carry
    kb = jnp.repeat(k, group, axis=1).astype(jnp.float32)
    vb = jnp.repeat(v, group, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kb,
                   preferred_element_type=jnp.float32) * scale
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = k_offset + jnp.arange(Skv)
    mask = None
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
    if valid_len is not None:
        pad = k_pos[None, :] < valid_len
        mask = pad if mask is None else mask & pad
    if mask is not None:
        s = jnp.where(mask[None, None], s, -1e30)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + p.sum(axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, vb, preferred_element_type=jnp.float32)
    return acc_new, m_new, l_new


def decode_attention_ref(q, k_cache, v_cache, cache_len, *, q_start=None,
                         scale: float | None = None):
    """Reference for :func:`repro.kernels.flash_decode.flash_decode_pallas`:
    dense decode attention over the cache with ring-buffer-aware length
    masking and the per-row chunk-causality mask (query ``j`` of row ``b``
    sits at ``q_start[b] + j``).  (The model-facing jnp
    path in ``models.attention.attention_decode`` additionally rounds the
    normalized probabilities to the cache dtype; this oracle keeps
    everything f32.)"""
    B, Hq, S, D = q.shape
    _, G, T, _ = k_cache.shape
    rep = Hq // G
    scale = scale if scale is not None else D ** -0.5
    qg = q.reshape(B, G, rep, S, D)
    s = jnp.einsum("bgrqd,bgsd->bgrqs", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
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
    o = jnp.einsum("bgrqs,bgsd->bgrqd", p, v_cache.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Hq, S, v_cache.shape[-1]).astype(q.dtype)


def transpose_ref(x):
    return jnp.swapaxes(x, -1, -2)

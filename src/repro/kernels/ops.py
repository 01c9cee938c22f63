"""Jit'd public wrappers around the Pallas kernels.

Each op picks an implementation:
  * ``impl="pallas"``     — compiled Pallas (the TPU target),
  * ``impl="interpret"``  — Pallas interpret mode (CPU-correctness runs),
  * ``impl="ref"``        — the pure-jnp oracle (also the dry-run model path
                            on the CPU backend, where Mosaic cannot lower).

``default_impl()`` resolves from the backend so model code never branches:
TPU -> pallas, everything else -> ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ref as _ref
from .gemm import gemm_pallas, gemm_panel_pallas
from .flash_attention import flash_attention_pallas, flash_attention_carry_pallas
from .flash_decode import flash_decode_pallas
from .relayout import transpose_tiled_pallas

__all__ = [
    "default_impl",
    "gemm",
    "gemm_panel",
    "flash_attention",
    "flash_attention_carry",
    "flash_decode",
    "transpose_tiled",
]


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _resolve(impl: str | None) -> str:
    impl = impl or default_impl()
    if impl == "interpret" and jax.default_backend() == "tpu":
        # interpret mode is the CPU oracle of the kernels; on the chip it
        # would run the kernel body as plain XLA ops and hide the kernel
        raise ValueError("impl='interpret' is not allowed on a TPU backend")
    return impl


def gemm(a, b, acc=None, *, majors: str = "I/I/K", impl: str | None = None, **kw):
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.gemm_ref(a, b, acc, majors=majors, out_dtype=kw.get("out_dtype"))
    return gemm_pallas(a, b, acc, majors=majors, interpret=(impl == "interpret"), **kw)


def gemm_panel(a, b, panel, jb, *, majors: str = "I/I/K", impl: str | None = None, **kw):
    """Rotating-accumulator SUMMA inner step: panel[j-block jb] += A @ B."""
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.gemm_panel_ref(a, b, panel, jb, majors=majors)
    return gemm_panel_pallas(a, b, panel, jb, majors=majors, interpret=(impl == "interpret"), **kw)


def flash_attention(q, k, v, *, causal: bool = True, impl: str | None = None, mixed: bool | None = None, **kw):
    impl = _resolve(impl)
    if impl == "ref":
        block = kw.get("bk", 128)
        return _ref.blockwise_attention_ref(
            q, k, v, causal=causal, block=min(block, k.shape[2]), mixed=mixed
        )
    # the Pallas kernel is always mixed-precision internally (f32 VMEM acc)
    return flash_attention_pallas(q, k, v, causal=causal, interpret=(impl == "interpret"), **kw)


def _zero_offset_ct(x):
    """Zero cotangent for an offset operand: float0 for integer positions
    (the only differentiability-correct tangent type for int primals)."""
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.inexact):
        return jnp.zeros_like(x)
    return np.zeros(x.shape, jax.dtypes.float0)


@functools.lru_cache(maxsize=None)
def _carry_step_vjp(causal, scale, valid_len, bq, bk, interpret):
    """custom_vjp wrapper for one carry-state flash step, cached per static
    config (``valid_len``/``scale`` are static argnames of the kernel).

    fwd is the Pallas kernel; bwd recomputes through the jnp oracle
    (:func:`repro.kernels.ref.flash_carry_ref`) and pulls the cotangent
    back with ``jax.vjp`` — flash-style recompute-in-backward, so sp_ring
    *training* takes the kernel path forward without falling off it for
    lack of a transpose rule.  Offsets are operands (traced ``axis_index``
    values ride scalar prefetch) and get float0 cotangents."""
    kernel_kw = dict(causal=causal, scale=scale, valid_len=valid_len,
                     bq=bq, bk=bk, interpret=interpret)

    @jax.custom_vjp
    def step(q, k, v, carry, q_offset, k_offset):
        return flash_attention_carry_pallas(
            q, k, v, carry, q_offset=q_offset, k_offset=k_offset, **kernel_kw
        )

    def fwd(q, k, v, carry, q_offset, k_offset):
        out = step(q, k, v, carry, q_offset, k_offset)
        return out, (q, k, v, carry, q_offset, k_offset)

    def bwd(res, ct):
        q, k, v, carry, q_offset, k_offset = res

        def oracle(q, k, v, carry):
            return _ref.flash_carry_ref(
                q, k, v, carry, q_offset=q_offset, k_offset=k_offset,
                valid_len=valid_len, causal=causal, scale=scale,
            )

        _, pull = jax.vjp(oracle, q, k, v, carry)
        dq, dk, dv, dcarry = pull(ct)
        return (dq, dk, dv, dcarry,
                _zero_offset_ct(q_offset), _zero_offset_ct(k_offset))

    step.defvjp(fwd, bwd)
    return step


def flash_attention_carry(q, k, v, carry=None, *, q_offset=0, k_offset=0,
                          valid_len=None, causal: bool = True,
                          impl: str | None = None, **kw):
    """One carry-state flash step (a sp_ring ring step): attention of the
    resident Q chunk against the held KV block, threading unnormalized
    ``(acc, m, l)``.  Offsets may be traced (``axis_index`` inside
    ``shard_map``) — the Pallas path routes them through scalar prefetch.
    The Pallas path carries a custom VJP (jnp-oracle recompute backward),
    so it is differentiable for sp_ring training."""
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.flash_carry_ref(
            q, k, v, carry, q_offset=q_offset, k_offset=k_offset,
            valid_len=valid_len, causal=causal, scale=kw.get("scale"),
        )
    step = _carry_step_vjp(
        causal, kw.get("scale"), valid_len, kw.get("bq", 512),
        kw.get("bk", 512), impl == "interpret",
    )
    return step(q, k, v, carry, q_offset, k_offset)


def flash_decode(q, k_cache, v_cache, cache_len, *, q_start=None,
                 impl: str | None = None, **kw):
    """Flash-decoding attention over the cache.  ``q_start`` (B,) is the
    position of each row's first query; query ``j`` sits at
    ``q_start + j``."""
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.decode_attention_ref(
            q, k_cache, v_cache, cache_len, q_start=q_start,
            scale=kw.get("scale"),
        )
    return flash_decode_pallas(
        q, k_cache, v_cache, cache_len, q_start=q_start,
        interpret=(impl == "interpret"), **kw,
    )


def transpose_tiled(x, *, impl: str | None = None, **kw):
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.transpose_ref(x)
    return transpose_tiled_pallas(x, interpret=(impl == "interpret"), **kw)

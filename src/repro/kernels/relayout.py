"""Tiled relayout (transpose) Pallas kernel — the transfer-transform hot spot.

The paper's central mechanism is a layout transformation performed *inside*
a transfer (MPI datatypes).  On TPU the equivalent data movement is a tiled
HBM->VMEM->HBM transpose; XLA emits one automatically when our
``RelayoutPlan`` contains a permutation, and this kernel is the hand-tiled
version used to (a) control VMEM tile shapes explicitly and (b) serve as the
per-shard transform in layout-agnostic collectives.

Handles the canonical plan shape produced by ``relayout_plan``: a batched
last-two-axes transpose ``(..., M, N) -> (..., N, M)``.  Arbitrary plans
decompose into at most two such passes (outer permutation is free through
BlockSpec index maps).

VMEM: one (bm, bn) input tile + one (bn, bm) output tile; defaults 256x256
f32 = 512 KiB total.  Tiles are multiples of (8, 128) for efficient VREG
shuffles on the transpose unit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["transpose_tiled_pallas"]


def _transpose_kernel(x_ref, o_ref):
    o_ref[0] = x_ref[0].T


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def transpose_tiled_pallas(x, *, bm: int = 256, bn: int = 256, interpret: bool = False):
    """``(..., M, N) -> (..., N, M)`` with explicit VMEM tiling."""
    *lead, M, N = x.shape
    B = 1
    for s in lead:
        B *= s
    x3 = x.reshape(B, M, N)
    bm_, bn_ = min(bm, M), min(bn, N)
    if M % bm_ or N % bn_:
        raise ValueError(f"({M},{N}) must divide tile ({bm_},{bn_})")
    out = pl.pallas_call(
        _transpose_kernel,
        grid=(B, M // bm_, N // bn_),
        in_specs=[pl.BlockSpec((1, bm_, bn_), lambda b, i, j: (b, i, j))],
        out_specs=pl.BlockSpec((1, bn_, bm_), lambda b, i, j: (b, j, i)),
        out_shape=jax.ShapeDtypeStruct((B, N, M), x.dtype),
        interpret=interpret,
        name="transpose_tiled_pallas",
    )(x3)
    return out.reshape(*lead, N, M)

"""Layout-parametric tiled GEMM Pallas kernel (the paper's case study, §5).

The paper evaluates a distributed GEMM whose three matrices each use an
independently chosen major dimension (configurations ``C/A/B`` = ``I/I/J``
etc., Fig. 3).  On TPU we adapt the idea to the MXU: the kernel's BlockSpec
``index_map`` absorbs the operand orientation, so a column-major operand is
consumed *without any pre-transpose pass* — the layout transformation rides
along with the HBM->VMEM tile fetch, exactly like MPI datatypes performing
the transform inside the transfer.

Orientation encoding (matching the paper's x-axis labels):
  * A is logically (i, k):  major='i' -> buffer (i, k);  major='k' -> buffer (k, i)
  * B is logically (k, j):  major='k' -> buffer (k, j);  major='j' -> buffer (j, k)
  * C is logically (i, j):  major='i' -> buffer (i, j);  major='j' -> buffer (j, i)

('major' = the OUTER buffer axis, i.e. the slower-varying one.)

Precision: the operand dtype decides it. Float32 operands are multiplied at
float32 precision (``lax.Precision.HIGHEST``, which Mosaic lowers to a
float32-precision ``tpu.matmul``; the v5e's MXU computes it in several bf16
passes), bf16 operands in the MXU's single bf16 pass. Both accumulate in f32.

VMEM budget: one (bm, bk) A tile + one (bk, bn) B tile + one (bm, bn) f32
accumulator.  Defaults bm=bn=bk=256 in f32: 3*256*256*4 B = 768 KiB << 16 MiB
VMEM; MXU dims are multiples of 128.

Buffer rotation (``gemm_panel_pallas``): the inner step of the
double-buffered ring SUMMA accumulates each local multiply into a *rotating*
j-block of a wider partial panel — block ``(r + s) % R`` at ring step ``s``.
The rotation index is a traced per-rank scalar, fed to the kernel as a
scalar-prefetch operand so the BlockSpec index maps offset the panel tiles
directly; the panel is aliased in-place (``input_output_aliases``), so the
blocks outside the rotation window are preserved without any copy and the
slice/update pair of the naive formulation disappears into the kernel's
HBM<->VMEM tile fetches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

__all__ = ["gemm_pallas", "gemm_panel_pallas"]


def _gemm_kernel(a_ref, b_ref, *refs, a_trans: bool, b_trans: bool, c_trans: bool, nk: int, has_acc: bool):
    if has_acc:
        cin_ref, c_ref, acc_ref = refs
    else:
        cin_ref, (c_ref, acc_ref) = None, refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        if cin_ref is None:
            acc_ref[...] = jnp.zeros_like(acc_ref)
        else:
            cin = cin_ref[...]
            if c_trans:
                cin = cin.T
            acc_ref[...] = cin.astype(jnp.float32)

    a = a_ref[...]
    if a_trans:
        a = a.T  # (bk, bm) tile fetched in buffer order -> logical (bm, bk)
    b = b_ref[...]
    if b_trans:
        b = b.T
    # float32 tiles are multiplied at float32 precision: at the default
    # precision the MXU would take them in one bf16 pass
    precision = lax.Precision.HIGHEST if a.dtype == b.dtype == jnp.float32 else None
    acc_ref[...] += jnp.dot(a, b, precision=precision, preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        acc = acc_ref[...]
        if c_trans:
            acc = acc.T
        c_ref[...] = acc.astype(c_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("majors", "bm", "bn", "bk", "interpret", "out_dtype"),
)
def gemm_pallas(
    a,
    b,
    acc=None,
    *,
    majors: str = "I/I/K",  # C/A/B major dims, paper Fig. 3 labels
    bm: int = 256,
    bn: int = 256,
    bk: int = 256,
    interpret: bool = False,
    out_dtype=None,
):
    """C = A @ B (+ acc) with per-operand physical orientation.

    ``a``/``b`` are the *buffers* (already in their physical layout); the
    ``majors`` string says how to interpret them, e.g. ``"J/K/J"`` means C is
    j-major (buffer (j,i)), A is k-major (buffer (k,i)), B is j-major
    (buffer (j,k)).  ``acc``, if given, is a previous C buffer (same
    orientation as the output) added into the accumulator — the epilogue-free
    inner step of blocked/SUMMA GEMMs.
    """
    c_major, a_major, b_major = majors.upper().split("/")
    a_trans = a_major == "K"  # buffer (k, i) -> need transpose of tiles
    b_trans = b_major == "J"
    c_trans = c_major == "J"

    if a_trans:
        K_, M = a.shape
    else:
        M, K_ = a.shape
    if b_trans:
        N, Kb = b.shape
    else:
        Kb, N = b.shape
    if K_ != Kb:
        raise ValueError(f"contraction mismatch: {a.shape} vs {b.shape} (majors={majors})")
    K = K_
    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, K)
    if M % bm_ or N % bn_ or K % bk_:
        raise ValueError(f"dims ({M},{N},{K}) must divide block ({bm_},{bn_},{bk_})")
    nm, nn, nk = M // bm_, N // bn_, K // bk_

    a_spec = (
        pl.BlockSpec((bk_, bm_), lambda i, j, k: (k, i))
        if a_trans
        else pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k))
    )
    b_spec = (
        pl.BlockSpec((bn_, bk_), lambda i, j, k: (j, k))
        if b_trans
        else pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j))
    )
    c_spec = (
        pl.BlockSpec((bn_, bm_), lambda i, j, k: (j, i))
        if c_trans
        else pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j))
    )
    out_dtype = out_dtype or a.dtype
    out_shape = (N, M) if c_trans else (M, N)
    if acc is not None and tuple(acc.shape) != out_shape:
        raise ValueError(f"acc shape {acc.shape} != output shape {out_shape} (majors={majors})")

    kernel = functools.partial(
        _gemm_kernel,
        a_trans=a_trans,
        b_trans=b_trans,
        c_trans=c_trans,
        nk=nk,
        has_acc=acc is not None,
    )
    in_specs = [a_spec, b_spec]
    operands = [a, b]
    if acc is not None:
        in_specs.append(c_spec)
        operands.append(acc)
    return pl.pallas_call(
        kernel,
        grid=(nm, nn, nk),
        in_specs=in_specs,
        out_specs=c_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
        scratch_shapes=[_vmem((bm_, bn_), jnp.float32)],
        interpret=interpret,
        name="gemm_pallas",
    )(*operands)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def _panel_kernel(jb_ref, a_ref, b_ref, panel_ref, out_ref, acc_ref, **kw):
    del jb_ref  # consumed by the BlockSpec index maps (scalar prefetch)
    _gemm_kernel(a_ref, b_ref, panel_ref, out_ref, acc_ref, has_acc=True, **kw)


@functools.partial(
    jax.jit,
    static_argnames=("majors", "bm", "bn", "bk", "interpret"),
)
def gemm_panel_pallas(
    a,
    b,
    panel,
    jb,
    *,
    majors: str = "I/I/K",
    bm: int = 256,
    bn: int = 256,
    bk: int = 256,
    interpret: bool = False,
):
    """panel[j-block jb] += A @ B — the rotating-accumulator SUMMA inner step.

    ``panel`` is the partial C panel spanning ``nb`` j-blocks of width N (the
    logical j extent of ``b``); ``jb`` selects the block to accumulate into
    and may be a *traced* scalar (each rank of the ring computes its own).
    The panel buffer uses the C orientation of ``majors``; the rotation rides
    the BlockSpec index maps via scalar prefetch and the panel is updated in
    place (``input_output_aliases``), leaving the other blocks untouched.
    Returns the whole updated panel.
    """
    from jax.experimental.pallas import tpu as pltpu

    c_major, a_major, b_major = majors.upper().split("/")
    a_trans = a_major == "K"
    b_trans = b_major == "J"
    c_trans = c_major == "J"

    if a_trans:
        K_, M = a.shape
    else:
        M, K_ = a.shape
    if b_trans:
        N, Kb = b.shape
    else:
        Kb, N = b.shape
    if K_ != Kb:
        raise ValueError(f"contraction mismatch: {a.shape} vs {b.shape} (majors={majors})")
    K = K_
    NJ, MP = (panel.shape[0], panel.shape[1]) if c_trans else (panel.shape[1], panel.shape[0])
    if MP != M or NJ % N:
        raise ValueError(
            f"panel shape {panel.shape} incompatible with block ({M},{N}) (majors={majors})"
        )
    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, K)
    if M % bm_ or N % bn_ or K % bk_:
        raise ValueError(f"dims ({M},{N},{K}) must divide block ({bm_},{bn_},{bk_})")
    nm, nn, nk = M // bm_, N // bn_, K // bk_

    a_spec = (
        pl.BlockSpec((bk_, bm_), lambda i, j, k, jb: (k, i))
        if a_trans
        else pl.BlockSpec((bm_, bk_), lambda i, j, k, jb: (i, k))
    )
    b_spec = (
        pl.BlockSpec((bn_, bk_), lambda i, j, k, jb: (j, k))
        if b_trans
        else pl.BlockSpec((bk_, bn_), lambda i, j, k, jb: (k, j))
    )
    # the panel tile maps rotate with the prefetched block index: block jb of
    # the panel holds j-columns [jb*N, (jb+1)*N), i.e. j-tile jb*nn + j
    panel_spec = (
        pl.BlockSpec((bn_, bm_), lambda i, j, k, jb: (jb[0] * nn + j, i))
        if c_trans
        else pl.BlockSpec((bm_, bn_), lambda i, j, k, jb: (i, jb[0] * nn + j))
    )

    kernel = functools.partial(
        _panel_kernel,
        a_trans=a_trans,
        b_trans=b_trans,
        c_trans=c_trans,
        nk=nk,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nm, nn, nk),
        in_specs=[a_spec, b_spec, panel_spec],
        out_specs=panel_spec,
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
    )
    jb_arr = jnp.asarray(jb, jnp.int32).reshape((1,))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(panel.shape, panel.dtype),
        input_output_aliases={3: 0},  # flat operands: jb, a, b, panel
        interpret=interpret,
        name="gemm_panel_pallas",
    )(jb_arr, a, b, panel)

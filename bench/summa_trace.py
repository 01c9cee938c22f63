"""What the SUMMA cell's per-layer metrics read from a traced run.

The benchmark marks each multiply on the host (``bench/systems/summa.py``):
``bench.summa.dispatch`` from the call to its return and
``bench.summa.wait`` around ``block_until_ready``. On the devices the
multiply is one program (``jit_ring_phase``) whose operations include the
ring's and the epilogue's collectives and the Pallas kernel of the inner
step, one HLO custom call named after its function
(``%gemm_panel_pallas.3 = ... custom-call(...)``).
"""
from __future__ import annotations

from bench import trace_reduce
from bench.engine_spans import spans

KERNEL = "gemm_panel_pallas"
DISPATCH = "bench.summa.dispatch"
# collectives by the name of their HLO instruction: the opcode, also in its
# async (-start / -done) forms, or, where the compiler keeps it, the JAX
# primitive (``ppermute.3``, ``psum``); ``_`` is read as ``-``
COLLECTIVES = ("collective-permute", "ppermute", "reduce-scatter", "psum", "all-gather",
               "all-reduce", "all-to-all")


def is_collective(op) -> bool:
    return trace_reduce.op_label(op).replace("_", "-").startswith(COLLECTIVES)


def is_kernel(op) -> bool:
    return trace_reduce.op_label(op) == KERNEL


def multiplies(run) -> int:
    """Multiplies whose dispatch lies inside the traced window."""
    return len(spans(run, DISPATCH))

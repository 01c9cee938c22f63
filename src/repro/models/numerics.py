"""Schedule-independent low-precision rounding for the decode path.

The model math rounds activations to ``cfg.act_dtype`` at every op boundary
(einsum outputs, rope, softmax probabilities, residual adds).  Those rounds
are *semantic* — they define the reference number stream — but XLA's
simplifier treats the converts as droppable and folds them into the f32
internals of neighbouring ops.  Which converts survive depends on the whole
program being compiled: the single-host oracle (blocks under ``lax.scan``,
one jitted computation) and the explicit tensor-parallel decode step
(unrolled shard_map body) fold *differently*, so the two programs drift one
ulp per layer apart and eventually emit different greedy tokens — with no
distributed-math error anywhere.

:func:`pin` places an ``optimization_barrier`` at a dtype boundary so the
round really happens there, making the emitted values a function of the op
sequence alone, not of the compilation schedule.  It is active only inside
:func:`pinned_rounding` — the serving engine enters it for its prefill and
decode steps (both the oracle and TP paths), while training keeps the
unpinned fast path.  This is what makes the distributed engine's greedy stream
token-for-token the single-host oracle's.
"""
from __future__ import annotations

from contextlib import contextmanager

import jax

__all__ = ["pin", "pinned_rounding"]

_PINNED = False


@contextmanager
def pinned_rounding():
    """Trace-time context: make :func:`pin` a real barrier.

    Enter it around *tracing* (the jit'd function body, not the call site of
    an already-compiled function) — ``pin`` reads the flag while the program
    is being staged out."""
    global _PINNED
    prev = _PINNED
    _PINNED = True
    try:
        yield
    finally:
        _PINNED = prev


def pin(x):
    """Materialize ``x`` exactly as typed when pinned rounding is active;
    identity (no graph change) otherwise."""
    return jax.lax.optimization_barrier(x) if _PINNED else x

"""Production mesh construction (dry-run and launch scripts).

A function, not a module-level constant — importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax

from repro.core import make_mesh

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod ('data','model'); 2 pods adds a leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests, examples)."""
    n = len(jax.devices())
    data = data if data is not None else n // model
    return make_mesh((data, model), ("data", "model"))

"""Mesh construction for the launch scripts.

A function, not a module-level constant — importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax

from repro.core import make_mesh

__all__ = ["make_local_mesh"]


def make_local_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests, examples)."""
    n = len(jax.devices())
    data = data if data is not None else n // model
    return make_mesh((data, model), ("data", "model"))

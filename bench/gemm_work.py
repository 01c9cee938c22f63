"""Operations and bytes the SUMMA GEMM requires, from its shapes.

As in ``bench/flops.py``, each count is of the work the algorithm needs,
never of what one implementation happens to move, so a share of a peak
computed from it cannot pass 100% unless the time leaves out part of the
work. The shapes come from the configuration's keys (``ni``, ``nj``,
``nk``, ``grid``, ``dtype``), as in ``bench/configs/summa-xl-f32.json``.
"""
from __future__ import annotations

import numpy as np


def summa_flops(c: dict) -> int:
    """One whole multiply, all ranks: 2 * ni * nj * nk."""
    return 2 * c["ni"] * c["nj"] * c["nk"]


def panel_gemm_cost(c: dict) -> tuple[int, int]:
    """(operations, bytes) of one call of the ring's inner step
    (``gemm_panel_pallas``) on one rank of the ``R x Cc`` grid: its
    (m, k) = (ni/R, nk/Cc) A tile times the (k, n) = (nk/Cc, nj/R) B panel
    it holds at that step, 2mnk operations; the A tile and the B panel read
    once and the (m, n) block of the partial panel written once. A multiply
    makes R such calls on each rank. The block's old contents are not
    counted: a multiply starts its partial panel at zero and each ring step
    fills a block that no earlier step touched, so what is there is zeros
    the work does not need. Re-reads of a tile that the kernel's own tiling
    makes are not counted either."""
    R, Cc = c["grid"]
    m, n, k = c["ni"] // R, c["nj"] // R, c["nk"] // Cc
    itemsize = np.dtype(c["dtype"]).itemsize
    return 2 * m * n * k, (m * k + k * n + m * n) * itemsize

"""Plain reference of ``summa-xl-f32``: C = A @ B in float64, with NumPy on
the host, over the operands the run drew.

The program takes and returns stacked per-rank tiles, ``(R, Cc) + tile``
for an ``R x Cc`` grid, each tile in the physical layout its major gives
(``majors`` is C/A/B, the paper's Fig. 3 labels: A is logically (i, k), B
(k, j), C (i, j); the major is the outer buffer axis). Rank (r, c) holds
A[i-block r, k-block c], B[k-block c, j-block r] and C[i-block r, j-block c]
(``examples/distributed_gemm.run_summa_gemm``). The functions here put the
tiles back into whole matrices with plain NumPy, independently of the comm
layer.
"""
import numpy as np


def _logical(tiles, major: str, outer: str):
    """Tiles as logical (row, column) blocks: a tile whose major is not its
    logical row axis ``outer`` is stored transposed."""
    return tiles if major == outer else tiles.swapaxes(-1, -2)


def global_a(tiles, majors: str) -> np.ndarray:
    t = _logical(tiles, majors.upper().split("/")[1], "I")  # (R, Cc, mi, kc)
    R, Cc, mi, kc = t.shape
    return t.transpose(0, 2, 1, 3).reshape(R * mi, Cc * kc)


def global_b(tiles, majors: str) -> np.ndarray:
    t = _logical(tiles, majors.upper().split("/")[2], "K")  # (R, Cc, kc, jr), j-block r
    R, Cc, kc, jr = t.shape
    return t.transpose(1, 2, 0, 3).reshape(Cc * kc, R * jr)


def global_c(tiles, majors: str) -> np.ndarray:
    t = _logical(tiles, majors.upper().split("/")[0], "I")  # (R, Cc, mi, jc)
    R, Cc, mi, jc = t.shape
    return t.transpose(0, 2, 1, 3).reshape(R * mi, Cc * jc)


def product(a, b) -> np.ndarray:
    return np.asarray(a, np.float64) @ np.asarray(b, np.float64)


def rel_err(c, want) -> float:
    """max |C - want| / max |want|."""
    return float(np.abs(np.asarray(c, np.float64) - want).max() / np.abs(want).max())

"""The paper's case study (§5): a layout-agnostic distributed GEMM.

Two algorithms, both layout-agnostic end to end:

1-D (``run_distributed_gemm``): each rank computes one row-panel of
C = A @ B — A is split along i, B broadcast, C gathered.

2-D SUMMA (``run_summa_gemm``): a ``(rows, cols)`` communicator grid (the
paper's ``MPI_Cart_create``).  Rank (r, c) owns A[i-block r, k-block c]; B's
k-panels live k-block-per-grid-column with their j-blocks spread down the
rows.  Each of R ring steps multiplies the local A tile against the current
B panel and the panels rotate along the *rows* sub-communicator with the
layout-agnostic p2p ring shift; the epilogue is a ``reduce_scatter_bag``
along the *cols* sub-communicator that sums the partial C panels over k and
scatters j — with the final C tile layout chosen freely, the transform fused
into the transfer.

The SUMMA ring is *double-buffered* by default: step ``s`` issues the panel
rotation with the non-blocking ``ring_shift_start`` (MPI_Isend/Irecv
analogue) *before* the local multiply and completes it with
``PendingTile.wait`` after, so the transfer has no data dependence on the
step's GEMM and the XLA scheduler overlaps the two.  The whole ring phase +
epilogue is built as ONE traced program (``summa_ring_program``) so the
overlap is *statically provable* from the compiled HLO:
``repro.launch.hlo_walk.analyze`` classifies every ``collective-permute`` as
overlapped or serialized from its def-use chains.  ``double_buffer=False``
keeps the blocking formulation (compute, then shift) — numerically
bit-identical, used as the reference.  The local multiply accumulates into a
rotating j-block of the partial panel via the buffer-rotation GEMM kernel
(``repro.kernels.ops.gemm_panel``).

In both, the *global* matrices and the *per-rank tiles* choose their physical
layouts independently (row-major or column-major per the C/A/B "majors"
configuration, Fig. 3), and every transfer transforms the layouts
automatically.  The per-rank compute is the layout-parametric GEMM kernel
(Pallas on TPU, its oracle elsewhere).

Run:  python examples/distributed_gemm.py --majors J/K/J --dataset MINI
      python examples/distributed_gemm.py --summa --grid 2x4
(run as a script on the CPU it fakes 8 devices; on a TPU host it uses the
real ones, and the SUMMA grid defaults to the squarest one they allow)
"""
import os
import argparse
import functools
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    DistBag,
    bag,
    intent_of,
    broadcast,
    dist_full,
    dist_sharding,
    gather,
    gatherv_bag,
    grid_extents,
    make_mesh,
    mpi_cart_traverser,
    mpi_traverser,
    ragged_split,
    rank_map,
    reduce_scatter_bag,
    reduce_scatterv_bag,
    ring,
    ring_shift_start,
    scatter,
    scatterv_bag,
    traverser,
)
from repro.core.layout import scalar, vector, into_blocks
from repro.core.traverser import bcast
from repro.kernels import ops


def _mat_layout(rows: str, cols: str, nr: int, nc: int, major: str):
    """Layout with the given major (outer) dimension — paper Fig. 3 labels."""
    if major == rows:
        return scalar(np.float32) ^ vector(cols, nc) ^ vector(rows, nr)  # rows outer
    return scalar(np.float32) ^ vector(rows, nr) ^ vector(cols, nc)  # cols outer


def run_distributed_gemm(*, ni: int, nj: int, nk: int, majors: str = "I/I/K", ranks: int | None = None,
                         mesh=None, verbose: bool = False):
    """Returns (C_result, C_oracle) as (ni, nj) numpy arrays."""
    c_major, a_major, b_major = majors.upper().split("/")
    if mesh is None:
        n_dev = len(jax.devices())
        ranks = ranks or n_dev
        mesh = make_mesh((ranks,), ("r",))
    ranks = ranks or mesh.shape["r"]
    assert ni % ranks == 0, (ni, ranks)

    rng = np.random.default_rng(7)
    A_np = rng.standard_normal((ni, nk)).astype(np.float32)
    B_np = rng.standard_normal((nk, nj)).astype(np.float32)

    # --- global bags, laid out per the config --------------------------------
    A_layout = _mat_layout("i", "k", ni, nk, "i" if a_major == "I" else "k")
    B_layout = _mat_layout("k", "j", nk, nj, "k" if b_major == "K" else "j")
    C_layout = _mat_layout("i", "j", ni, nj, "i" if c_major == "I" else "j")
    A_glob = bag(A_layout, A_np if A_layout.axis_names == ("i", "k") else A_np.T)
    B_glob = bag(B_layout, B_np if B_layout.axis_names == ("k", "j") else B_np.T)

    # --- distribution: rank dim R = row-blocks of i (paper §4.1) -------------
    A_root_layout = A_layout ^ into_blocks("i", "R", num_blocks=ranks)
    A_root = bag(A_root_layout, A_glob.data)
    dt = mpi_traverser("R", traverser(A_root), mesh)

    # --- per-rank tile layouts, chosen independently of the global ones ------
    A_tile = _mat_layout("i", "k", ni // ranks, nk, "i" if a_major == "I" else "k")
    B_tile = B_layout
    C_tile = _mat_layout("i", "j", ni // ranks, nj, "i" if c_major == "I" else "j")

    t0 = time.perf_counter()
    A_dist = scatter(A_root, A_tile, dt)  # layout transform rides the scatter
    B_all = broadcast(B_glob, dt, dst_layout=B_tile)

    def compute(rank, a_tile):
        # per-rank layout-parametric GEMM (paper's kernel, Pallas on TPU)
        out = ops.gemm(a_tile.data, B_all.data, majors=majors)
        return bag(C_tile, out)

    C_dist = rank_map(compute, dt, A_dist, out_tile_layout=C_tile)
    C_root_layout = C_layout ^ into_blocks("i", "R", num_blocks=ranks)
    C_root = gather(C_dist, C_root_layout)
    C_root.data.block_until_ready()
    elapsed = time.perf_counter() - t0

    # back to a plain (ni, nj) row-major array for checking
    flat = bag(C_root_layout, C_root.data).to_layout(
        scalar(np.float32) ^ vector("j", nj) ^ vector("i", ni // ranks) ^ vector("R", ranks)
    )
    C_result = np.asarray(flat.data).reshape(ni, nj)
    C_oracle = A_np @ B_np
    if verbose:
        err = np.abs(C_result - C_oracle).max()
        print(f"majors={majors} ranks={ranks} ni,nj,nk=({ni},{nj},{nk}) "
              f"time={elapsed*1e3:.2f}ms max_err={err:.2e}")
    return C_result, C_oracle


def comm_volume_model(algo: str, *, ni: int, nj: int, nk: int,
                      grid: tuple[int, int] | None = None, ranks: int | None = None,
                      dtype_bytes: int = 4, ragged: bool = False) -> dict:
    """Analytic per-rank communication volume (bytes) of the two algorithms.

    The headline asymptotics the benchmark tables report: the 1-D row-panel
    algorithm replicates B to every rank — O(n^2) per rank regardless of P —
    while the 2-D SUMMA ring moves only the (nk/Cc, nj/R) panel per step,
    O(n^2/sqrt(P)) on a square grid.  ``ring_bytes`` is exact and matches the
    ``collective-permute`` bytes the HLO walker counts in the dry-run trace;
    the reduce-scatter/broadcast terms follow the HLO walker's conventions
    (``repro.launch.hlo_walk``: result bytes x1).
    """
    if algo == "summa2d":
        if grid is None:
            raise ValueError("summa2d model needs grid=(rows, cols)")
        R, Cc = grid
        if ragged:
            # ragged (v-collective) SUMMA: tiles move at padded *capacity* on
            # the wire, but the modeled payload is the mean per-rank VALID
            # bytes.  Rank (r, c) at step s ships B block (k-block c,
            # j-block (r+s)%R) = ek[c] * ej[(r+s)%R] elements; averaging over
            # the grid, sum_s ej telescopes to (R-1) * nj / R and mean ek is
            # nk / Cc — the exact-division formula with real divisions.
            cap_i, _ = ragged_split(ni, R)
            cap_k, _ = ragged_split(nk, Cc)
            cap_jr, _ = ragged_split(nj, R)
            cap_jc, _ = ragged_split(nj, Cc)
            ring = (R - 1) * (nk / Cc) * (nj / R) * dtype_bytes
            ring_padded = (R - 1) * cap_k * cap_jr * dtype_bytes
            rs = (ni / R) * (nj / Cc) * dtype_bytes
            rs_padded = cap_i * cap_jc * dtype_bytes
            return {
                "algo": algo, "ragged": True,
                "ring_bytes": ring, "ring_padded_bytes": ring_padded,
                "reduce_scatter_bytes": rs, "reduce_scatter_padded_bytes": rs_padded,
                "total_bytes": ring + rs, "total_padded_bytes": ring_padded + rs_padded,
                # static valid/padded ratios per collective kind, consumed by
                # hlo_walk.analyze(valid_fractions=...) so padding never
                # inflates the modeled collective cost
                "valid_fractions": {
                    "collective-permute": ring / ring_padded if ring_padded else 1.0,
                    "reduce-scatter": rs / rs_padded if rs_padded else 1.0,
                },
            }
        ring = (R - 1) * (nk // Cc) * (nj // R) * dtype_bytes
        reduce_scatter = (ni // R) * (nj // Cc) * dtype_bytes
        return {"algo": algo, "ring_bytes": ring,
                "reduce_scatter_bytes": reduce_scatter,
                "total_bytes": ring + reduce_scatter}
    if algo == "panel1d":
        if ranks is None:
            raise ValueError("panel1d model needs ranks")
        bcast_b = nk * nj * dtype_bytes  # B replicated to every rank: O(n^2)
        scatter_b = (ni // ranks) * nk * dtype_bytes
        gather_b = (ni // ranks) * nj * dtype_bytes
        return {"algo": algo, "broadcast_bytes": bcast_b, "scatter_bytes": scatter_b,
                "gather_bytes": gather_b, "total_bytes": bcast_b + scatter_b + gather_b}
    raise ValueError(f"unknown algo {algo!r}")


def default_grid(n_devices: int) -> tuple[int, int]:
    """The squarest (rows, cols) grid over ``n_devices``, rows <= cols."""
    rows = max(r for r in range(1, int(n_devices ** 0.5) + 1) if n_devices % r == 0)
    return rows, n_devices // rows


def _tile_block(n: int, block: int = 256) -> int:
    """Kernel block along a tile dim of extent ``n``: ``block`` where it
    divides, else the whole dim (a whole dim is always a legal TPU block)."""
    return block if n % block == 0 else n


@functools.lru_cache(maxsize=64)  # reuse the jitted program across calls
def summa_ring_program(*, ni: int, nj: int, nk: int, grid: tuple[int, int] = (2, 4),
                       majors: str = "I/I/K", mesh=None, double_buffer: bool = True):
    """Build the SUMMA ring phase + reduce-scatter epilogue as ONE traced
    program, so the comm/compute structure is inspectable in the compiled HLO.

    Returns ``(fn, meta)``: ``fn`` is a jitted function taking the stacked
    per-rank A tiles and B panels (``DistBag.data``) and returning the
    stacked C tiles; ``meta`` carries the mesh, traversers, tile layouts,
    abstract arguments for dry-run lowering, and the analytic comm model.

    The schedule is a declared comm plan (:func:`repro.core.ring`): the
    planner issues each step's panel rotation with the non-blocking
    ``ring_shift_start`` *before* the local GEMM and waits after it — the
    transfer is off the def-use chain between consecutive GEMMs, so
    ``hlo_walk.analyze`` classifies every ring ``collective-permute`` as
    overlapped, and ``meta["plan_intent"]`` records the declared intent the
    dry-run gates verify.  With ``double_buffer=False`` the planner starts
    and waits back-to-back (the blocking interpretation) — numerically
    bit-identical by construction.
    """
    c_major, a_major, b_major = majors.upper().split("/")
    R, Cc = grid
    if mesh is None:
        mesh = make_mesh((R, Cc), ("rows", "cols"))
    assert ni % R == 0 and nk % Cc == 0 and nj % R == 0 and nj % Cc == 0, (ni, nj, nk, grid)
    mi, kc, jr, jc = ni // R, nk // Cc, nj // R, nj // Cc

    # --- global layouts + communicator grid (paper's MPI_Cart_create) --------
    A_layout = _mat_layout("i", "k", ni, nk, "i" if a_major == "I" else "k")
    B_layout = _mat_layout("k", "j", nk, nj, "k" if b_major == "K" else "j")
    A_root_l = A_layout ^ into_blocks("i", "Ri", num_blocks=R) ^ into_blocks("k", "Ck", num_blocks=Cc)
    B_root_l = B_layout ^ into_blocks("k", "Ck", num_blocks=Cc) ^ into_blocks("j", "Rj", num_blocks=R)
    dtA = mpi_cart_traverser([("Ri", "rows"), ("Ck", "cols")], traverser(A_root_l), mesh)
    dtB = mpi_cart_traverser([("Rj", "rows"), ("Ck", "cols")], traverser(B_root_l), mesh)

    # --- per-rank tile layouts, chosen independently of the global ones ------
    A_tile = _mat_layout("i", "k", mi, kc, "i" if a_major == "I" else "k")
    B_tile = _mat_layout("k", "j", kc, jr, "k" if b_major == "K" else "j")
    C_tile = _mat_layout("i", "j", mi, jc, "i" if c_major == "I" else "j")
    P_l = _mat_layout("i", "j", mi, nj, "i")  # partial panel, i-major internal

    local_majors = f"I/{a_major}/{b_major}"
    blocks = dict(bm=_tile_block(mi), bn=_tile_block(jr), bk=_tile_block(kc))

    def ring_phase(a_data, b_data):
        A_dist = DistBag(a_data, A_tile, dtA, ("Ri", "Ck"))
        B_cur = DistBag(b_data, B_tile, dtB, ("Rj", "Ck"))
        P = dist_full(dtA, P_l)

        def compute(p, b_cur, s):
            def step(state, p_, a, b_panel, _s=s):
                # per-rank layout-parametric GEMM (paper's kernel, Pallas on
                # TPU) accumulating into the rotating j-block of the panel
                jb = (state["Ri"] + _s) % R
                new = ops.gemm_panel(a.data, b_panel.data, p_.data, jb,
                                     majors=local_majors, **blocks)
                return p_.with_data(new)

            return rank_map(step, dtA, p, A_dist, b_cur, out_tile_layout=P_l)

        # the schedule is declared once: the planner issues each step's
        # rotation (MPI_Start analogue) before the local GEMM and waits after
        # it, and the epilogue sums partials over k (grid cols) and scatters
        # j, landing each rank's C tile directly in its chosen layout
        plan = ring(
            R,
            transfer=lambda b_cur, s: ring_shift_start(b_cur, -1, rank_dim="Rj"),
            compute=compute,
            epilogue=lambda p, b_cur: reduce_scatter_bag(
                p, C_tile, scatter_dim="j", rank_dim="Ck"
            ).data,
        )
        return plan.run(B_cur, P, double_buffer=double_buffer)

    shA = dist_sharding(dtA, A_tile)
    shB = dist_sharding(dtB, B_tile)
    fn = jax.jit(ring_phase, in_shardings=(shA, shB))
    meta = dict(
        mesh=mesh, dtA=dtA, dtB=dtB, grid=grid, steps=R,
        A_layout=A_layout, B_layout=B_layout,
        A_root_l=A_root_l, B_root_l=B_root_l,
        A_tile=A_tile, B_tile=B_tile, C_tile=C_tile, panel_layout=P_l,
        plan_intent=intent_of("ring"),
        abstract_args=(
            jax.ShapeDtypeStruct((R, Cc) + A_tile.shape, A_tile.dtype),
            jax.ShapeDtypeStruct((R, Cc) + B_tile.shape, B_tile.dtype),
        ),
        comm_model=comm_volume_model("summa2d", ni=ni, nj=nj, nk=nk, grid=grid),
    )
    return fn, meta


def run_summa_gemm(*, ni: int, nj: int, nk: int, grid: tuple[int, int] = (2, 4),
                   majors: str = "I/I/K", mesh=None, verbose: bool = False,
                   double_buffer: bool = True):
    """2-D-grid SUMMA C = A @ B; returns (C_result, C_oracle) as (ni, nj).

    Placement on the (rows=R, cols=Cc) grid:
      * A[i-block r, k-block c] on rank (r, c)        (stationary)
      * B[k-block c, j-block r] on rank (r, c)        (rotates along rows)
      * C[i-block r, j-chunk c] on rank (r, c)        (reduce_scatter output)

    Ring phase: at step s rank (r, c) holds B[k-block c, j-block (r+s) % R]
    and fills j-block (r+s) % R of its partial panel P = A[r,c] @ B[k c, :];
    the B panels ring-shift one hop along the *rows* sub-communicator —
    non-blocking and overlapped with the multiply when ``double_buffer``
    (the default), blocking otherwise.  See :func:`summa_ring_program`.
    """
    R, Cc = grid
    fn, meta = summa_ring_program(ni=ni, nj=nj, nk=nk, grid=grid, majors=majors,
                                  mesh=mesh, double_buffer=double_buffer)
    dtA, dtB = meta["dtA"], meta["dtB"]
    A_tile, B_tile, C_tile = meta["A_tile"], meta["B_tile"], meta["C_tile"]
    mi, jc = ni // R, nj // Cc

    rng = np.random.default_rng(11)
    A_np = rng.standard_normal((ni, nk)).astype(np.float32)
    B_np = rng.standard_normal((nk, nj)).astype(np.float32)

    # --- global bags, laid out per the config (layouts from the program) -----
    A_layout, B_layout = meta["A_layout"], meta["B_layout"]
    A_glob = bag(A_layout, A_np if A_layout.axis_names == ("i", "k") else A_np.T)
    B_glob = bag(B_layout, B_np if B_layout.axis_names == ("k", "j") else B_np.T)
    A_root = bag(meta["A_root_l"], A_glob.data)
    B_root = bag(meta["B_root_l"], B_glob.data)

    t0 = time.perf_counter()
    A_dist = scatter(A_root, A_tile, dtA)  # layout transform rides the scatter
    B_cur = scatter(B_root, B_tile, dtB)
    C_data = fn(A_dist.data, B_cur.data)  # the whole ring + epilogue, one program
    C_grid = DistBag(C_data, C_tile, dtA, ("Ri", "Ck"))
    C_grid.data.block_until_ready()
    elapsed = time.perf_counter() - t0

    # back to a plain (ni, nj) row-major array for checking
    flat_tile = _mat_layout("i", "j", mi, jc, "i")
    C_result = np.zeros((ni, nj), np.float32)
    for r in range(R):
        for c in range(Cc):
            t = C_grid.tile((r, c)).to_layout(flat_tile)
            C_result[r * mi:(r + 1) * mi, c * jc:(c + 1) * jc] = np.asarray(t.data)
    C_oracle = A_np @ B_np
    if verbose:
        err = np.abs(C_result - C_oracle).max()
        variant = "double-buffered" if double_buffer else "blocking"
        print(f"SUMMA[{variant}] majors={majors} grid={grid} ni,nj,nk=({ni},{nj},{nk}) "
              f"time={elapsed*1e3:.2f}ms max_err={err:.2e}")
    return C_result, C_oracle


@functools.lru_cache(maxsize=64)  # reuse the jitted program across calls
def ragged_summa_program(*, ni: int, nj: int, nk: int, grid: tuple[int, int] = (2, 4),
                         majors: str = "I/I/K", mesh=None, double_buffer: bool = True):
    """The *ragged* SUMMA ring: ``ni``/``nj``/``nk`` need NOT divide the grid.

    Every matrix dim is split with :func:`repro.core.ragged_split` into
    balanced ragged blocks carried as per-rank extents (the MPI v-collective
    counts) over padded capacity tiles.  The structure is identical to
    :func:`summa_ring_program` — R ring steps, the panel rotation issued
    non-blocking *before* each step's local GEMM — except that:

      * A tiles and B panels are ragged DistBags (zero padding behind the
        valid leading block, so the padded GEMM contributions vanish);
      * ``ring_shift_start`` rotates the B extents table together with the
        panels (the receiver adopts the sender's counts);
      * the epilogue is :func:`repro.core.reduce_scatterv_bag`: the
        block-ragged partial panels are compacted/re-padded with static
        slices and reduced+scattered so rank (r, c) lands its
        ``(ei[r], ejc[c])`` valid C block in a capacity tile.

    ``meta["comm_model"]`` carries the analytic ragged model with both
    *padded* (wire) and *valid* (payload) bytes plus the per-kind
    ``valid_fractions`` that ``hlo_walk.analyze`` uses to keep padding out
    of the modeled collective cost.
    """
    c_major, a_major, b_major = majors.upper().split("/")
    R, Cc = grid
    if mesh is None:
        mesh = make_mesh((R, Cc), ("rows", "cols"))
    cap_i, ei = ragged_split(ni, R)
    cap_k, ek = ragged_split(nk, Cc)
    cap_jr, ejr = ragged_split(nj, R)
    cap_jc, ejc = ragged_split(nj, Cc)

    # --- global layouts + communicator grid (no into_blocks: nothing divides)
    A_layout = _mat_layout("i", "k", ni, nk, "i" if a_major == "I" else "k")
    B_layout = _mat_layout("k", "j", nk, nj, "k" if b_major == "K" else "j")
    dtA = mpi_cart_traverser(
        [("Ri", "rows"), ("Ck", "cols")],
        traverser(scalar(np.float32) ^ vector("Ck", Cc) ^ vector("Ri", R)), mesh)
    dtB = mpi_cart_traverser(
        [("Rj", "rows"), ("Ck", "cols")],
        traverser(scalar(np.float32) ^ vector("Ck", Cc) ^ vector("Rj", R)), mesh)

    # --- per-rank padded capacity tile layouts (valid = leading extents) -----
    A_tile = _mat_layout("i", "k", cap_i, cap_k, "i" if a_major == "I" else "k")
    B_tile = _mat_layout("k", "j", cap_k, cap_jr, "k" if b_major == "K" else "j")
    C_tile = _mat_layout("i", "j", cap_i, cap_jc, "i" if c_major == "I" else "j")
    P_l = _mat_layout("i", "j", cap_i, R * cap_jr, "i")  # partial panel, i-major

    extA = grid_extents(dtA, ("Ri", "Ck"), {"Ri": ("i", ei), "Ck": ("k", ek)})
    extB = grid_extents(dtB, ("Rj", "Ck"), {"Rj": ("j", ejr), "Ck": ("k", ek)})
    extP = grid_extents(dtA, ("Ri", "Ck"), {"Ri": ("i", ei)})

    local_majors = f"I/{a_major}/{b_major}"

    def ring_phase(a_data, b_data):
        A_dist = DistBag(a_data, A_tile, dtA, ("Ri", "Ck"), extents=extA)
        B_cur = DistBag(b_data, B_tile, dtB, ("Rj", "Ck"), extents=extB)
        P = dist_full(dtA, P_l)

        def compute(p, b_cur, s):
            def step(state, p_, a, b_panel, _s=s):
                # padded capacity GEMM: zero padding in A's i/k and the
                # panel's k/j contributes zeros, so the accumulation into the
                # rotating j-block stays exact without masks
                jb = (state["Ri"] + _s) % R
                new = ops.gemm_panel(a.data, b_panel.data, p_.data, jb, majors=local_majors)
                return p_.with_data(new)

            return rank_map(step, dtA, p, A_dist, b_cur, out_tile_layout=P_l,
                            out_extents=extP)

        # same declared schedule as the dense SUMMA — the extents table
        # rotates with the panels inside the planner's transfers, and the
        # ragged epilogue compacts the R block-ragged j slabs, re-pads into
        # Cc ragged output blocks, reduces over k (grid cols) and scatters j
        plan = ring(
            R,
            transfer=lambda b_cur, s: ring_shift_start(b_cur, -1, rank_dim="Rj"),
            compute=compute,
            epilogue=lambda p, b_cur: reduce_scatterv_bag(
                p, C_tile, scatter_dim="j", in_blocks=(cap_jr, ejr),
                out_extents=ejc, rank_dim="Ck"
            ).data,
        )
        return plan.run(B_cur, P, double_buffer=double_buffer)

    shA = dist_sharding(dtA, A_tile)
    shB = dist_sharding(dtB, B_tile)
    fn = jax.jit(ring_phase, in_shardings=(shA, shB))
    meta = dict(
        mesh=mesh, dtA=dtA, dtB=dtB, grid=grid, steps=R,
        A_layout=A_layout, B_layout=B_layout,
        A_tile=A_tile, B_tile=B_tile, C_tile=C_tile, panel_layout=P_l,
        caps=dict(i=cap_i, k=cap_k, jr=cap_jr, jc=cap_jc),
        extents=dict(i=ei, k=ek, jr=ejr, jc=ejc),
        A_ragged={"Ri": ("i", ei), "Ck": ("k", ek)},
        B_ragged={"Rj": ("j", ejr), "Ck": ("k", ek)},
        C_extents=grid_extents(dtA, ("Ri", "Ck"), {"Ri": ("i", ei), "Ck": ("j", ejc)}),
        plan_intent=intent_of("ring"),
        abstract_args=(
            jax.ShapeDtypeStruct((R, Cc) + A_tile.shape, A_tile.dtype),
            jax.ShapeDtypeStruct((R, Cc) + B_tile.shape, B_tile.dtype),
        ),
        comm_model=comm_volume_model("summa2d", ni=ni, nj=nj, nk=nk, grid=grid,
                                     ragged=True),
    )
    return fn, meta


def run_ragged_summa_gemm(*, ni: int, nj: int, nk: int, grid: tuple[int, int] = (2, 4),
                          majors: str = "I/I/K", mesh=None, verbose: bool = False,
                          double_buffer: bool = True):
    """Ragged SUMMA C = A @ B for dims that do NOT divide the grid; returns
    (C_result, C_oracle) as (ni, nj) numpy arrays.

    A and B enter through :func:`repro.core.scatterv_bag` (MPI_Scatterv with
    balanced counts), the traced program of :func:`ragged_summa_program` runs
    the double-buffered ring + v reduce-scatter, and the C tiles come back
    through :func:`repro.core.gatherv_bag` — padding never appears in any
    logical result.
    """
    R, Cc = grid
    fn, meta = ragged_summa_program(ni=ni, nj=nj, nk=nk, grid=grid, majors=majors,
                                    mesh=mesh, double_buffer=double_buffer)
    dtA, dtB = meta["dtA"], meta["dtB"]
    A_tile, B_tile, C_tile = meta["A_tile"], meta["B_tile"], meta["C_tile"]

    rng = np.random.default_rng(13)
    A_np = rng.standard_normal((ni, nk)).astype(np.float32)
    B_np = rng.standard_normal((nk, nj)).astype(np.float32)

    A_layout, B_layout = meta["A_layout"], meta["B_layout"]
    A_glob = bag(A_layout, A_np if A_layout.axis_names == ("i", "k") else A_np.T)
    B_glob = bag(B_layout, B_np if B_layout.axis_names == ("k", "j") else B_np.T)

    t0 = time.perf_counter()
    A_dist = scatterv_bag(A_glob, A_tile, dtA, meta["A_ragged"])
    B_dist = scatterv_bag(B_glob, B_tile, dtB, meta["B_ragged"])
    C_data = fn(A_dist.data, B_dist.data)  # the whole ring + epilogue, one program
    C_grid = DistBag(C_data, C_tile, dtA, ("Ri", "Ck"), extents=meta["C_extents"])
    C_grid.data.block_until_ready()
    elapsed = time.perf_counter() - t0

    # gatherv back to a plain (ni, nj) row-major root for checking
    C_root_l = _mat_layout("i", "j", ni, nj, "i")  # axes (i, j) row-major
    C_root = gatherv_bag(C_grid, C_root_l)
    C_result = np.asarray(C_root.data).reshape(ni, nj)
    C_oracle = A_np @ B_np
    if verbose:
        err = np.abs(C_result - C_oracle).max()
        variant = "double-buffered" if double_buffer else "blocking"
        print(f"ragged SUMMA[{variant}] majors={majors} grid={grid} "
              f"ni,nj,nk=({ni},{nj},{nk}) caps={meta['caps']} "
              f"time={elapsed*1e3:.2f}ms max_err={err:.2e}")
    return C_result, C_oracle


def main():
    from repro.configs.gemm_case_study import DATASETS, LAYOUT_CONFIGS

    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="MINI", choices=list(DATASETS))
    ap.add_argument("--majors", default=None, help="e.g. J/K/J; default: all 8")
    ap.add_argument("--ranks", type=int, default=None)
    ap.add_argument("--summa", action="store_true", help="2-D-grid SUMMA instead of 1-D")
    ap.add_argument("--grid", default=None,
                    help="SUMMA grid rows x cols (default: squarest over the devices)")
    ap.add_argument("--blocking", action="store_true",
                    help="SUMMA: blocking ring shifts instead of the double-buffered default")
    ap.add_argument("--uneven", action="store_true",
                    help="SUMMA: bump every dim by +1 so nothing divides the "
                         "grid and the ragged (v-collective) path runs")
    args = ap.parse_args()
    # CPU bring-up: 8 fake host devices (the flag is read when the backend
    # starts, below); a TPU host uses its own devices
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    grid = (tuple(int(x) for x in args.grid.split("x")) if args.grid
            else default_grid(len(jax.devices())))

    ni, nj, nk = DATASETS[args.dataset]
    configs = [args.majors] if args.majors else LAYOUT_CONFIGS
    for majors in configs:
        if args.summa and args.uneven:
            C, ref = run_ragged_summa_gemm(ni=ni + 1, nj=nj + 1, nk=nk + 1,
                                           majors=majors, grid=grid,
                                           double_buffer=not args.blocking, verbose=True)
        elif args.summa:
            C, ref = run_summa_gemm(ni=ni, nj=nj, nk=nk, majors=majors, grid=grid,
                                    double_buffer=not args.blocking, verbose=True)
        else:
            C, ref = run_distributed_gemm(ni=ni, nj=nj, nk=nk, majors=majors, ranks=args.ranks, verbose=True)
        np.testing.assert_allclose(C, ref, rtol=1e-3, atol=1e-3)
    print("all configurations validated")


if __name__ == "__main__":
    main()

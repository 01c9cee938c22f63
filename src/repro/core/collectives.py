"""Layout-agnostic collective operations (paper §4.2) on a JAX mesh.

The signature of every operation takes *bags* (buffer + layout) and a
:class:`DistTraverser` — never a PartitionSpec or an MPI datatype.  The
layout transformation required by differing endpoint layouts is derived
automatically (``relayout_plan``) and executes inside the same XLA program as
the data movement, which is the TPU analogue of MPI performing the transform
inside the transfer.

Index-space type checks (paper: "the index space of the distributed structure
has to be a subspace of the root structure index space, and the difference
has to be covered by the dimension bound to the communicator") happen at
trace time and raise :class:`LayoutError`.

A :class:`DistBag` may be distributed over *several* ranking dimensions at
once (a communicator grid, e.g. ``('rows', 'cols')`` — the paper's
``MPI_Cart_create``).  Every collective then names the ranking dimension it
operates along; the remaining grid dimensions act as independent
sub-communicators, exactly like ``MPI_Comm_split`` keyed by the other grid
coordinates.

Non-blocking collectives
------------------------
Every reduce collective has a non-blocking twin — ``all_gather_start``,
``all_reduce_start``, ``reduce_scatter_start``, ``all_to_all_start`` — the
``MPI_Iallgather``/``Iallreduce``/``Ireduce_scatter``/``Ialltoall``
analogues.  The ``*_start`` form *issues* the relayout-fused operation and
returns a :class:`repro.core.request.Pending` immediately; compute traced
between start and :meth:`~repro.core.request.Pending.wait` carries no data
dependence on the collective, so the XLA scheduler may overlap the two.  The
blocking collectives are literally ``*_start(...).wait()`` — one
issue/complete code path, so the two forms are bit-identical by
construction.

Ragged distribution (the MPI v-collectives)
-------------------------------------------
MPI's answer to non-uniform buffers is the ``v`` family —
``MPI_Scatterv``/``Gatherv``/``Allgatherv``/``Alltoallv`` — whose
counts/displacements arrays describe a different extent per rank.  The
layout-agnostic analogue here is :attr:`DistBag.extents`: per-rank *valid*
sizes along tiled dims, carried next to a homogeneous **padded capacity**
tile layout.  Valid elements occupy the leading slice along each ragged dim;
the rest of the buffer is zero padding that rides the wire but never enters
logical results (``tile()`` returns the valid view).  The extents table is
static (known at trace time), so every per-rank transform lowers to static
slices inside one XLA program — no dynamic shapes.

The extents <-> counts/displacements mapping: ``extents[r][dim]`` is rank
``r``'s *count* along ``dim``; the displacement of rank ``r`` is the prefix
sum of the preceding ranks' extents along the rank dim that owns ``dim``
(:func:`repro.core.dims.ragged_split` builds balanced tables).

Correspondence table:

=======================  ====================================================
MPI                      repro.core
=======================  ====================================================
``MPI_Scatterv``         :func:`scatterv_bag` (extents = counts)
``MPI_Gatherv``          :func:`gatherv_bag`
``MPI_Allgatherv``       :func:`all_gatherv_bag` / ``all_gatherv_dist``
``MPI_Iallgatherv``      :func:`all_gatherv_start`
``MPI_Alltoallv``        :func:`all_to_allv_bag`
``MPI_Ialltoallv``       :func:`all_to_allv_start`
``Reduce_scatter`` (v)   :func:`reduce_scatterv_bag` / ``_start``
``MPI_Ireduce_scatter``  :func:`shard_reduce_scatterv_start` (inside
(flat shard form)        ``shard_map``: flat padded buffer + recvcounts
                         extents — the ZeRO gradient-bucket leg)
``MPI_Iallgatherv``      :func:`shard_all_gatherv_start` (inside
(flat shard form)        ``shard_map``: the param-prefetch return leg)
=======================  ====================================================

Every v-collective shares the ``_issue_*``/:class:`Pending` path with the
dense forms: the blocking call is ``*_start(...).wait()`` by construction.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .bag import Bag
from .dims import LayoutError, check_same_space, prod
from .layout import Axis, Layout
from .relayout import check_ragged_dims, relayout
from .request import Pending, wait_all
from .dist import DistTraverser

__all__ = [
    "DistBag",
    "Pending",
    "wait_all",
    "scatter",
    "gather",
    "broadcast",
    "all_gather_bag",
    "all_gather_dist",
    "all_reduce_bag",
    "reduce_scatter_bag",
    "all_to_all_bag",
    "all_gather_start",
    "all_reduce_start",
    "reduce_scatter_start",
    "all_to_all_start",
    "grid_extents",
    "scatterv_bag",
    "gatherv_bag",
    "all_gatherv_bag",
    "all_gatherv_dist",
    "all_gatherv_start",
    "all_to_allv_bag",
    "all_to_allv_start",
    "reduce_scatterv_bag",
    "reduce_scatterv_start",
    "shard_reduce_scatterv_start",
    "shard_all_gatherv_start",
    "reduce_identity",
    "dist_full",
    "dist_sharding",
    "rank_map",
]

_REDUCERS = {
    "add": jax.lax.psum,
    "mean": jax.lax.psum,
    "max": jax.lax.pmax,
    "min": jax.lax.pmin,
}


def reduce_identity(op: str, dtype):
    """The identity element of reduce op ``op`` for ``dtype`` — the value
    padding must carry so it never enters a reduction's result: 0 for
    ``add``/``mean``, ``-inf``/``+inf`` (or the integer extremes) for
    ``max``/``min``.  Zero padding is *only* the identity of add/mean;
    capacity fill for a max/min pipeline should use this instead
    (``scatterv_bag(..., pad_value=reduce_identity(op, dtype))``)."""
    _resolve_reduce(op)
    dt = np.dtype(dtype)
    if op in ("add", "mean"):
        return dt.type(0)
    if dt.kind == "f":
        return dt.type(-np.inf if op == "max" else np.inf)
    if dt.kind in "iu":
        info = np.iinfo(dt)
        return dt.type(info.min if op == "max" else info.max)
    raise LayoutError(f"reduce_identity: no {op!r} identity for dtype {dt}")


@dataclasses.dataclass(frozen=True)
class DistBag:
    """A bag scattered over the ranks of a DistTraverser.

    ``data`` is the *global* array of shape ``(R1, ..., Rk, *tile_shape)``
    whose leading axes (one per ranking dim) are sharded over the
    communicator's mesh axes — each device holds exactly its tile, already in
    ``tile_layout``.
    """

    data: Any
    tile_layout: Layout
    dt: DistTraverser
    rank_dims: tuple[str, ...]
    # per-rank tile layouts for heterogeneous bags (e.g. an all_gather whose
    # ranks declared different destination layouts, or a send_recv receiver
    # keeping its declared layout); when set, ``tile(r)`` views rank r's
    # buffer through its own layout (reshaping the homogeneous stacked slot
    # when the per-rank physical shape differs — same element count).
    tile_layouts: tuple[Layout, ...] | None = None
    # per-rank valid extents for *ragged* bags (the MPI v-collective
    # counts): a tuple over flat ranks (row-major over ``grid_shape``) of
    # ``((dim, valid_extent), ...)`` pairs.  The tile buffer keeps the
    # homogeneous padded *capacity* shape of ``tile_layout``; valid elements
    # occupy the leading slice along each ragged dim and the rest is zero
    # padding.  None = dense (every tile full).
    extents: tuple[tuple[tuple[str, int], ...], ...] | None = None

    def __post_init__(self):
        if isinstance(self.rank_dims, str):  # tolerate the pre-grid call style
            object.__setattr__(self, "rank_dims", (self.rank_dims,))
        if self.extents is not None and len(self.extents) != self.comm_size:
            raise LayoutError(
                f"extents table has {len(self.extents)} entries for comm size {self.comm_size}"
            )

    @property
    def rank_dim(self) -> str:
        """The single ranking dim (1-D communicators; errors on grids)."""
        if len(self.rank_dims) != 1:
            raise LayoutError(
                f"DistBag spans communicator grid {self.rank_dims}; name the dim explicitly"
            )
        return self.rank_dims[0]

    @property
    def comm_size(self) -> int:
        return prod(self.dt.comm_size(d) for d in self.rank_dims)

    @property
    def grid_shape(self) -> tuple[int, ...]:
        return tuple(self.dt.comm_size(d) for d in self.rank_dims)

    # -- ragged queries ---------------------------------------------------------
    @property
    def is_ragged(self) -> bool:
        return self.extents is not None

    def ragged_dims(self) -> tuple[str, ...]:
        """Dims with per-rank valid extents (empty for dense bags)."""
        if self.extents is None:
            return ()
        seen: dict[str, None] = {}
        for entry in self.extents:
            for d, _ in entry:
                seen[d] = None
        return tuple(seen)

    def flat_rank(self, rank: int | Sequence[int]) -> int:
        """Row-major flat index of a grid coordinate (``MPI_Cart_rank``)."""
        coords = (rank,) if isinstance(rank, int) else tuple(rank)
        if len(coords) != len(self.rank_dims):
            raise LayoutError(f"rank {rank!r} does not address grid {self.rank_dims}")
        flat = 0
        for c, s in zip(coords, self.grid_shape):
            if not 0 <= c < s:
                raise LayoutError(f"rank {rank!r} out of range for grid {self.grid_shape}")
            flat = flat * s + c
        return flat

    def rank_extents(self, rank: int | Sequence[int]) -> dict[str, int]:
        """Rank ``rank``'s valid extents (full capacity space for dense bags)."""
        space = dict(self.tile_layout.index_space())
        if self.extents is not None:
            space.update(dict(self.extents[self.flat_rank(rank)]))
        return space

    def tile_padded_bytes(self) -> int:
        """Bytes of one padded capacity tile — the *wire* size of a transfer."""
        return self.tile_layout.size_bytes()

    def valid_bytes(self) -> int:
        """Total valid payload bytes across all ranks (excludes padding)."""
        import numpy as np

        item = np.dtype(self.tile_layout.dtype).itemsize
        if self.extents is None:
            return self.comm_size * self.tile_padded_bytes()
        total = 0
        for flat in range(self.comm_size):
            space = dict(self.tile_layout.index_space())
            space.update(dict(self.extents[flat]))
            total += prod(space.values()) * item
        return total

    def padded_bytes(self) -> int:
        """Total allocated bytes across all ranks (capacity x comm size)."""
        return self.comm_size * self.tile_padded_bytes()

    def tile(self, rank: int | Sequence[int]) -> Bag:
        """Host-side view of one rank's tile (reference semantics, tests).

        ``rank`` is an int for 1-D communicators, a coordinate tuple on
        grids.  Heterogeneous bags (``tile_layouts``) view the slot through
        the rank's own layout; ragged bags return the *valid* leading region
        only (the padding never appears in logical results).
        """
        coords = (rank,) if isinstance(rank, int) else tuple(rank)
        flat = self.flat_rank(coords)
        layout = self.tile_layout
        if self.tile_layouts is not None:
            layout = self.tile_layouts[flat]
        arr = self.data[coords]
        if tuple(arr.shape) != layout.shape:
            if prod(arr.shape) != prod(layout.shape):
                raise LayoutError(
                    f"tile({rank!r}): slot shape {tuple(arr.shape)} cannot hold "
                    f"layout shape {layout.shape}"
                )
            arr = arr.reshape(layout.shape)
        b = Bag(arr, layout)
        if self.extents is not None and self.extents[flat]:
            b = b.valid_view(dict(self.extents[flat]))
        return b

    def with_data(self, data) -> "DistBag":
        return dataclasses.replace(self, data=data)


# -----------------------------------------------------------------------------
# shared plumbing
# -----------------------------------------------------------------------------
def _as_rank_dims(dt: DistTraverser, rank_dim) -> tuple[str, ...]:
    if rank_dim is None:
        return dt.rank_dims
    if isinstance(rank_dim, str):
        return (rank_dim,)
    return tuple(rank_dim)


def _transfer_layout(tile: Layout, leaves: tuple[tuple[str, int], ...]) -> Layout:
    """Tile layout with the rank-dim leaves prepended as outermost axes."""
    for leaf, _ in leaves:
        if any(a.name == leaf for a in tile.axes):
            raise LayoutError(f"rank leaf dim {leaf!r} collides with tile axis")
    axes = tuple(Axis(leaf, s) for leaf, s in leaves) + tile.axes
    dim_map = tuple((leaf, (leaf,)) for leaf, _ in leaves) + tile.dim_map
    return Layout(tile.dtype, axes, dim_map)


def _all_leaves(dt: DistTraverser, rank_dims: Sequence[str]) -> tuple[tuple[str, int], ...]:
    out: tuple[tuple[str, int], ...] = ()
    for d in rank_dims:
        out += dt.rank_leaves(d)
    return out


def _check_scatter_spaces(
    root: Layout, tile: Layout, dt: DistTraverser, rank_dims: Sequence[str]
) -> None:
    leaves = _all_leaves(dt, rank_dims)
    expected = dict(tile.index_space())
    for leaf, size in leaves:
        if leaf in expected:
            raise LayoutError(f"rank leaf {leaf!r} already in tile index space")
        expected[leaf] = size
    check_same_space(root.index_space(), expected, what="scatter(root, tile x ranks)")
    # and the traverser must agree with both (it was built from the structures)
    trav_space = dt.index_space()
    for d, s in tile.index_space().items():
        if d in trav_space and trav_space[d] != s:
            raise LayoutError(f"traverser dim {d!r} extent {trav_space[d]} != tile {s}")


def _grid_spec(dt: DistTraverser, rank_dims: Sequence[str], tile_ndim: int) -> P:
    entries = []
    for d in rank_dims:
        axs = dt.rank_mesh_axes(d)
        entries.append(axs if len(axs) > 1 else axs[0])
    return P(*entries, *([None] * tile_ndim))


def _lead_shape(dt: DistTraverser, rank_dims: Sequence[str]) -> tuple[int, ...]:
    return tuple(dt.comm_size(d) for d in rank_dims)


def grid_extents(
    dt: DistTraverser,
    rank_dims: Sequence[str],
    ragged: Mapping[str, tuple[str, Sequence[int]]],
) -> tuple[tuple[tuple[str, int], ...], ...]:
    """Build a flat-rank extents table from per-grid-dim ragged specs.

    ``ragged`` maps a rank dim to ``(tile dim, per-coordinate valid
    extents)`` — the extents <-> counts mapping of the MPI v-collectives: the
    extent list is the counts array along that grid dim, the displacements
    are its prefix sums.  Rank dims absent from ``ragged`` are dense.  The
    result is indexed row-major over the grid shape, like
    ``DistBag.tile_layouts``.
    """
    for rd in ragged:
        if rd not in rank_dims:
            raise LayoutError(f"grid_extents: {rd!r} is not a rank dim (have {tuple(rank_dims)})")
    seen_dims = [dim for dim, _ in ragged.values()]
    if len(set(seen_dims)) != len(seen_dims):
        raise LayoutError(f"grid_extents: a tile dim is ragged over two rank dims: {seen_dims}")
    shape = [dt.comm_size(d) for d in rank_dims]
    for rd, (dim, exts) in ragged.items():
        if len(exts) != dt.comm_size(rd):
            raise LayoutError(
                f"grid_extents: {len(exts)} extents for {rd!r} of comm size {dt.comm_size(rd)}"
            )
    out = []
    for coords in itertools.product(*(range(s) for s in shape)):
        entry = []
        for rd, c in zip(rank_dims, coords):
            if rd in ragged:
                dim, exts = ragged[rd]
                entry.append((dim, int(exts[c])))
        out.append(tuple(entry))
    return tuple(out)


def _ragged_owner_candidates(dist: DistBag) -> dict[str, list[int]]:
    """For each ragged dim, the rank-dim positions its extents are
    *separable* along (depend only on that position's coordinate) — the
    inverse of :func:`grid_extents`.  Uniform extents are separable along
    every position, so callers disambiguate with the root-space sums
    (:func:`_match_ragged_owners`).  Raises when an extents table is not a
    per-grid-dim product (hand-built tables may couple dims arbitrarily —
    those bags still work for p2p/tile views, but not for the gather-side
    displacement arithmetic that needs per-coordinate counts).
    """
    assert dist.extents is not None
    shape = dist.grid_shape
    coords_list = list(itertools.product(*(range(s) for s in shape)))
    by_dim: dict[str, dict[tuple, int]] = {}
    for coords, entry in zip(coords_list, dist.extents):
        for d, e in entry:
            by_dim.setdefault(d, {})[coords] = e
    out: dict[str, list[int]] = {}
    for d, table in by_dim.items():
        if len(table) != len(coords_list):
            raise LayoutError(f"ragged dim {d!r} has extents on only some ranks")
        cands = []
        for p in range(len(shape)):
            per_coord: dict[int, int] = {}
            if all(per_coord.setdefault(coords[p], e) == e for coords, e in table.items()):
                cands.append(p)
        if not cands:
            raise LayoutError(
                f"ragged dim {d!r}: extents do not vary along a single rank dim "
                f"(not a grid_extents-style table)"
            )
        out[d] = cands
    return out


def _ragged_owners(dist: DistBag) -> dict[str, int]:
    """Unambiguous {ragged dim -> rank-dim position} map for 1-D bags and
    uniquely-separable tables (all_gatherv/all_to_allv); grid gathers with
    possibly-uniform dims go through :func:`_match_ragged_owners` instead."""
    owners = {}
    for d, cands in _ragged_owner_candidates(dist).items():
        owners[d] = cands[0]
    return owners


def _match_ragged_owners(dist: DistBag, root_space: Mapping[str, int]) -> dict[str, int]:
    """Assign each ragged dim to the rank dim that tiles it, as a perfect
    matching over grid positions.

    Candidates come from separability; the root-space sums disambiguate
    dims whose extents are uniform (separable along *every* position): the
    owning position is the one whose per-coordinate extents sum to the root
    extent.  A small backtracking search finds the permutation (grids are
    2-3 dims, so this is trivial).
    """
    cand_sets = _ragged_owner_candidates(dist)
    shape = dist.grid_shape
    filtered: dict[str, list[int]] = {}
    for d, cands in cand_sets.items():
        keep = []
        for p in cands:
            if sum(_dim_extent_list(dist, d, p)) == root_space.get(d):
                keep.append(p)
        if not keep:
            raise LayoutError(
                f"gatherv: extents of {d!r} sum to none of the candidate rank "
                f"dims' totals (root extent {root_space.get(d)})"
            )
        filtered[d] = keep
    dims = sorted(filtered, key=lambda d: len(filtered[d]))
    if len(dims) != len(shape):
        raise LayoutError(
            f"gatherv: ragged dims {dims} must cover every rank dim "
            f"{dist.rank_dims} exactly once"
        )

    def assign(i: int, used: set) -> dict[str, int] | None:
        if i == len(dims):
            return {}
        d = dims[i]
        for p in filtered[d]:
            if p in used:
                continue
            rest = assign(i + 1, used | {p})
            if rest is not None:
                rest[d] = p
                return rest
        return None

    owners = assign(0, set())
    if owners is None:
        raise LayoutError(
            f"gatherv: no one-to-one assignment of ragged dims {dims} to rank "
            f"dims {dist.rank_dims} matches the root extents"
        )
    return owners


def _dim_extent_list(dist: DistBag, dim: str, pos: int) -> list[int]:
    """Per-coordinate extents of ``dim`` along rank-dim position ``pos``."""
    shape = dist.grid_shape
    out = []
    for c in range(shape[pos]):
        coords = [0] * len(shape)
        coords[pos] = c
        out.append(dist.rank_extents(tuple(coords))[dim])
    return out


def _require_dense(dist: DistBag, what: str, dims: Sequence[str] = ()) -> None:
    """Trace-time guard: the dense collectives cannot reorganize ragged dims
    (their counts differ per rank) — direct the caller to the v-form."""
    if dist.extents is None:
        return
    bad = set(dist.ragged_dims()) & set(dims) if dims else set(dist.ragged_dims())
    if bad:
        raise LayoutError(
            f"{what}: bag is ragged along {sorted(bad)}; use the v-collective "
            "(scatterv/gatherv/all_gatherv/all_to_allv/reduce_scatterv) instead"
        )


def _uniform_extents_along(dist: DistBag, rank_dim: str, what: str):
    """Extents carried through a collective that reduces over ``rank_dim``:
    every member of each ``rank_dim`` sub-communicator must agree (an
    elementwise reduce across differing valid regions is ill-typed)."""
    if dist.extents is None:
        return None
    pos = dist.rank_dims.index(rank_dim)
    shape = dist.grid_shape
    out = list(dist.extents)
    for coords in itertools.product(*(range(s) for s in shape)):
        if coords[pos] == 0:
            continue
        base = list(coords)
        base[pos] = 0
        if dist.extents[dist.flat_rank(coords)] != dist.extents[dist.flat_rank(tuple(base))]:
            raise LayoutError(
                f"{what}: extents differ across the {rank_dim!r} communicator "
                "(elementwise reduce over ragged tiles is ill-typed)"
            )
    return tuple(out)


def _flat_rank(dt: DistTraverser, rank_dim: str):
    """Traced communicator rank along one ranking dim (MPI_Comm_rank)."""
    rank = 0
    for ax in dt.rank_mesh_axes(rank_dim):
        rank = rank * dt.mesh.shape[ax] + jax.lax.axis_index(ax)
    return rank


def _reduce_axes(dt: DistTraverser, rank_dim: str):
    axs = dt.rank_mesh_axes(rank_dim)
    return axs if len(axs) > 1 else axs[0]


def _shard_collective(
    dist: DistBag, out_layout: Layout, tile_fn: Callable[[Any], Any]
) -> DistBag:
    """Run ``tile_fn(local_tile) -> out_tile`` on every rank inside shard_map."""
    dt, rank_dims = dist.dt, dist.rank_dims
    lead = len(rank_dims)
    in_spec = _grid_spec(dt, rank_dims, dist.tile_layout.ndim)
    out_spec = _grid_spec(dt, rank_dims, out_layout.ndim)

    def shard_fn(x):
        t = x.reshape(dist.tile_layout.shape)
        out = tile_fn(t)
        return out.reshape((1,) * lead + out_layout.shape)

    mapped = shard_map(shard_fn, mesh=dt.mesh, in_specs=(in_spec,), out_specs=out_spec)(
        dist.data
    )
    return DistBag(mapped, out_layout, dt, rank_dims)


# -----------------------------------------------------------------------------
# root <-> tiles (scatter / gather / broadcast)
# -----------------------------------------------------------------------------
def scatter(
    root: Bag,
    tile_layout: Layout,
    dt: DistTraverser,
    rank_dim: str | Sequence[str] | None = None,
) -> DistBag:
    """Scatter ``root`` so each rank holds one tile in ``tile_layout``.

    Works for arbitrary (root layout, tile layout) pairs over the same logical
    space — including different dimension orders and blockings on the two
    sides; the relayout is fused into the scatter by XLA.  With a grid
    traverser, ``rank_dim`` may list several ranking dims (default: all of
    them) and the tiles distribute over the full communicator grid.
    """
    rank_dims = _as_rank_dims(dt, rank_dim)
    _check_scatter_spaces(root.layout, tile_layout, dt, rank_dims)
    leaves = _all_leaves(dt, rank_dims)
    xfer = _transfer_layout(tile_layout, leaves)
    arr = relayout(root.data, root.layout, xfer)
    arr = arr.reshape(_lead_shape(dt, rank_dims) + tile_layout.shape)
    sharding = NamedSharding(dt.mesh, _grid_spec(dt, rank_dims, tile_layout.ndim))
    arr = jax.device_put(arr, sharding)
    return DistBag(arr, tile_layout, dt, rank_dims)


def gather(dist: DistBag, root_layout: Layout) -> Bag:
    """Gather the tiles back into a root bag with ``root_layout`` (any layout
    spanning the same global logical space)."""
    _require_dense(dist, "gather (use gatherv_bag for ragged tiles)")
    _check_scatter_spaces(root_layout, dist.tile_layout, dist.dt, dist.rank_dims)
    leaves = _all_leaves(dist.dt, dist.rank_dims)
    xfer = _transfer_layout(dist.tile_layout, leaves)
    arr = dist.data.reshape(xfer.shape)
    out = relayout(arr, xfer, root_layout)
    out = jax.device_put(out, NamedSharding(dist.dt.mesh, P()))  # replicated root
    return Bag(out, root_layout)


def broadcast(b: Bag, dt: DistTraverser, dst_layout: Layout | None = None) -> Bag:
    """Replicate a bag to every rank, relayouting if the destination layout
    differs (the paper's broadcast between column-major and row-major)."""
    data = b.data
    layout = b.layout
    if dst_layout is not None:
        check_same_space(layout.index_space(), dst_layout.index_space(), what="broadcast")
        data = relayout(data, layout, dst_layout)
        layout = dst_layout
    data = jax.device_put(data, NamedSharding(dt.mesh, P()))
    return Bag(data, layout)


def _issue_all_gather(
    dist: DistBag,
    root_layout: Layout | Sequence[Layout],
    rank_dims: Sequence[str],
) -> DistBag:
    """Issue the true ``jax.lax.all_gather`` along ``rank_dims`` (shared by the
    blocking and non-blocking entry points).

    Unlike :func:`gather`, which assembles the root structure through the
    host-visible replicated array, this moves the tiles with the on-device
    all-gather and applies each rank's *destination-layout* transform inside
    the same XLA program as the transfer — the ``MPI_Allgather`` whose receive
    datatype is honored per rank.  ``root_layout`` may be a single layout
    (every rank declares the same destination) or a sequence of per-rank
    layouts over the same index space and physical shape (1-D communicators
    only); the per-rank transform is selected by the communicator rank.
    """
    dt = dist.dt
    _require_dense(dist, "all_gather (use all_gatherv_bag for ragged tiles)")
    layouts = (
        [root_layout] if isinstance(root_layout, Layout) else list(root_layout)
    )
    if len(layouts) > 1 and len(rank_dims) != 1:
        raise LayoutError("per-rank all_gather layouts need a 1-D communicator")
    R_total = prod(dt.comm_size(d) for d in rank_dims)
    if len(layouts) not in (1, R_total):
        raise LayoutError(
            f"all_gather: got {len(layouts)} destination layouts for comm size {R_total}"
        )
    for l in layouts:
        _check_scatter_spaces(l, dist.tile_layout, dt, rank_dims)
        if l.shape != layouts[0].shape:
            raise LayoutError(
                f"per-rank all_gather layouts must share one physical shape: "
                f"{l.shape} != {layouts[0].shape}"
            )
    leaves = _all_leaves(dt, rank_dims)
    xfer = _transfer_layout(dist.tile_layout, leaves)
    axes: tuple[str, ...] = ()
    for d in rank_dims:
        axes += tuple(dt.rank_mesh_axes(d))

    def tile_fn(t):
        g = jax.lax.all_gather(t, axes, axis=0, tiled=False)
        g = g.reshape(xfer.shape)
        if len(layouts) == 1:
            return relayout(g, xfer, layouts[0])
        return jax.lax.switch(
            _flat_rank(dt, rank_dims[0]),
            [lambda x, _l=l: relayout(x, xfer, _l) for l in layouts],
            g,
        )

    # keep the bag's full grid distribution: ranks outside ``rank_dims``
    # still hold independent (sub-communicator) results, ranks inside hold
    # replicated copies — exactly MPI_Allgather's per-rank receive buffers.
    out = _shard_collective(dist, layouts[0], tile_fn)
    if len(layouts) > 1:
        # tile_layouts is indexed by the *full-grid* flat rank; the declared
        # layouts key on the gathered (1-D) communicator dim only, so expand
        # them across the other grid coordinates (every sub-communicator of
        # the grid sees the same per-rank declarations)
        pos = out.rank_dims.index(rank_dims[0])
        full = tuple(
            layouts[coords[pos]]
            for coords in itertools.product(*(range(s) for s in out.grid_shape))
        )
        out = dataclasses.replace(out, tile_layouts=full)
    return out


def all_gather_start(
    dist: DistBag,
    root_layout: Layout | Sequence[Layout],
    *,
    rank_dim: str | Sequence[str] | None = None,
) -> Pending:
    """Non-blocking all-gather (``MPI_Iallgather``): issue the transfer and
    return a :class:`Pending` whose :meth:`~Pending.wait` hands back a
    :class:`DistBag` in which every rank of the ``rank_dim`` communicator
    holds the full gathered structure in its destination layout."""
    rank_dims = _as_rank_dims(dist.dt, rank_dim) if rank_dim is not None else dist.rank_dims
    for d in rank_dims:
        if d not in dist.rank_dims:
            raise LayoutError(f"bag is not distributed over {d!r} (has {dist.rank_dims})")
    return Pending(_issue_all_gather(dist, root_layout, rank_dims), op="all_gather")


def all_gather_dist(
    dist: DistBag,
    root_layout: Layout | Sequence[Layout],
    *,
    rank_dim: str | Sequence[str] | None = None,
) -> DistBag:
    """Blocking all-gather returning the per-rank receive buffers as a
    :class:`DistBag` (``all_gather_start(...).wait()``)."""
    return all_gather_start(dist, root_layout, rank_dim=rank_dim).wait()


def all_gather_bag(dist: DistBag, root_layout: Layout) -> Bag:
    """Every rank ends with the full structure in ``root_layout``.

    Implemented over the true on-device ``jax.lax.all_gather`` (not the
    host-root :func:`gather`, which remains available as the reference
    oracle): the tiles are gathered and relayouted inside one XLA program,
    and the replicated result is returned as a root :class:`Bag`.
    """
    db = all_gather_dist(dist, root_layout)
    first = db.data[(0,) * len(dist.rank_dims)]  # every rank holds a full copy
    out = jax.device_put(first, NamedSharding(dist.dt.mesh, P()))
    return Bag(out, root_layout)


def dist_sharding(
    dt: DistTraverser,
    tile_layout: Layout,
    rank_dim: str | Sequence[str] | None = None,
) -> NamedSharding:
    """The NamedSharding of a DistBag's stacked global array — for building
    jit'able programs over ``DistBag.data`` (``in_shardings`` of a traced
    SUMMA ring, dry-run lowering from ShapeDtypeStructs, ...)."""
    rank_dims = _as_rank_dims(dt, rank_dim)
    return NamedSharding(dt.mesh, _grid_spec(dt, rank_dims, tile_layout.ndim))


def dist_full(
    dt: DistTraverser,
    tile_layout: Layout,
    *,
    fill: Any = 0.0,
    rank_dim: str | Sequence[str] | None = None,
) -> DistBag:
    """Allocate a DistBag with every tile filled with ``fill`` (the
    distributed counterpart of :func:`repro.core.bag`)."""
    rank_dims = _as_rank_dims(dt, rank_dim)
    shape = _lead_shape(dt, rank_dims) + tile_layout.shape
    arr = jnp.full(shape, fill, dtype=tile_layout.dtype)
    sharding = NamedSharding(dt.mesh, _grid_spec(dt, rank_dims, tile_layout.ndim))
    return DistBag(jax.device_put(arr, sharding), tile_layout, dt, rank_dims)


# -----------------------------------------------------------------------------
# reduce collectives (MPI_Allreduce / MPI_Reduce_scatter / MPI_Alltoall)
# -----------------------------------------------------------------------------
def _resolve_reduce(op: str):
    if op not in _REDUCERS:
        raise LayoutError(f"unknown reduce op {op!r} (have {sorted(_REDUCERS)})")
    return _REDUCERS[op]


def _issue_all_reduce(
    dist: DistBag,
    op: str,
    rank_dim: str | None,
    out_tile_layout: Layout | None,
) -> DistBag:
    """Issue the relayout-fused all-reduce (shared by the blocking and
    non-blocking entry points)."""
    rank_dim = rank_dim or dist.rank_dims[0]
    if rank_dim not in dist.rank_dims:
        raise LayoutError(f"bag is not distributed over {rank_dim!r} (has {dist.rank_dims})")
    out_layout = out_tile_layout or dist.tile_layout
    check_same_space(
        dist.tile_layout.index_space(), out_layout.index_space(), what="all_reduce"
    )
    carried = _uniform_extents_along(dist, rank_dim, "all_reduce")
    if carried is not None:
        check_ragged_dims(dist.tile_layout, out_layout, dist.ragged_dims(), what="all_reduce")
    reducer = _resolve_reduce(op)
    axes = _reduce_axes(dist.dt, rank_dim)
    R = dist.dt.comm_size(rank_dim)

    def tile_fn(t):
        red = reducer(t, axes)
        if op == "mean":
            red = red / R
        return relayout(red, dist.tile_layout, out_layout)

    out = _shard_collective(dist, out_layout, tile_fn)
    if carried is not None:
        out = dataclasses.replace(out, extents=carried)
    return out


def all_reduce_start(
    dist: DistBag,
    op: str = "add",
    *,
    rank_dim: str | None = None,
    out_tile_layout: Layout | None = None,
) -> Pending:
    """Non-blocking all-reduce (``MPI_Iallreduce``): issue the reduction and
    return a :class:`Pending` immediately."""
    return Pending(_issue_all_reduce(dist, op, rank_dim, out_tile_layout), op="all_reduce")


def all_reduce_bag(
    dist: DistBag,
    op: str = "add",
    *,
    rank_dim: str | None = None,
    out_tile_layout: Layout | None = None,
) -> DistBag:
    """Reduce tiles elementwise across the ``rank_dim`` communicator; every
    rank of that communicator ends with the same reduced tile (MPI_Allreduce).

    ``out_tile_layout`` may differ from the input tile layout — the relayout
    fuses into the same XLA program as the reduction.
    """
    return all_reduce_start(
        dist, op, rank_dim=rank_dim, out_tile_layout=out_tile_layout
    ).wait()


def _fresh_axis_name(layout: Layout, base: str) -> str:
    name = base
    while any(a.name == name for a in layout.axes) or any(d == name for d, _ in layout.dim_map):
        name += "_"
    return name


def _block_over(layout: Layout, dim: str, name: str, R: int) -> Layout:
    """``layout`` with a new outermost axis of size ``R`` enumerating the R
    outer blocks of logical ``dim`` (so the result spans ``dim`` extent * R)."""
    axes = (Axis(name, R),) + layout.axes
    dim_map = tuple(
        (d, ((name,) + axs) if d == dim else axs) for d, axs in layout.dim_map
    )
    return Layout(layout.dtype, axes, dim_map)


def _issue_reduce_scatter(
    dist: DistBag,
    out_tile_layout: Layout,
    scatter_dim: str | None,
    op: str,
    rank_dim: str | None,
) -> DistBag:
    """Issue the relayout-fused reduce-scatter (shared by the blocking and
    non-blocking entry points)."""
    _require_dense(dist, "reduce_scatter (use reduce_scatterv_bag for ragged tiles)")
    rank_dim = rank_dim or dist.rank_dims[0]
    if rank_dim not in dist.rank_dims:
        raise LayoutError(f"bag is not distributed over {rank_dim!r} (has {dist.rank_dims})")
    R = dist.dt.comm_size(rank_dim)
    in_space = dist.tile_layout.index_space()
    out_space = out_tile_layout.index_space()
    if scatter_dim is None:
        cands = [
            d for d, s in in_space.items() if out_space.get(d, -1) * R == s
        ]
        if len(cands) != 1:
            raise LayoutError(
                f"cannot infer scatter dim from {in_space} -> {out_space} "
                f"with comm size {R} (candidates: {cands}); pass scatter_dim"
            )
        (scatter_dim,) = cands
    expected = dict(out_space)
    if scatter_dim not in expected:
        raise LayoutError(f"scatter dim {scatter_dim!r} missing from output space {out_space}")
    expected[scatter_dim] = expected[scatter_dim] * R
    check_same_space(in_space, expected, what=f"reduce_scatter over {scatter_dim!r}")
    _resolve_reduce(op)
    blk = _fresh_axis_name(out_tile_layout, "__rs")
    mid = _block_over(out_tile_layout, scatter_dim, blk, R)
    axes = _reduce_axes(dist.dt, rank_dim)

    def tile_fn(t):
        x = relayout(t, dist.tile_layout, mid)  # (R, *out_shape), block r = rank r's part
        if op in ("add", "mean"):
            y = jax.lax.psum_scatter(x, axes, scatter_dimension=0, tiled=False)
            if op == "mean":
                y = y / R
        else:
            # direct psum_scatter-style route for max/min: exchange the R
            # stacked blocks so each rank holds every contribution of its
            # own block, then reduce locally — 1/R the wire bytes of the
            # old allreduce-then-slice form.
            y = jax.lax.all_to_all(x, axes, split_axis=0, concat_axis=0, tiled=False)
            y = (jnp.max if op == "max" else jnp.min)(y, axis=0)
        return y

    return _shard_collective(dist, out_tile_layout, tile_fn)


def reduce_scatter_start(
    dist: DistBag,
    out_tile_layout: Layout,
    *,
    scatter_dim: str | None = None,
    op: str = "add",
    rank_dim: str | None = None,
) -> Pending:
    """Non-blocking reduce-scatter (``MPI_Ireduce_scatter``): issue the
    reduce+scatter and return a :class:`Pending` immediately."""
    return Pending(
        _issue_reduce_scatter(dist, out_tile_layout, scatter_dim, op, rank_dim),
        op="reduce_scatter",
    )


def reduce_scatter_bag(
    dist: DistBag,
    out_tile_layout: Layout,
    *,
    scatter_dim: str | None = None,
    op: str = "add",
    rank_dim: str | None = None,
) -> DistBag:
    """Elementwise-reduce tiles across the ``rank_dim`` communicator, then
    scatter the result: communicator rank ``r`` keeps logical block ``r`` of
    ``scatter_dim`` (MPI_Reduce_scatter_block).

    The output tile layout is free — rank ``r``'s block lands directly in
    ``out_tile_layout``, with the transform fused into the transfer.  Index
    spaces are checked at trace time: the output space must equal the input
    space except that ``scatter_dim``'s extent shrinks by the communicator
    size.
    """
    return reduce_scatter_start(
        dist, out_tile_layout, scatter_dim=scatter_dim, op=op, rank_dim=rank_dim
    ).wait()


def _dense_layout(dtype, items: Sequence[tuple[str, int]]) -> Layout:
    """Row-major layout over ``items`` (dim, extent) pairs, outer..inner."""
    axes = tuple(Axis(d, s) for d, s in items)
    dim_map = tuple((d, (d,)) for d, _ in items)
    return Layout(dtype, axes, dim_map)


def _issue_all_to_all(
    dist: DistBag,
    out_tile_layout: Layout,
    split_dim: str,
    concat_dim: str,
    rank_dim: str | None,
) -> DistBag:
    """Issue the relayout-fused all-to-all (shared by the blocking and
    non-blocking entry points)."""
    _require_dense(dist, "all_to_all (use all_to_allv_bag for ragged tiles)")
    if split_dim == concat_dim:
        raise LayoutError("all_to_all: split_dim and concat_dim must differ")
    rank_dim = rank_dim or dist.rank_dims[0]
    if rank_dim not in dist.rank_dims:
        raise LayoutError(f"bag is not distributed over {rank_dim!r} (has {dist.rank_dims})")
    R = dist.dt.comm_size(rank_dim)
    in_space = dist.tile_layout.index_space()
    out_space = out_tile_layout.index_space()
    expected = dict(out_space)
    for d in (split_dim, concat_dim):
        if d not in expected:
            raise LayoutError(f"dim {d!r} missing from output space {out_space}")
    if in_space.get(split_dim) != out_space[split_dim] * R:
        raise LayoutError(
            f"all_to_all: split dim {split_dim!r} must shrink by comm size {R}: "
            f"{in_space.get(split_dim)} -> {out_space[split_dim]}"
        )
    if in_space.get(concat_dim, -1) * R != out_space[concat_dim]:
        raise LayoutError(
            f"all_to_all: concat dim {concat_dim!r} must grow by comm size {R}: "
            f"{in_space.get(concat_dim)} -> {out_space[concat_dim]}"
        )
    expected[split_dim] = out_space[split_dim] * R
    expected[concat_dim] = out_space[concat_dim] // R
    check_same_space(in_space, expected, what="all_to_all")

    # canonical dense layout of one exchanged piece (any order works; the
    # endpoint relayouts absorb it)
    piece = _dense_layout(
        dist.tile_layout.dtype,
        [
            (d, out_space[split_dim] if d == split_dim else in_space[d])
            for d in in_space
        ],
    )
    blk = _fresh_axis_name(piece, "__aa")
    send_l = _block_over(piece, split_dim, blk, R)  # spans the input tile space
    recv_l = _block_over(piece, concat_dim, blk, R)  # spans the output tile space
    axes = _reduce_axes(dist.dt, rank_dim)

    def tile_fn(t):
        x = relayout(t, dist.tile_layout, send_l)  # (R, *piece)
        y = jax.lax.all_to_all(x, axes, split_axis=0, concat_axis=0, tiled=False)
        return relayout(y, recv_l, out_tile_layout)

    return _shard_collective(dist, out_tile_layout, tile_fn)


def all_to_all_start(
    dist: DistBag,
    out_tile_layout: Layout,
    *,
    split_dim: str,
    concat_dim: str,
    rank_dim: str | None = None,
) -> Pending:
    """Non-blocking all-to-all (``MPI_Ialltoall``): issue the reshard and
    return a :class:`Pending` immediately."""
    return Pending(
        _issue_all_to_all(dist, out_tile_layout, split_dim, concat_dim, rank_dim),
        op="all_to_all",
    )


def all_to_all_bag(
    dist: DistBag,
    out_tile_layout: Layout,
    *,
    split_dim: str,
    concat_dim: str,
    rank_dim: str | None = None,
) -> DistBag:
    """MPI_Alltoall along the ``rank_dim`` communicator: each rank splits its
    tile into R blocks of ``split_dim``, sends block ``j`` to rank ``j``, and
    concatenates the received blocks (in rank order) along ``concat_dim``.

    This is the layout-agnostic reshard primitive: a bag tiled along one
    logical dim becomes tiled along another, with both endpoint tile layouts
    chosen freely.  Trace-time checks: ``split_dim`` shrinks by R,
    ``concat_dim`` grows by R, everything else matches.
    """
    return all_to_all_start(
        dist, out_tile_layout, split_dim=split_dim, concat_dim=concat_dim, rank_dim=rank_dim
    ).wait()


# -----------------------------------------------------------------------------
# ragged v-collectives (MPI_Scatterv / Gatherv / Allgatherv / Alltoallv)
# -----------------------------------------------------------------------------
def _check_vscatter(
    root_layout: Layout,
    tile_layout: Layout,
    dt: DistTraverser,
    rank_dims: Sequence[str],
    ragged: Mapping[str, tuple[str, Sequence[int]]],
) -> None:
    if set(ragged) != set(rank_dims):
        raise LayoutError(
            f"scatterv: ragged spec covers {sorted(ragged)} but the operation "
            f"distributes over {tuple(rank_dims)}; every rank dim needs its "
            "(tile dim, extents) counts (use scatter for dense block dims)"
        )
    root_space = root_layout.index_space()
    tile_space = tile_layout.index_space()
    if set(root_space) != set(tile_space):
        raise LayoutError(
            f"scatterv: root dims {sorted(root_space)} != tile dims {sorted(tile_space)}"
        )
    rdims = []
    for rd in rank_dims:
        dim, exts = ragged[rd]
        rdims.append(dim)
        if dim not in tile_space:
            raise LayoutError(f"scatterv: ragged dim {dim!r} missing from tile space")
        if len(exts) != dt.comm_size(rd):
            raise LayoutError(
                f"scatterv: {len(exts)} extents for {rd!r} of comm size {dt.comm_size(rd)}"
            )
        if min(exts) < 1:
            raise LayoutError(f"scatterv: empty block in extents {tuple(exts)} for {rd!r}")
        if max(exts) > tile_space[dim]:
            raise LayoutError(
                f"scatterv: extent {max(exts)} of dim {dim!r} exceeds tile "
                f"capacity {tile_space[dim]}"
            )
        if sum(exts) != root_space[dim]:
            raise LayoutError(
                f"scatterv: extents of {dim!r} sum to {sum(exts)} != root extent "
                f"{root_space[dim]} (counts must tile the root exactly)"
            )
    for d, s in tile_space.items():
        if d not in rdims and root_space[d] != s:
            raise LayoutError(
                f"scatterv: dense dim {d!r} extent {s} != root extent {root_space[d]}"
            )
    check_ragged_dims(tile_layout, tile_layout, rdims, what="scatterv(tile)")


def _prefix_sums(exts: Sequence[int]) -> list[int]:
    out, acc = [0], 0
    for e in exts:
        acc += e
        out.append(acc)
    return out


def scatterv_bag(
    root: Bag,
    tile_layout: Layout,
    dt: DistTraverser,
    ragged: Mapping[str, tuple[str, Sequence[int]]],
    rank_dim: str | Sequence[str] | None = None,
    *,
    pad_value=0,
) -> DistBag:
    """``MPI_Scatterv``: scatter ``root`` into per-rank *ragged* tiles.

    ``ragged`` maps each rank dim to ``(tile dim, per-coordinate extents)``
    — the counts array; displacements are its prefix sums.  ``tile_layout``
    is the homogeneous padded *capacity* layout (its ragged dims sized at the
    max extent, typically ``ceil(total / R)`` from
    :func:`repro.core.dims.ragged_split`); rank ``r`` receives its
    ``extents[r]``-sized logical block in the leading slice with zero
    padding behind it, relayouted from any root layout exactly like
    :func:`scatter`.  The result carries the extents table, so downstream
    collectives and :meth:`DistBag.tile` stay padding-free.

    ``pad_value`` is the capacity-fill value (default 0, the add/mean
    identity).  Tiles feeding a local ``max``/``min`` over a ragged dim
    should fill with that op's identity instead:
    ``pad_value=reduce_identity(op, dtype)``.
    """
    rank_dims = _as_rank_dims(dt, rank_dim)
    ragged = dict(ragged)
    _check_vscatter(root.layout, tile_layout, dt, rank_dims, ragged)
    canon = _dense_layout(root.layout.dtype, list(root.layout.index_space().items()))
    arr = relayout(root.data, root.layout, canon)
    axis_of = {d: canon.axis_index(d) for d, _ in canon.dim_map}
    offs = {rd: _prefix_sums(ragged[rd][1]) for rd in rank_dims}
    lead = _lead_shape(dt, rank_dims)
    tiles = []
    for coords in itertools.product(*(range(s) for s in lead)):
        slicer: list[Any] = [slice(None)] * canon.ndim
        shrunk_canon, shrunk_tile = canon, tile_layout
        for rd, c in zip(rank_dims, coords):
            dim, exts = ragged[rd]
            o = offs[rd][c]
            slicer[axis_of[dim]] = slice(o, o + exts[c])
            shrunk_canon = shrunk_canon.resize_dim(dim, exts[c])
            shrunk_tile = shrunk_tile.resize_dim(dim, exts[c])
        chunk = relayout(arr[tuple(slicer)], shrunk_canon, shrunk_tile)
        pad = [(0, full - cur) for full, cur in zip(tile_layout.shape, shrunk_tile.shape)]
        tiles.append(jnp.pad(chunk, pad, constant_values=pad_value))
    data = jnp.stack(tiles).reshape(lead + tile_layout.shape)
    sharding = NamedSharding(dt.mesh, _grid_spec(dt, rank_dims, tile_layout.ndim))
    data = jax.device_put(data, sharding)
    return DistBag(
        data, tile_layout, dt, tuple(rank_dims), extents=grid_extents(dt, rank_dims, ragged)
    )


def gatherv_bag(dist: DistBag, root_layout: Layout) -> Bag:
    """``MPI_Gatherv``: assemble the ragged tiles back into a root bag.

    The displacement arithmetic is recovered from the bag's extents table
    (each ragged dim's counts vary along exactly one rank dim); only the
    valid leading regions enter the result — the padding never leaves the
    tiles.  Host-root reference semantics, the inverse of
    :func:`scatterv_bag` for any ``root_layout`` over the same space.
    """
    if dist.extents is None:
        raise LayoutError("gatherv_bag: bag is dense (no extents); use gather")
    root_space = root_layout.index_space()
    tile_space = dist.tile_layout.index_space()
    if set(root_space) != set(tile_space):
        raise LayoutError(
            f"gatherv_bag: root dims {sorted(root_space)} != tile dims {sorted(tile_space)}"
        )
    # assign each ragged dim to the rank dim that tiles it; the root-space
    # sums disambiguate uniform (exactly-divisible) dims
    owners = _match_ragged_owners(dist, root_space)
    ext_lists = {d: _dim_extent_list(dist, d, p) for d, p in owners.items()}
    for d, s in tile_space.items():
        if d not in owners and root_space[d] != s:
            raise LayoutError(
                f"gatherv_bag: dense dim {d!r} extent {s} != root extent {root_space[d]}"
            )
    canon = _dense_layout(root_layout.dtype, list(root_space.items()))
    axis_of = {d: canon.axis_index(d) for d, _ in canon.dim_map}
    offs = {d: _prefix_sums(exts) for d, exts in ext_lists.items()}
    out = jnp.zeros(canon.shape, dtype=root_layout.dtype)
    for coords in itertools.product(*(range(s) for s in dist.grid_shape)):
        t = dist.tile(coords)  # valid view: ragged dims already resized
        shrunk_canon = canon
        slicer: list[Any] = [slice(None)] * canon.ndim
        for d, p in owners.items():
            e = ext_lists[d][coords[p]]
            o = offs[d][coords[p]]
            shrunk_canon = shrunk_canon.resize_dim(d, e)
            slicer[axis_of[d]] = slice(o, o + e)
        out = out.at[tuple(slicer)].set(relayout(t.data, t.layout, shrunk_canon))
    res = relayout(out, canon, root_layout)
    res = jax.device_put(res, NamedSharding(dist.dt.mesh, P()))
    return Bag(res, root_layout)


def _gatherv_cat_dim(dist: DistBag, pos: int, root_space: Mapping[str, int], what: str) -> str:
    """The ragged dim whose extents the rank dim at grid position ``pos``
    tiles (per-sub-communicator counts): candidates from separability,
    disambiguated by the root-space sum and by unique ownership."""
    cands = _ragged_owner_candidates(dist)
    matches = [
        d
        for d, ps in cands.items()
        if pos in ps and sum(_dim_extent_list(dist, d, pos)) == root_space.get(d)
    ]
    if len(matches) > 1:
        unique = [d for d in matches if cands[d] == [pos]]
        matches = unique or matches
    if len(matches) != 1:
        raise LayoutError(
            f"{what}: cannot identify the ragged dim tiled by rank dim "
            f"{dist.rank_dims[pos]!r} (candidates: {sorted(matches)} of "
            f"ragged dims {sorted(cands)})"
        )
    return matches[0]


def _issue_all_gatherv(dist: DistBag, root_layout: Layout, rank_dims: Sequence[str]) -> DistBag:
    """Issue the true on-device all-gather of ragged tiles (shared by the
    blocking and non-blocking entry points): the padded capacity tiles move
    over the wire (uniform datatype), and the static per-rank extents drive
    the valid-slice concatenation *inside* the same XLA program — the
    ``MPI_Allgatherv`` whose recvcounts/displs are compile-time constants.

    On a communicator grid the gather runs along one named rank dim; the
    other grid dims act as independent sub-communicators
    (``MPI_Comm_split``), the per-sub-communicator counts coming from the
    grid extents table.  Dims tiled by the *other* rank dims stay ragged at
    capacity in the result and keep their extents.
    """
    dt = dist.dt
    if dist.extents is None:
        raise LayoutError("all_gatherv: bag is dense (no extents); use all_gather_*")
    if len(rank_dims) != 1:
        raise LayoutError(
            "all_gatherv gathers along one rank dim per call; name it "
            f"explicitly on the grid {dist.rank_dims}"
        )
    (rd,) = rank_dims
    pos = dist.rank_dims.index(rd)
    root_space = root_layout.index_space()
    cat_dim = _gatherv_cat_dim(dist, pos, root_space, "all_gatherv")
    exts = _dim_extent_list(dist, cat_dim, pos)
    R = dt.comm_size(rd)
    total = sum(exts)
    # dims tiled by the other grid dims ride through at capacity; their
    # extents must not vary along ``rd`` (separability guarantees the slice
    # sizes are uniform inside every sub-communicator)
    other_ragged = tuple(d for d in dist.ragged_dims() if d != cat_dim)
    if other_ragged:
        _uniform_extents_along(
            dataclasses.replace(
                dist,
                extents=tuple(
                    tuple(p for p in entry if p[0] != cat_dim) for entry in dist.extents
                ),
            ),
            rd,
            "all_gatherv (other ragged dims)",
        )
    expected = dict(dist.tile_layout.index_space())
    expected[cat_dim] = total
    check_same_space(root_layout.index_space(), expected, what="all_gatherv(root, sum of tiles)")
    check_ragged_dims(dist.tile_layout, dist.tile_layout, (cat_dim,), what="all_gatherv")
    check_ragged_dims(root_layout, root_layout, other_ragged, what="all_gatherv(out)")
    ax = dist.tile_layout.axis_index(dist.tile_layout.dim_axes(cat_dim)[0])
    full_l = dist.tile_layout.resize_dim(cat_dim, total)
    axes = tuple(dt.rank_mesh_axes(rd))

    def tile_fn(t):
        g = jax.lax.all_gather(t, axes, axis=0, tiled=False)  # (R, *capacity)
        parts = [jax.lax.slice_in_dim(g[r], 0, exts[r], axis=ax) for r in range(R)]
        full = jnp.concatenate(parts, axis=ax)
        return relayout(full, full_l, root_layout)

    out = _shard_collective(dist, root_layout, tile_fn)
    if other_ragged:
        new_ext = tuple(
            tuple(p for p in entry if p[0] != cat_dim) for entry in dist.extents
        )
        out = dataclasses.replace(out, extents=new_ext)
    return out


def all_gatherv_start(
    dist: DistBag, root_layout: Layout, *, rank_dim: str | Sequence[str] | None = None
) -> Pending:
    """Non-blocking ragged all-gather (``MPI_Iallgatherv``): issue the
    transfer and return a :class:`Pending` whose :meth:`~Pending.wait` hands
    back a :class:`DistBag` in which every rank holds the full compacted
    structure in ``root_layout``."""
    rank_dims = _as_rank_dims(dist.dt, rank_dim) if rank_dim is not None else dist.rank_dims
    for d in rank_dims:
        if d not in dist.rank_dims:
            raise LayoutError(f"bag is not distributed over {d!r} (has {dist.rank_dims})")
    return Pending(_issue_all_gatherv(dist, root_layout, rank_dims), op="all_gatherv")


def all_gatherv_dist(
    dist: DistBag, root_layout: Layout, *, rank_dim: str | Sequence[str] | None = None
) -> DistBag:
    """Blocking ragged all-gather returning the per-rank receive buffers
    (``all_gatherv_start(...).wait()``)."""
    return all_gatherv_start(dist, root_layout, rank_dim=rank_dim).wait()


def all_gatherv_bag(dist: DistBag, root_layout: Layout) -> Bag:
    """``MPI_Allgatherv``: every rank ends with the full structure — the
    ragged tiles' valid regions concatenated in rank order — in
    ``root_layout``, via the true on-device all-gather.

    On a communicator grid this gathers along every rank dim in turn (one
    sub-communicator all-gather per grid dim, like a dimension-ordered
    ``MPI_Allgatherv`` over a Cartesian communicator), so each grid dim
    must tile its own ragged dim."""
    root_space = root_layout.index_space()
    db = dist
    for i, rd in enumerate(dist.rank_dims):
        last = i == len(dist.rank_dims) - 1
        if last:
            target = root_layout
        else:
            pos = db.rank_dims.index(rd)
            cat_dim = _gatherv_cat_dim(db, pos, root_space, "all_gatherv")
            space = dict(db.tile_layout.index_space())
            space[cat_dim] = root_space[cat_dim]
            target = _dense_layout(root_layout.dtype, list(space.items()))
        db = all_gatherv_dist(db, target, rank_dim=rd)
    first = db.data[(0,) * len(dist.rank_dims)]
    out = jax.device_put(first, NamedSharding(dist.dt.mesh, P()))
    return Bag(out, root_layout)


def _issue_reduce_scatterv(
    dist: DistBag,
    out_tile_layout: Layout,
    scatter_dim: str,
    in_blocks: tuple[int, Sequence[int]],
    out_extents: Sequence[int],
    op: str,
    rank_dim: str | None,
) -> DistBag:
    """Issue the ragged reduce-scatter (shared by blocking/non-blocking).

    The input tile's ``scatter_dim`` is *block-ragged*: ``in_blocks =
    (capacity, extents)`` describes B interior blocks of uniform capacity
    whose valid leading extents differ (a partial panel accumulated block by
    block, e.g. the ragged SUMMA epilogue).  The blocks are compacted and
    re-padded into R output blocks of ``out_extents`` — all static slices,
    identical on every rank — then reduced+scattered: ``add``/``mean`` go
    through ``psum_scatter`` (zero padding is their identity); ``max``/
    ``min`` re-pad with :func:`reduce_identity`, exchange the stacked
    blocks with an all-to-all, reduce locally, and re-zero the output
    padding so the bag's zero-padding contract survives the op.
    """
    rank_dim = rank_dim or dist.rank_dims[0]
    if rank_dim not in dist.rank_dims:
        raise LayoutError(f"bag is not distributed over {rank_dim!r} (has {dist.rank_dims})")
    _resolve_reduce(op)
    if scatter_dim in dist.ragged_dims():
        raise LayoutError(
            f"reduce_scatterv: {scatter_dim!r} is leading-ragged in the input; "
            "its block structure must come via in_blocks"
        )
    _uniform_extents_along(dist, rank_dim, "reduce_scatterv")
    R = dist.dt.comm_size(rank_dim)
    cap_in, in_exts = in_blocks
    in_exts = tuple(int(e) for e in in_exts)
    B = len(in_exts)
    total = sum(in_exts)
    out_extents = tuple(int(e) for e in out_extents)
    if len(out_extents) != R:
        raise LayoutError(f"reduce_scatterv: {len(out_extents)} out extents for comm size {R}")
    if sum(out_extents) != total:
        raise LayoutError(
            f"reduce_scatterv: out extents sum {sum(out_extents)} != in extents sum {total}"
        )
    if max(in_exts) > cap_in or min(in_exts) < 0:
        raise LayoutError(f"reduce_scatterv: in extents {in_exts} exceed capacity {cap_in}")
    in_space = dist.tile_layout.index_space()
    out_space = out_tile_layout.index_space()
    if in_space.get(scatter_dim) != B * cap_in:
        raise LayoutError(
            f"reduce_scatterv: scatter dim {scatter_dim!r} extent {in_space.get(scatter_dim)} "
            f"!= {B} blocks x capacity {cap_in}"
        )
    cap_out = out_space.get(scatter_dim)
    if cap_out is None or max(out_extents) > cap_out:
        raise LayoutError(
            f"reduce_scatterv: out extents {out_extents} exceed output capacity {cap_out}"
        )
    expected = dict(in_space)
    expected[scatter_dim] = cap_out
    check_same_space(out_space, expected, what=f"reduce_scatterv over {scatter_dim!r}")
    other_ragged = tuple(d for d in dist.ragged_dims())
    check_ragged_dims(
        dist.tile_layout, out_tile_layout, (scatter_dim,) + other_ragged, what="reduce_scatterv"
    )
    rest = [(d, s) for d, s in in_space.items() if d != scatter_dim]
    mid_in = _dense_layout(dist.tile_layout.dtype, rest + [(scatter_dim, B * cap_in)])
    mid_out = _dense_layout(out_tile_layout.dtype, rest + [(scatter_dim, cap_out)])
    axes = _reduce_axes(dist.dt, rank_dim)
    pos = dist.rank_dims.index(rank_dim)
    ident = reduce_identity(op, dist.tile_layout.dtype)
    # for max/min the output padding must be re-zeroed (the reduce of
    # identities is the identity, not 0): rank-dependent valid extents along
    # scatter_dim and along the other ragged dims, read from static tables
    # indexed by the traced communicator coordinates
    other_masks: list[tuple[int, int, jnp.ndarray]] = []  # (axis, owner pos, table)
    if op not in ("add", "mean") and dist.extents is not None:
        cands = _ragged_owner_candidates(dist)
        for i, (d, _) in enumerate(rest):
            if d not in cands:
                continue
            # extents are uniform along rank_dim (checked above), so the
            # owner is a position other than rank_dim's unless constant
            p = next((c for c in cands[d] if c != pos), cands[d][0])
            other_masks.append((i, p, jnp.asarray(_dim_extent_list(dist, d, p))))

    # displacement prefix sums over the valid stream: input block b holds
    # stream rows [ibase[b], ibase[b+1]), output rank r wants rows
    # [obase[r], obase[r+1])
    ibase = [0]
    for b in range(B):
        ibase.append(ibase[-1] + in_exts[b])
    obase = [0]
    for r in range(R):
        obase.append(obase[-1] + out_extents[r])

    def tile_fn(t):
        x = relayout(t, dist.tile_layout, mid_in)
        # slice each output rank's rows straight out of the padded input
        # blocks via the displacement offsets — no compacted full-stream
        # intermediate; stream order is preserved so the reduced result is
        # bitwise identical to compact-then-scatter
        pieces = []
        for r in range(R):
            parts = []
            for b in range(B):
                lo = max(obase[r], ibase[b])
                hi = min(obase[r + 1], ibase[b + 1])
                if lo >= hi:
                    continue
                s = b * cap_in + (lo - ibase[b])
                parts.append(jax.lax.slice_in_dim(x, s, s + (hi - lo), axis=-1))
            e = out_extents[r]
            if not parts:
                pieces.append(jnp.full(x.shape[:-1] + (cap_out,), ident, x.dtype))
                continue
            blk = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)
            pad = [(0, 0)] * (blk.ndim - 1) + [(0, cap_out - e)]
            pieces.append(jnp.pad(blk, pad, constant_values=ident))
        stacked = jnp.stack(pieces)  # (R, *mid_out shape), block r = rank r's part
        if op in ("add", "mean"):
            y = jax.lax.psum_scatter(stacked, axes, scatter_dimension=0, tiled=False)
            if op == "mean":
                y = y / R
        else:
            y = jax.lax.all_to_all(stacked, axes, split_axis=0, concat_axis=0, tiled=False)
            y = (jnp.max if op == "max" else jnp.min)(y, axis=0)
            # restore the zero-padding contract of the result bag
            my_ext = jnp.asarray(out_extents)[_flat_rank(dist.dt, rank_dim)]
            valid = jax.lax.broadcasted_iota(jnp.int32, y.shape, y.ndim - 1) < my_ext
            for axis, p, table in other_masks:
                e = table[_flat_rank(dist.dt, dist.rank_dims[p])]
                valid &= jax.lax.broadcasted_iota(jnp.int32, y.shape, axis) < e
            y = jnp.where(valid, y, jnp.zeros((), y.dtype))
        return relayout(y, mid_out, out_tile_layout)

    out = _shard_collective(dist, out_tile_layout, tile_fn)
    pos = dist.rank_dims.index(rank_dim)
    new_ext = []
    for coords in itertools.product(*(range(s) for s in dist.grid_shape)):
        entry = [
            p
            for p in (dist.extents[dist.flat_rank(coords)] if dist.extents else ())
            if p[0] != scatter_dim
        ]
        entry.append((scatter_dim, out_extents[coords[pos]]))
        new_ext.append(tuple(entry))
    return dataclasses.replace(out, extents=tuple(new_ext))


def reduce_scatterv_start(
    dist: DistBag,
    out_tile_layout: Layout,
    *,
    scatter_dim: str,
    in_blocks: tuple[int, Sequence[int]],
    out_extents: Sequence[int],
    op: str = "add",
    rank_dim: str | None = None,
) -> Pending:
    """Non-blocking ragged reduce-scatter: issue and return a
    :class:`Pending` immediately (see :func:`reduce_scatterv_bag`)."""
    return Pending(
        _issue_reduce_scatterv(dist, out_tile_layout, scatter_dim, in_blocks, out_extents, op, rank_dim),
        op="reduce_scatterv",
    )


def reduce_scatterv_bag(
    dist: DistBag,
    out_tile_layout: Layout,
    *,
    scatter_dim: str,
    in_blocks: tuple[int, Sequence[int]],
    out_extents: Sequence[int],
    op: str = "add",
    rank_dim: str | None = None,
) -> DistBag:
    """Ragged ``MPI_Reduce_scatter``: elementwise-reduce block-ragged panels
    across the ``rank_dim`` communicator and scatter ``scatter_dim`` so rank
    ``r`` keeps its ``out_extents[r]``-sized logical block (leading slice of
    a ``max(out_extents)``-capacity tile).  See :func:`_issue_reduce_scatterv`
    for the block-compaction semantics."""
    return reduce_scatterv_start(
        dist,
        out_tile_layout,
        scatter_dim=scatter_dim,
        in_blocks=in_blocks,
        out_extents=out_extents,
        op=op,
        rank_dim=rank_dim,
    ).wait()


def _issue_all_to_allv(
    dist: DistBag,
    out_tile_layout: Layout,
    split_dim: str,
    concat_dim: str,
    split_extents: Sequence[int],
    rank_dim: str | None,
) -> DistBag:
    """Issue the ragged all-to-all (shared by blocking/non-blocking).

    The ragged transpose-reshard: a bag tiled raggedly along ``concat_dim``
    (its extents table) becomes tiled raggedly along ``split_dim``
    (``split_extents``); rank ``r`` sends the ``(split_extents[j],
    my-concat-extent)`` sub-block to rank ``j``.  Blocks move at uniform
    padded capacity over the wire; both the send-side split and the
    receive-side compaction are static slices identical on every rank, so
    the whole exchange stays one SPMD program — ``MPI_Alltoallv`` with
    compile-time counts.

    On a communicator grid the exchange runs along the named ``rank_dim``
    sub-communicators; dims tiled by the other grid dims ride through at
    capacity and keep their extents, and the per-sub-communicator counts of
    ``concat_dim`` come from the grid extents table.
    """
    if split_dim == concat_dim:
        raise LayoutError("all_to_allv: split_dim and concat_dim must differ")
    rank_dim = rank_dim or dist.rank_dims[0]
    if rank_dim not in dist.rank_dims:
        raise LayoutError(f"bag is not distributed over {rank_dim!r} (has {dist.rank_dims})")
    pos = dist.rank_dims.index(rank_dim)
    R = dist.dt.comm_size(rank_dim)
    split_extents = tuple(int(e) for e in split_extents)
    if len(split_extents) != R:
        raise LayoutError(f"all_to_allv: {len(split_extents)} split extents for comm size {R}")
    if dist.extents is None:
        raise LayoutError(
            "all_to_allv: input must be ragged along concat_dim (use all_to_all for dense)"
        )
    cands = _ragged_owner_candidates(dist)
    if concat_dim not in cands or pos not in cands[concat_dim]:
        raise LayoutError(
            f"all_to_allv: input must be ragged along {concat_dim!r} over "
            f"{rank_dim!r} (ragged dims: {sorted(cands)})"
        )
    if split_dim in cands:
        raise LayoutError(
            f"all_to_allv: split dim {split_dim!r} must be dense in the input "
            f"(ragged dims: {sorted(cands)})"
        )
    other_ragged = tuple(d for d in dist.ragged_dims() if d != concat_dim)
    for d in other_ragged:
        if cands[d] == [pos]:
            raise LayoutError(
                f"all_to_allv: ragged dim {d!r} varies along {rank_dim!r}; only "
                f"{concat_dim!r} may (other ragged dims belong to other grid dims)"
            )
    concat_exts = _dim_extent_list(dist, concat_dim, pos)
    in_space = dist.tile_layout.index_space()
    out_space = out_tile_layout.index_space()
    X_total = sum(split_extents)
    if in_space.get(split_dim) != X_total:
        raise LayoutError(
            f"all_to_allv: split dim {split_dim!r} extent {in_space.get(split_dim)} "
            f"!= split extents sum {X_total}"
        )
    cap_s = out_space.get(split_dim)
    if cap_s is None or max(split_extents) > cap_s:
        raise LayoutError(
            f"all_to_allv: split extents {split_extents} exceed output capacity {cap_s}"
        )
    C_total = sum(concat_exts)
    if out_space.get(concat_dim) != C_total:
        raise LayoutError(
            f"all_to_allv: concat dim {concat_dim!r} output extent "
            f"{out_space.get(concat_dim)} != concat extents sum {C_total}"
        )
    expected = {d: s for d, s in in_space.items() if d not in (split_dim, concat_dim)}
    expected[split_dim] = cap_s
    expected[concat_dim] = C_total
    check_same_space(out_space, expected, what="all_to_allv")
    check_ragged_dims(
        dist.tile_layout,
        out_tile_layout,
        (split_dim, concat_dim) + other_ragged,
        what="all_to_allv",
    )
    cap_c = in_space[concat_dim]
    rest = [(d, s) for d, s in in_space.items() if d not in (split_dim, concat_dim)]
    mid_in = _dense_layout(
        dist.tile_layout.dtype, rest + [(split_dim, X_total), (concat_dim, cap_c)]
    )
    mid_out = _dense_layout(
        out_tile_layout.dtype, rest + [(split_dim, cap_s), (concat_dim, C_total)]
    )
    axes = _reduce_axes(dist.dt, rank_dim)

    def tile_fn(t):
        x = relayout(t, dist.tile_layout, mid_in)  # (..., X_total, cap_c)
        pieces, off = [], 0
        for j in range(R):
            e = split_extents[j]
            p = jax.lax.slice_in_dim(x, off, off + e, axis=-2)
            off += e
            pad = [(0, 0)] * x.ndim
            pad[-2] = (0, cap_s - e)
            pieces.append(jnp.pad(p, pad))
        stacked = jnp.stack(pieces)  # (R, ..., cap_s, cap_c)
        y = jax.lax.all_to_all(stacked, axes, split_axis=0, concat_axis=0, tiled=False)
        # received piece j is valid (split_extents[me], concat_exts[j]);
        # compact the concat padding — the extents list is shared knowledge,
        # so the slice sizes are the same on every rank
        parts = [jax.lax.slice_in_dim(y[j], 0, concat_exts[j], axis=-1) for j in range(R)]
        full = jnp.concatenate(parts, axis=-1)  # (..., cap_s, C_total)
        return relayout(full, mid_out, out_tile_layout)

    out = _shard_collective(dist, out_tile_layout, tile_fn)
    new_ext = []
    for coords in itertools.product(*(range(s) for s in dist.grid_shape)):
        entry = [
            p for p in dist.extents[dist.flat_rank(coords)] if p[0] != concat_dim
        ]
        entry.append((split_dim, split_extents[coords[pos]]))
        new_ext.append(tuple(entry))
    return dataclasses.replace(out, extents=tuple(new_ext))


def all_to_allv_start(
    dist: DistBag,
    out_tile_layout: Layout,
    *,
    split_dim: str,
    concat_dim: str,
    split_extents: Sequence[int],
    rank_dim: str | None = None,
) -> Pending:
    """Non-blocking ragged all-to-all (``MPI_Ialltoallv``): issue the
    reshard and return a :class:`Pending` immediately."""
    return Pending(
        _issue_all_to_allv(dist, out_tile_layout, split_dim, concat_dim, split_extents, rank_dim),
        op="all_to_allv",
    )


def all_to_allv_bag(
    dist: DistBag,
    out_tile_layout: Layout,
    *,
    split_dim: str,
    concat_dim: str,
    split_extents: Sequence[int],
    rank_dim: str | None = None,
) -> DistBag:
    """``MPI_Alltoallv``: reshard a bag tiled raggedly along ``concat_dim``
    into one tiled raggedly along ``split_dim`` (see
    :func:`_issue_all_to_allv`); blocking = ``all_to_allv_start(...).wait()``
    by construction."""
    return all_to_allv_start(
        dist,
        out_tile_layout,
        split_dim=split_dim,
        concat_dim=concat_dim,
        split_extents=split_extents,
        rank_dim=rank_dim,
    ).wait()


# -----------------------------------------------------------------------------
# per-rank compute
# -----------------------------------------------------------------------------
def rank_map(
    fn: Callable[..., Any],
    dt: DistTraverser,
    *dist_bags: DistBag,
    out_tile_layout: Layout | None = None,
    rank_dim: str | Sequence[str] | None = None,
    out_extents: tuple[tuple[tuple[str, int], ...], ...] | None = None,
) -> DistBag:
    """Run ``fn(rank, *tile_bags) -> tile_bag_or_array`` on every rank.

    ``out_extents`` (optional) attaches a per-rank valid-extents table to the
    result — per-rank compute on padded ragged tiles (``fn`` sees the full
    capacity buffers and is responsible for keeping the padding inert, e.g.
    zeros under add-reductions).

    The per-rank computation sees plain :class:`Bag` tiles in their declared
    layouts (paper Listing 5's ``modify(tile[state])``).  Implemented with
    ``shard_map`` over the communicator's mesh axes; the rank index is
    reconstructed from the mesh axis indices exactly like ``MPI_Comm_rank``.

    On a 1-D communicator ``rank`` is the integer rank; on a grid it is a
    state dict ``{rank_dim: coordinate}`` (the paper's ``MPI_Cart_coords``).
    Input bags may live on different traversers (e.g. operands of a SUMMA
    step bound to different grid dims) as long as they share the mesh.
    """
    rank_dims = _as_rank_dims(dt, rank_dim)
    for db in dist_bags:
        if db.dt.mesh is not dt.mesh and db.dt.mesh != dt.mesh:
            raise LayoutError("rank_map: all bags must live on the same mesh")
    in_specs = tuple(
        _grid_spec(db.dt, db.rank_dims, db.tile_layout.ndim) for db in dist_bags
    )
    out_layout = out_tile_layout or dist_bags[0].tile_layout
    out_spec = _grid_spec(dt, rank_dims, out_layout.ndim)
    lead = len(rank_dims)

    def shard_fn(*tiles):
        if lead == 1:
            rank = _flat_rank(dt, rank_dims[0])
        else:
            rank = {d: _flat_rank(dt, d) for d in rank_dims}
        bags = [
            Bag(t.reshape(db.tile_layout.shape), db.tile_layout)
            for t, db in zip(tiles, dist_bags)
        ]
        out = fn(rank, *bags)
        out_arr = out.data if isinstance(out, Bag) else out
        return out_arr.reshape((1,) * lead + out_layout.shape)

    # check_vma=False: the per-rank compute may hold Pallas kernels, which
    # have no varying-axes rule
    mapped = shard_map(
        shard_fn, mesh=dt.mesh, in_specs=in_specs, out_specs=out_spec, check_vma=False
    )(*[db.data for db in dist_bags])
    return DistBag(mapped, out_layout, dt, rank_dims, extents=out_extents)


def _check_flat_extents(n: int, extents: Sequence[int], what: str) -> int:
    """Validate a flat recvcounts table against an ``R * cap`` buffer; returns
    the per-rank capacity."""
    R = len(extents)
    if R == 0 or n % R:
        raise LayoutError(
            f"{what}: flat size {n} must be R * cap for R={R} ranks"
        )
    cap = n // R
    for r, e in enumerate(extents):
        if not 0 <= int(e) <= cap:
            raise LayoutError(
                f"{what}: extents[{r}]={e} outside [0, cap={cap}]"
            )
    return cap


def shard_reduce_scatterv_start(x, axis_name: str, *, extents: Sequence[int]) -> Pending:
    """Inside-``shard_map`` ``MPI_Ireduce_scatter`` over a *flat padded*
    buffer: reduce the per-rank ``(R * cap,)`` partials over ``axis_name``
    and hand rank ``r`` its own ``(cap,)`` slice, of which the leading
    ``extents[r]`` elements are valid payload (the ``recvcounts`` table —
    :func:`repro.models.sharding.ragged_grad_extents` builds it from a
    gradient bucket's element count).  The capacity-pad tail is zeros by
    construction (:func:`repro.train.buckets.pack_bucket`), so it is inert
    under the sum and is wire-vs-valid accounted by the walker
    (``dryrun --train``), exactly like the ragged-SUMMA panels.

    Returns the :class:`Pending`; blocking = ``.wait()`` by construction.
    The ZeRO train step issues one of these per gradient bucket — every
    bucket in flight before any wait (:func:`repro.core.plan.bucket`)."""
    def rs(a):
        _check_flat_extents(a.shape[0], extents, "shard_reduce_scatterv_start")
        return jax.lax.psum_scatter(a, axis_name, scatter_dimension=0, tiled=True)

    return Pending(jax.tree_util.tree_map(rs, x), op="reduce_scatterv")


def shard_all_gatherv_start(x, axis_name: str, *, extents: Sequence[int]) -> Pending:
    """Inside-``shard_map`` ``MPI_Iallgatherv`` over flat capacity shards:
    concatenate every rank's ``(cap,)`` shard in rank order into the full
    ``(R * cap,)`` buffer, of which rank ``r``'s slice carries
    ``extents[r]`` valid elements (counts; displacements are the ``r * cap``
    capacity offsets).  The ZeRO train step's param-prefetch return leg:
    each updated 1/R param shard is regathered ahead of the next forward
    (:func:`repro.core.plan.bucket`'s combine stage).

    Returns the :class:`Pending`; blocking = ``.wait()`` by construction."""
    def ag(a):
        R = len(extents)
        _check_flat_extents(a.shape[0] * R, extents, "shard_all_gatherv_start")
        return jax.lax.all_gather(a, axis_name, axis=0, tiled=True)

    return Pending(jax.tree_util.tree_map(ag, x), op="all_gatherv")

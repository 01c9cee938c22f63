"""Distributed traversers: the paper's *MPI traverser* on a JAX device mesh.

An MPI traverser (paper §4.1) is a regular traverser with one dimension — the
*ranking dimension* — bound to the MPI rank.  On TPU the communicator is a
:class:`jax.sharding.Mesh`; the ranking dimension binds to one or more mesh
axes, and its extent is deduced from the mesh if left open (the paper's
"set automatically to the communicator size").

From a binding we *derive* ``PartitionSpec``s for any layout — the analogue of
Noarr-MPI deriving MPI datatypes from structures: the user never writes a
PartitionSpec by hand, they bind named dims.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .dims import LayoutError, prod
from .layout import Layout
from .traverser import Traverser, set_length

__all__ = [
    "DistTraverser",
    "make_mesh",
    "mpi_traverser",
    "mpi_cart_traverser",
    "partition_spec",
    "named_sharding",
]

MeshAxes = tuple[str, ...]


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], **kwargs) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``.  JAX defaults mesh axes to
    ``Explicit``, which breaks the ``shard_map``-based collectives and the
    GSPMD recipes built on this mesh."""
    auto = (jax.sharding.AxisType.Auto,) * len(tuple(axis_names))
    return jax.make_mesh(axis_shapes, axis_names, axis_types=auto, **kwargs)


def _as_axes(a) -> MeshAxes:
    if isinstance(a, str):
        return (a,)
    return tuple(a)


@dataclasses.dataclass(frozen=True)
class DistTraverser:
    """Traverser + mesh + {rank dim -> mesh axes} bindings."""

    trav: Traverser
    mesh: Mesh
    bindings: tuple[tuple[str, MeshAxes], ...]  # rank dim -> mesh axes (ordered)

    # -- communicator-like queries ------------------------------------------------
    def comm_size(self, dim: str | None = None) -> int:
        if dim is None:
            return prod(self.mesh_axis_size(ax) for _, axs in self.bindings for ax in axs)
        axs = dict(self.bindings)[dim]
        return prod(self.mesh_axis_size(ax) for ax in axs)

    def mesh_axis_size(self, axis: str) -> int:
        return self.mesh.shape[axis]

    @property
    def rank_dims(self) -> tuple[str, ...]:
        return tuple(d for d, _ in self.bindings)

    def rank_mesh_axes(self, dim: str) -> MeshAxes:
        return dict(self.bindings)[dim]

    # -- traverser passthrough ------------------------------------------------------
    def index_space(self) -> dict[str, int]:
        return self.trav.index_space()

    @property
    def order(self) -> tuple[str, ...]:
        return self.trav.order

    def __xor__(self, transform) -> "DistTraverser":
        return dataclasses.replace(self, trav=self.trav ^ transform)

    def __or__(self, fn) -> None:
        # Host-side reference iteration over the *full* space, including rank
        # dims (single-controller JAX sees all shards).
        return self.trav | fn

    # -- sub-communicators (MPI_Comm_split / MPI_Cart_sub analogue) -----------------
    def sub(self, *dims: str) -> "DistTraverser":
        """Restrict the communicator to the named ranking dims.

        The paper's ``MPI_Comm_split``: on a ``('rows', 'cols')`` grid,
        ``dt.sub('rows')`` is the column communicator family — one independent
        communicator per fixed ``cols`` coordinate, which is exactly how the
        collectives treat the dropped dims.
        """
        known = dict(self.bindings)
        missing = [d for d in dims if d not in known]
        if missing:
            raise LayoutError(f"sub{dims}: unknown rank dims {missing} (have {self.rank_dims})")
        if not dims:
            raise LayoutError("sub() needs at least one rank dim")
        return dataclasses.replace(
            self, bindings=tuple((d, axs) for d, axs in self.bindings if d in dims)
        )

    # -- rank decomposition -----------------------------------------------------------
    def rank_leaves(self, dim: str) -> tuple[tuple[str, int], ...]:
        """Leaf dims (with extents) composing the ranking dim ``dim``
        (non-trivial when the rank dim was ``merge_blocks``-ed from a grid)."""
        dec = self.trav._resolved_decomp()
        if dim in dec:
            return dec[dim]
        return ((dim, self.trav.dim_size(dim)),)  # type: ignore[return-value]

    def tile_space(self) -> dict[str, int]:
        """Index space per rank = full space minus rank-dim leaves."""
        space = self.index_space()
        for d in self.rank_dims:
            for leaf, _ in self.rank_leaves(d):
                space.pop(leaf, None)
            space.pop(d, None)
        return space


def mpi_traverser(
    rank_dim: str,
    trav: Traverser,
    mesh: Mesh,
    axes: Sequence[str] | str | None = None,
) -> DistTraverser:
    """Bind ``rank_dim`` of ``trav`` to the mesh (paper ``mpi_traverser<'r'>``).

    ``axes`` defaults to *all* mesh axes (the whole communicator).  The rank
    dim's extent must equal the product of the bound mesh axis sizes; if the
    extent is open it is deduced automatically.
    """
    mesh_axes = _as_axes(axes) if axes is not None else tuple(mesh.axis_names)
    for ax in mesh_axes:
        if ax not in mesh.shape:
            raise LayoutError(f"mesh has no axis {ax!r} (has {tuple(mesh.axis_names)})")
    size = prod(mesh.shape[ax] for ax in mesh_axes)
    current = trav.dim_size(rank_dim)
    if current is None:
        trav = trav ^ set_length(rank_dim, size)
    elif current != size:
        raise LayoutError(
            f"rank dim {rank_dim!r} has extent {current} but communicator "
            f"axes {mesh_axes} have size {size}"
        )
    dt = DistTraverser(trav=trav, mesh=mesh, bindings=((rank_dim, mesh_axes),))
    dt.trav._resolved_decomp()  # force early deduction errors (type safety)
    return dt


def mpi_cart_traverser(
    bindings: Sequence[tuple[str, Sequence[str] | str]] | Mapping[str, Sequence[str] | str],
    trav: Traverser,
    mesh: Mesh,
) -> DistTraverser:
    """Bind several rank dims to disjoint mesh-axis groups — the paper's
    ``MPI_Cart_create``: a communicator grid, e.g. ``[('Ri', 'rows'),
    ('Cj', 'cols')]`` on a 2-D mesh.

    Each rank dim's extent must equal (or, if open, is deduced as) the product
    of its mesh axes.  Collectives then operate along one grid dim at a time;
    :meth:`DistTraverser.sub` extracts the per-dim sub-communicator.
    """
    items = list(bindings.items()) if isinstance(bindings, Mapping) else list(bindings)
    if not items:
        raise LayoutError("mpi_cart_traverser needs at least one (rank dim, mesh axes) binding")
    used: set[str] = set()
    norm: list[tuple[str, MeshAxes]] = []
    for rank_dim, axes in items:
        mesh_axes = _as_axes(axes)
        for ax in mesh_axes:
            if ax not in mesh.shape:
                raise LayoutError(f"mesh has no axis {ax!r} (has {tuple(mesh.axis_names)})")
            if ax in used:
                raise LayoutError(f"mesh axis {ax!r} bound to two rank dims")
            used.add(ax)
        size = prod(mesh.shape[ax] for ax in mesh_axes)
        current = trav.dim_size(rank_dim)
        if current is None:
            trav = trav ^ set_length(rank_dim, size)
        elif current != size:
            raise LayoutError(
                f"rank dim {rank_dim!r} has extent {current} but communicator "
                f"axes {mesh_axes} have size {size}"
            )
        norm.append((rank_dim, mesh_axes))
    dt = DistTraverser(trav=trav, mesh=mesh, bindings=tuple(norm))
    dt.trav._resolved_decomp()  # force early deduction errors (type safety)
    return dt


# -----------------------------------------------------------------------------
# PartitionSpec derivation — the "automatic MPI datatype" of the TPU world.
# -----------------------------------------------------------------------------
def partition_spec(layout: Layout, bindings: Mapping[str, Any], *, priority: Sequence[str] | None = None) -> P:
    """Derive a PartitionSpec for ``layout`` from dim/axis -> mesh-axis bindings.

    Binding keys may name a *physical axis* (e.g. the block axis ``'F'`` of a
    blocked ffn dim) or a *logical dim* that maps to a single physical axis.
    Values are a mesh axis name or tuple of names.  Unbound axes replicate.

    ``priority`` resolves conflicts when two dims of one tensor bind to the
    same mesh axis (e.g. MoE expert weights carry both ``e`` and ``f``, both
    recipe-bound to ``model``): dims earlier in ``priority`` win, later ones
    fall back to replication.  Default priority = binding insertion order.
    """
    axis_dim = {ax: d for d, axs in layout.dim_map for ax in axs}
    order = list(priority) if priority is not None else list(bindings)
    order += [k for k in bindings if k not in order]
    used_mesh_axes: set[str] = set()
    # normalize: physical axis name -> mesh axes
    norm: dict[str, MeshAxes] = {}
    for key in order:
        val = bindings.get(key)
        if val is None:
            continue
        target: str
        if any(a.name == key for a in layout.axes):
            target = key
        else:
            # a logical dim: must map to exactly one physical axis
            daxs = None
            for d, axs in layout.dim_map:
                if d == key:
                    daxs = axs
            if daxs is None:
                continue  # binding irrelevant for this layout
            if len(daxs) != 1:
                raise LayoutError(
                    f"cannot bind blocked dim {key!r} (axes {daxs}) to mesh axes {val!r}; "
                    "bind one of its physical axes instead"
                )
            target = daxs[0]
        if target in norm:
            raise LayoutError(f"axis {target!r} bound twice")
        val_axes = _as_axes(val)
        if any(ax in used_mesh_axes for ax in val_axes):
            continue  # mesh axis already consumed by a higher-priority dim
        used_mesh_axes.update(val_axes)
        norm[target] = val_axes
    entries = []
    for a in layout.axes:
        axs = norm.get(a.name)
        if axs is None:
            entries.append(None)
        else:
            entries.append(axs if len(axs) > 1 else axs[0])
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def named_sharding(mesh: Mesh, layout: Layout, bindings: Mapping[str, Any], *, priority: Sequence[str] | None = None) -> NamedSharding:
    spec = partition_spec(layout, bindings, priority=priority)
    # type-safety: partitioned extents must divide by mesh axes
    for a, entry in zip(layout.axes, tuple(spec) + (None,) * (layout.ndim - len(spec))):
        if entry is None:
            continue
        axs = _as_axes(entry)
        div = prod(mesh.shape[x] for x in axs)
        if a.size is None or a.size % div:
            raise LayoutError(
                f"axis {a} not divisible by mesh axes {axs} (size {div})"
            )
    return NamedSharding(mesh, spec)

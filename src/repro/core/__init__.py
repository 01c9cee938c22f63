"""repro.core — layout-agnostic distributed-array algebra (the paper's
contribution, adapted from Noarr-MPI to JAX/TPU).

Public API mirrors the paper's vocabulary:

* layouts:    ``scalar ^ vector ^ into_blocks ^ hoist ^ ...`` -> :class:`Layout`
* bags:       :func:`bag` / :class:`Bag` — buffer + layout, logical indexing
* traversers: :func:`traverser` ^ ``hoist/fix/span/bcast/merge_blocks``
* relayout:   :func:`relayout` — the MPI-datatype-construction analogue
* dist:       :func:`mpi_traverser` / :func:`mpi_cart_traverser` ->
              :class:`DistTraverser`; layout-agnostic collectives, p2p and
              sharding derivation

Paper section -> module map:

=========  =======================================  =============================
Section    Paper concept                            Module
=========  =======================================  =============================
§2         structures, bags, traversers             ``layout``, ``bag``,
                                                    ``traverser``
§3.1       MPI datatype derivation & taxonomy       ``relayout``
                                                    (``transfer_kind``)
§3.2       signature/type safety                    ``dims`` (``LayoutError``,
                                                    ``check_same_space``)
§4.1       MPI traverser, rank binding,             ``dist`` (``mpi_traverser``,
           communicator grids / Comm_split          ``mpi_cart_traverser``,
                                                    ``DistTraverser.sub``)
§4.2       collectives (scatter/gather/bcast,       ``collectives``
           allreduce/reduce_scatter/alltoall)
§4.3       point-to-point send/recv, ring shifts    ``p2p``
§5         layout-parametric distributed GEMM       ``repro.kernels.gemm`` +
                                                    ``examples/distributed_gemm``
=========  =======================================  =============================

Ragged distribution (MPI v-collectives)
---------------------------------------
Non-uniform per-rank buffers — MPI's counts/displacements world — are
first-class: a :class:`~repro.core.collectives.DistBag` may carry an
``extents`` table of per-rank valid sizes next to a homogeneous *padded
capacity* tile layout.  Correspondence:

======================  =====================================================
MPI                     repro.core
======================  =====================================================
``MPI_Scatterv``        :func:`scatterv_bag` (extents = counts, displs =
                        prefix sums; ``ragged_split`` builds balanced tables)
``MPI_Gatherv``         :func:`gatherv_bag`
``MPI_Allgatherv``      :func:`all_gatherv_bag` (+ ``_dist`` / ``_start``)
``MPI_Alltoallv``       :func:`all_to_allv_bag` (+ ``_start``)
``Reduce_scatter`` (v)  :func:`reduce_scatterv_bag` (+ ``_start``)
======================  =====================================================

The non-blocking twins share the dense collectives'
``_issue_*``/:class:`Pending` request layer; blocking = ``_start().wait()``
by construction.

Comm plans
----------
:mod:`repro.core.plan` lifts the request layer one level up: an algorithm
declares its communication schedule once (:func:`ring` / :func:`halo` /
:func:`pipeline` / ``stagger`` / :func:`dispatch` — the MPI
persistent-request / ``MPI_Start`` pattern) and the planner emits the
double-buffered program with a bit-identical blocking interpretation.  Each
plan carries a declared overlap intent that
``repro.launch.hlo_walk.plan_agreement`` verifies against the compiled HLO.

Serving on the comm layer
-------------------------
The continuous-batching engine (:mod:`repro.serve`) is the same abstraction
stack driven from the other end: every serving phase is one of the layer's
collectives over the request-length extents table.

======================  =====================================================
Engine phase            MPI analogue (repro.core construct)
======================  =====================================================
KV cache residency      ragged ``DistBag``: uniform capacity tiles (slots x
                        max_len) + per-request valid extents
                        (``repro.serve.kv.KVLedger`` — the ``recvcounts``
                        table, applied to memory instead of the wire)
admission-time prefill  ``Allgatherv`` over sequence shards: the prompt
                        chunk's ring attention (``sp_ring`` plan) rotates
                        KV shards exactly like the v-collective's ragged
                        tiles, masked to each request's valid length
decode (per layer)      ``Iallreduce`` (tensor-parallel partial sums) /
                        ``Iallgather`` (vocab-sharded logits) issued through
                        the shared :class:`Pending` request path
                        (:mod:`repro.serve.tp_decode`)
decode schedule         ``stagger`` comm plan: persistent-request round-robin
                        over independent microbatches — microbatch *i*'s
                        reduction completes behind microbatch *i+1*'s
                        compute, so no collective sits on the decode
                        critical path (``dryrun --serve`` gates 0
                        serialized)
slot release/admit      extents-table update — the same bookkeeping a
                        ragged redistribution performs before reusing a tile
======================  =====================================================

Attention kernel dispatch
-------------------------
The comm plans above schedule the *wire*; the per-step *compute* they
overlap against is kernelized in :mod:`repro.kernels`.  Two Pallas hot
paths plug into the plans' compute slots (full table in
``repro.models.attention``):

* ``flash_attention_carry`` — one ``sp_ring`` ring step as a single
  carry-state flash kernel over the resident Q chunk vs the held KV block,
  threading unnormalized ``(acc, m, l)`` across hops (input/output aliased,
  so the chained result is bit-identical to the single-shot kernel at f32);
* ``flash_decode`` — flash decoding over the serving engine's KV cache:
  the cache blocks stream through an online softmax held in VMEM, masked by
  each slot's ``cache_len`` and start position (scalar-prefetched extents).

Defaults resolve per backend (TPU -> compiled Pallas, CPU -> jnp
reference); ``impl="interpret"`` runs the same kernels through the Pallas
interpreter so the dry-run gates (``dryrun --sp-ring/--serve
--attn-impl interpret``) prove overlap with the real kernels in the trace.

MoE dispatch
------------
Expert-parallel mixture-of-experts routing is the v-collective layer's
``MPI_Alltoallv`` showcase (:func:`repro.models.ffn.moe_expert_parallel`,
selected by ``cfg.moe_dispatch = "ep"``): the router's per-(rank, expert)
token counts ARE the counts/displacements tables, experts shard *raggedly*
over the model ranks (``ragged_expert_extents`` — ``n_experts`` need not
divide the axis), and the two wire legs ride the :func:`dispatch` comm
plan, double-buffered over expert groups so both classify *overlapped*.

======================  =====================================================
MoE phase               MPI analogue (repro.core construct)
======================  =====================================================
routing/slotting        shard-local counts-table fill: top-k gates scatter
                        tokens into packed (group, dest rank, expert, slot)
                        rows — building ``sendcounts``/``sdispls`` without
                        touching the wire
token dispatch          ``Ialltoallv`` (:func:`all_to_allv_start`): ragged
                        split over the destination model ranks; zero-count
                        experts ride through as zero split extents, padding
                        is wire-vs-valid accounted (``dryrun --moe``)
expert GEMMs            :func:`rank_map` over the *resident* rows only —
                        each rank contracts its own experts' tokens, indexed
                        through host-built displacement tables
gated combine           the inverse ``Ialltoallv`` returns expert outputs to
                        their token owners, concatenating back into exactly
                        the packed scatter order before the gate-weighted sum
schedule                :func:`dispatch` comm plan: issue group *g+1*'s
                        dispatch before waiting on *g*, issue *g*'s combine
                        right after its GEMMs — both a2a legs complete
                        behind sibling expert compute (``dryrun --moe``
                        gates 0 serialized; one group = the serialized
                        negative control)
======================  =====================================================

Training comm
-------------
The explicit ZeRO-2 train step (:func:`repro.train.trainer.
make_zero_train_step`) is the layer's flat-shard v-collective showcase:
gradients pack into dtype-homogeneous buckets whose counts/displacements
tables span the flattened param pytree (:mod:`repro.train.buckets`), and
every wire leg rides the :func:`bucket` comm plan.

======================  =====================================================
Training phase          MPI analogue (repro.core construct)
======================  =====================================================
grad bucketing          counts/displacements over the flat param space —
                        the ``MPI_Type_indexed`` tables, built once from
                        the abstract params (no wire traffic)
bucket grad reduce      ``MPI_Ireduce_scatter``
                        (:func:`shard_reduce_scatterv_start`): each bucket's
                        flat sum scatters into per-rank capacity shards the
                        moment the backward produces it; sibling buckets'
                        norm/update math hides the wire (``dryrun --train``
                        gates 0 serialized; the whole-model single bucket is
                        the serialized negative control)
grad-norm clip          ``MPI_Iallreduce`` of the per-shard squared-norm
                        partial sums — one scalar on the wire regardless of
                        bucket count
sharded AdamW           :func:`rank_map` discipline over the 1/R optimizer
                        shard: moments live as flat ``P("data")`` buffers
                        (ZeRO partitioning), each rank updates only its
                        capacity slice
param prefetch          ``MPI_Iallgatherv`` (:func:`shard_all_gatherv_start`):
                        updated shards regather into full params off the
                        compute chain — the prefetch for the next forward
======================  =====================================================
"""
from .dims import LayoutError, ceil_div, common_refinement, ragged_split
from .layout import (
    Axis,
    Layout,
    ProtoStructure,
    scalar,
    vector,
    vectors,
    vectors_like,
    into_blocks,
    hoist,
    reorder,
    rename,
    set_length,
    fix_dim,
)
from .layout import merge_blocks as merge_blocks_layout
from .bag import Bag, bag, idx
from .traverser import (
    Traverser,
    traverser,
    fix,
    span,
    bcast,
    merge_blocks,
)
from .traverser import hoist as hoist_trav
from .traverser import set_length as set_length_trav
from .relayout import RelayoutPlan, check_ragged_dims, relayout, relayout_plan, transfer_kind
from .request import Pending, wait_all
from .dist import DistTraverser, make_mesh, mpi_traverser, mpi_cart_traverser
from .collectives import (
    DistBag,
    scatter,
    gather,
    broadcast,
    all_gather_bag,
    all_gather_dist,
    all_reduce_bag,
    reduce_scatter_bag,
    all_to_all_bag,
    all_gather_start,
    all_reduce_start,
    reduce_scatter_start,
    all_to_all_start,
    grid_extents,
    scatterv_bag,
    gatherv_bag,
    all_gatherv_bag,
    all_gatherv_dist,
    all_gatherv_start,
    all_to_allv_bag,
    all_to_allv_start,
    reduce_scatterv_bag,
    reduce_scatterv_start,
    reduce_identity,
    dist_full,
    dist_sharding,
    rank_map,
    shard_all_gatherv_start,
    shard_reduce_scatterv_start,
)
from .plan import (CommPlan, bucket, dispatch, halo, intent_of, pipeline,
                   ring, stagger)
from .p2p import (
    PendingTile,
    permute,
    permute_start,
    ring_shift,
    ring_shift_start,
    send_recv,
    shard_all_gather_start,
    shard_all_reduce_start,
    shard_reduce_scatter_start,
    shard_ring_shift,
    shard_ring_shift_start,
    wait,
)

__all__ = [
    "LayoutError",
    "ceil_div",
    "common_refinement",
    "ragged_split",
    "check_ragged_dims",
    "Axis",
    "Layout",
    "ProtoStructure",
    "scalar",
    "vector",
    "vectors",
    "vectors_like",
    "into_blocks",
    "hoist",
    "reorder",
    "rename",
    "set_length",
    "fix_dim",
    "merge_blocks_layout",
    "Bag",
    "bag",
    "idx",
    "Traverser",
    "traverser",
    "fix",
    "span",
    "bcast",
    "merge_blocks",
    "hoist_trav",
    "set_length_trav",
    "RelayoutPlan",
    "relayout",
    "relayout_plan",
    "transfer_kind",
    "DistTraverser",
    "mpi_traverser",
    "mpi_cart_traverser",
    "make_mesh",
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
    "reduce_identity",
    "dist_full",
    "dist_sharding",
    "rank_map",
    "shard_all_gatherv_start",
    "shard_reduce_scatterv_start",
    "DistBag",
    "Pending",
    "wait_all",
    "CommPlan",
    "ring",
    "halo",
    "pipeline",
    "stagger",
    "dispatch",
    "bucket",
    "intent_of",
    "send_recv",
    "permute",
    "ring_shift",
    "PendingTile",
    "permute_start",
    "ring_shift_start",
    "shard_all_gather_start",
    "shard_all_reduce_start",
    "shard_reduce_scatter_start",
    "shard_ring_shift",
    "shard_ring_shift_start",
    "wait",
]

"""IndexCore — the shard-agnostic heart of the Jasper index.

One capacity-allocated pytree holds everything a single shard needs to
serve the full mutation lifecycle — packed RaBitQ codes, adjacency,
tombstone/free-pool state, medoid/meta — and a set of pure core ops
(`core_search`, `core_insert_at`, `core_delete`, `core_consolidate`,
`core_grow`) operates on it. Single-device `JasperIndex` is a thin host
driver over ONE core; `ShardedJasperIndex` (core/distributed.py) is the
same driver with the core `shard_map`-wrapped per row-shard. Neither
backend carries its own search/insert logic: the 1-shard case and the
N-shard case are literally the same functions.

Layout invariants the sharded layer relies on:

  * every array is capacity-major, so a stacked (S, cap, ...) view of S
    cores is the row-sharded global state and `shard_map` hands each
    device a bit-identical local core;
  * `tombstone_bits` packs 8 rows/byte — capacities must be multiples of
    8 so per-shard bitmaps concatenate cleanly (init_core enforces it);
  * `rq_params` (rotation/centroid) is dataset-level state, replicated
    across shards; `codes` (one row per vector: packed bytes + the two
    per-row factors) is row state, sharded like vectors.

All core ops are pure: they take a core (plus host-shaped scalars) and
return a new core. Host concerns — slot allocation, quantizer training,
MIPS augmentation, capacity-doubling policy — stay in the drivers.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.beam_search import (
    beam_search,
    beam_search_quantized,
    make_exact_scorer,
    rerank_frontier,
)
from repro.core.construction import (
    ConstructionParams,
    batch_insert_at,
    bootstrap_graph,
    build_graph,
)
from repro.core.mutations import (
    N_LABEL_BYTES,
    MutationState,
    consolidate as consolidate_graph,
    delete_rows,
    grow_rows,
    grow_state,
    init_mutation_state,
    take_free_slots,
    unpack_bitmap,
)
from repro.core.rabitq import (
    RaBitQCodes,
    RaBitQParams,
    pack_codes,
    packed_dim,
    rabitq_encode,
    rabitq_preprocess_query,
)
from repro.core.vamana import VamanaGraph

Array = jax.Array

_INF = float("inf")


# ---------------------------------------------------------------------------
# The core pytree
# ---------------------------------------------------------------------------

@partial(jax.tree_util.register_dataclass,
         data_fields=("vectors", "vec_sqnorm", "adjacency", "n_valid",
                      "medoid", "mut", "codes", "rq_params"),
         meta_fields=())
@dataclass(frozen=True)
class IndexCore:
    """One shard's complete index state (a pure pytree).

    vectors:    f32[cap, D]      full-precision rows (rerank / exact path)
    vec_sqnorm: f32[cap]         cached |row|^2
    adjacency:  int32[cap, R]    Vamana out-edges, -1 padded
    n_valid:    int32 scalar     high-water mark (prefix of written rows)
    medoid:     int32 scalar     search/construction entry point
    mut:        MutationState    tombstone bitmap + free pool + generation
    codes:      RaBitQCodes|None packed quantized rows (canonical HBM form)
    rq_params:  RaBitQParams|None dataset-level quantizer (replicated)
    """

    vectors: Array
    vec_sqnorm: Array
    adjacency: Array
    n_valid: Array
    medoid: Array
    mut: MutationState
    codes: RaBitQCodes | None
    rq_params: RaBitQParams | None

    @property
    def capacity(self) -> int:
        return self.adjacency.shape[0]

    @property
    def store_dims(self) -> int:
        return self.vectors.shape[1]

    @property
    def degree_bound(self) -> int:
        return self.adjacency.shape[1]

    @property
    def graph(self) -> VamanaGraph:
        return VamanaGraph(adjacency=self.adjacency, n_valid=self.n_valid,
                           medoid=self.medoid)


def init_core(capacity: int, store_dims: int, degree_bound: int) -> IndexCore:
    """Empty core. (The sharded layer additionally requires per-shard
    capacities divisible by 8 so tombstone bitmaps concatenate cleanly —
    enforced there, not here: a lone core packs any capacity.)"""
    return IndexCore(
        vectors=jnp.zeros((capacity, store_dims), jnp.float32),
        vec_sqnorm=jnp.zeros((capacity,), jnp.float32),
        adjacency=jnp.full((capacity, degree_bound), -1, jnp.int32),
        n_valid=jnp.int32(0),
        medoid=jnp.int32(0),
        mut=init_mutation_state(capacity),
        codes=None,
        rq_params=None,
    )


def with_graph(core: IndexCore, graph: VamanaGraph) -> IndexCore:
    return replace(core, adjacency=graph.adjacency, n_valid=graph.n_valid,
                   medoid=graph.medoid)


def attach_quantizer(core: IndexCore, params: RaBitQParams) -> IndexCore:
    """Install a trained quantizer + capacity-allocated packed buffers."""
    cap = core.capacity
    # all-zero rows: codes 0 and both factors +0.0
    codes = RaBitQCodes(
        rows=jnp.zeros((cap, packed_dim(core.store_dims, params.bits) + 8),
                       jnp.uint8),
        bits=params.bits, dims=core.store_dims)
    return replace(core, codes=codes, rq_params=params)


# ---------------------------------------------------------------------------
# Pure core ops
# ---------------------------------------------------------------------------

def core_write_rows(core: IndexCore, ids: Array, rows: Array) -> IndexCore:
    """Write vector rows (+ fused encode into the packed code buffer)."""
    ids = jnp.asarray(ids, jnp.int32)
    vectors = core.vectors.at[ids].set(rows)
    sqnorm = core.vec_sqnorm.at[ids].set(jnp.sum(rows * rows, axis=-1))
    codes = core.codes
    if codes is not None:
        enc = rabitq_encode(core.rq_params, rows)
        codes = replace(codes, rows=codes.rows.at[ids].set(enc.rows))
    return replace(core, vectors=vectors, vec_sqnorm=sqnorm, codes=codes)


def core_set_labels(core: IndexCore, ids, label_rows) -> IndexCore:
    """Write per-row label bitsets (uint8[B, N_LABEL_BYTES]) for `ids`.

    Labels are row metadata like vec_sqnorm — set at insert, cleared when
    a slot is reused, moved with the row through rebalance/reshard. Does
    not bump the generation: the caller's insert already did.
    """
    labels = core.mut.labels.at[jnp.asarray(ids, jnp.int32)].set(
        jnp.asarray(label_rows, jnp.uint8))
    return replace(core, mut=replace(core.mut, labels=labels))


@partial(jax.jit, static_argnames=("params",))
def core_insert_at(core: IndexCore, ids: Array, rows: Array, *,
                   params: ConstructionParams) -> IndexCore:
    """Write + graph-link a batch of (already slot-allocated) rows.

    ids need not be contiguous (the drivers reuse freed slots). n_valid
    advances to the high-water mark; the generation counter bumps once.
    """
    core = core_write_rows(core, ids, rows)
    graph = batch_insert_at(core.vectors, core.graph,
                            jnp.asarray(ids, jnp.int32), params=params,
                            vec_sqnorm=core.vec_sqnorm,
                            tombstone_bits=core.mut.tombstone_bits)
    core = with_graph(core, graph)
    return replace(core, mut=replace(core.mut,
                                     generation=core.mut.generation + 1))


@partial(jax.jit, static_argnames=("n0", "params"))
def core_bootstrap(core: IndexCore, rows: Array, *, n0: int,
                   params: ConstructionParams) -> IndexCore:
    """All-pairs bootstrap over the first n0 rows (empty-core base case)."""
    core = core_write_rows(core, jnp.arange(n0, dtype=jnp.int32), rows)
    graph = bootstrap_graph(core.vectors, core.graph, n0=n0, params=params)
    return with_graph(core, graph)


def core_build(core: IndexCore, data: Array, *, params: ConstructionParams,
               refine: bool = False, progress_fn=None) -> IndexCore:
    """Bulk construction (host driver): reset mutation state, write rows
    0..N, bootstrap + prefix-doubling batch insertion."""
    n = data.shape[0]
    if n > core.capacity:
        raise ValueError(f"data size {n} exceeds capacity {core.capacity}")
    core = replace(
        core,
        mut=replace(init_mutation_state(core.capacity),
                    generation=core.mut.generation + 1))
    core = core_write_rows(core, jnp.arange(n, dtype=jnp.int32), data)
    graph = build_graph(core.vectors, n, params=params, refine=refine,
                        progress_fn=progress_fn)
    core = with_graph(core, graph)
    jax.block_until_ready(core.adjacency)       # storage semantics
    return core


@partial(jax.jit, static_argnames=("spec", "filter_tombstones"))
def core_search(core: IndexCore, queries: Array, *, spec,
                filter_tombstones: bool = True,
                filter_bytes: Array | None = None) -> tuple:
    """THE search path — exact and quantized, kernel and jnp, 1..N shards.

    spec: a `ResolvedSearchSpec` (frozen/hashable, so it is ONE static jit
      argument instead of the former 11-kwarg explosion). Build it with
      `SearchSpec(...).resolve()` — all default formulas and validation
      live there, never here.
    queries are already metric-prepped (the drivers handle MIPS
    augmentation). Returns (ids (Q,k), dists (Q,k), n_hops (Q,)) — and
    with spec.telemetry == "on", a fourth `SearchTelemetry` element (the
    static branch keeps "off" bit-identical to a pre-telemetry build:
    same tuple arity, zero extra kernel outputs).

    spec.quantized: beam-search on RaBitQ estimated distances over the
      packed codes; spec.use_kernels routes scoring through the fused
      Pallas `rabitq_search_step` kernel (in-VMEM unpack + MXU dot +
      masking epilogue). spec.rerank then re-scores the final frontier
      exactly, tiled `spec.rerank_tile` queries at a time.
    filter_tombstones: False skips every bitmap lookup — the drivers pass
      it when no bit can possibly be set, keeping the delete-free
      workload on filter-free executables. (Execution-time liveness, not
      configuration: deliberately NOT a spec field.)
    spec.traverse_deleted: False additionally folds the bitmap into the
      scoring epilogues (kernel paths fuse the per-candidate byte gather).
    filter_bytes: uint8[N_LABEL_BYTES] label filter (runtime operand —
      the plan never splits on filter VALUES). Must be present iff the
      spec was resolved from one with a `filter` (spec.filtered is the
      static presence bit). Rows whose label bitset does not intersect it
      are never returned; spec.filter_mode == "exclude" additionally
      masks them during the walk (the traverse/exclude split mirrors
      traverse_deleted).
    """
    k = spec.k
    tomb = core.mut.tombstone_bits if filter_tombstones else None
    graph = core.graph
    tel_on = spec.telemetry == "on"
    filtered = spec.filtered
    if filtered != (filter_bytes is not None):
        raise ValueError(
            "spec.filtered and the filter_bytes operand must agree: "
            f"filtered={filtered}, filter_bytes "
            f"{'present' if filter_bytes is not None else 'absent'}")
    labels = core.mut.labels if filtered else None
    fb = jnp.asarray(filter_bytes, jnp.uint8) if filtered else None
    filter_exclude = filtered and spec.filter_mode == "exclude"

    def _out(ids, dists, res):
        if tel_on:
            return ids, dists, res.n_hops, res.telemetry
        return ids, dists, res.n_hops

    if spec.fusion != "none":
        # fused execution: ONE Pallas launch per hop ("hop") or per search
        # ("megakernel") — gather + score + liveness + top-L merge fused,
        # frontier state on-chip. Same shapes in/out as the unfused loop,
        # so the rerank / k-slice epilogue below is shared verbatim.
        from repro.kernels.search_step.ops import fused_beam_search
        if spec.quantized:
            if core.codes is None:
                raise ValueError("core has no quantized codes")
            rq = rabitq_preprocess_query(core.rq_params, queries)
            res = fused_beam_search(
                graph, mode=spec.fusion, beam_width=spec.beam_width,
                max_iters=spec.max_iters, beam_schedule=spec.beam_schedule,
                codes=core.codes, rq_query=rq, tombstone_bits=tomb,
                traverse_deleted=spec.traverse_deleted,
                labels=labels, filter_bytes=fb,
                filter_exclude=filter_exclude,
                telemetry=tel_on)
            if spec.rerank_source == "host":
                # host-tier rerank: core.vectors may be evicted (None),
                # so hand the driver the FULL-width estimator frontier —
                # the gather + exact rerank run outside this graph
                # (core/storage.py), bit-identical to the branch below
                return _out(res.frontier_ids, res.frontier_dists, res)
            if spec.rerank:
                exact_d = rerank_frontier(
                    core.vectors, core.vec_sqnorm, queries,
                    res.frontier_ids, tile_q=spec.rerank_tile,
                    use_kernels=spec.use_kernels)
                sd, si = jax.lax.sort((exact_d, res.frontier_ids),
                                      dimension=1, is_stable=True,
                                      num_keys=1)
                si = jnp.where(jnp.isfinite(sd), si, -1)
                return _out(si[:, :k], sd[:, :k], res)
        else:
            res = fused_beam_search(
                graph, mode=spec.fusion, beam_width=spec.beam_width,
                max_iters=spec.max_iters, beam_schedule=spec.beam_schedule,
                queries=queries, vectors=core.vectors,
                vec_sqnorm=core.vec_sqnorm, tombstone_bits=tomb,
                traverse_deleted=spec.traverse_deleted,
                labels=labels, filter_bytes=fb,
                filter_exclude=filter_exclude,
                telemetry=tel_on)
        return _out(res.frontier_ids[:, :k], res.frontier_dists[:, :k], res)
    if spec.quantized:
        if core.codes is None:
            raise ValueError("core has no quantized codes")
        rq = rabitq_preprocess_query(core.rq_params, queries)
        res = beam_search_quantized(
            graph, core.codes, rq, beam_width=spec.beam_width,
            max_iters=spec.max_iters, expand_per_iter=spec.expand,
            use_kernels=spec.use_kernels, merge_strategy=spec.merge,
            tombstone_bits=tomb, traverse_deleted=spec.traverse_deleted,
            labels=labels, filter_bytes=fb, filter_exclude=filter_exclude,
            beam_schedule=spec.beam_schedule, telemetry=tel_on)
        if spec.rerank_source == "host":
            # full-width estimator frontier for the driver-side host
            # rerank (see the fused branch above)
            return _out(res.frontier_ids, res.frontier_dists, res)
        if spec.rerank:
            exact_d = rerank_frontier(
                core.vectors, core.vec_sqnorm, queries, res.frontier_ids,
                tile_q=spec.rerank_tile, use_kernels=spec.use_kernels)
            sd, si = jax.lax.sort((exact_d, res.frontier_ids), dimension=1,
                                  is_stable=True, num_keys=1)
            si = jnp.where(jnp.isfinite(sd), si, -1)
            return _out(si[:, :k], sd[:, :k], res)
    else:
        if spec.use_kernels:
            from repro.kernels.distance.ops import make_kernel_scorer
            score = make_kernel_scorer(
                core.vectors, queries, graph.n_valid, core.vec_sqnorm,
                tombstone_bits=(None if spec.traverse_deleted else tomb),
                labels=(labels if filter_exclude else None),
                filter_bytes=(fb if filter_exclude else None))
        else:
            score = make_exact_scorer(core.vectors, queries, graph.n_valid,
                                      core.vec_sqnorm)
        res = beam_search(graph, score, queries.shape[0],
                          beam_width=spec.beam_width,
                          max_iters=spec.max_iters,
                          expand_per_iter=spec.expand,
                          merge_strategy=spec.merge,
                          tombstone_bits=tomb,
                          traverse_deleted=spec.traverse_deleted,
                          labels=labels, filter_bytes=fb,
                          filter_exclude=filter_exclude,
                          beam_schedule=spec.beam_schedule,
                          telemetry=tel_on)
    return _out(res.frontier_ids[:, :k], res.frontier_dists[:, :k], res)


@partial(jax.jit, static_argnames=("k",))
def core_brute_force(core: IndexCore, queries: Array, *, k: int
                     ) -> tuple[Array, Array]:
    """Exact top-k full scan over LIVE rows (recall ground truth)."""
    from repro.core.distances import pairwise_l2_squared
    d = pairwise_l2_squared(queries, core.vectors, core.vec_sqnorm)
    cap = core.capacity
    mask = ((jnp.arange(cap) < core.n_valid)
            & ~unpack_bitmap(core.mut.tombstone_bits, cap))
    d = jnp.where(mask[None, :], d, jnp.inf)
    neg, ids = jax.lax.top_k(-d, k)
    return ids.astype(jnp.int32), -neg


@jax.jit
def core_delete(core: IndexCore, padded_ids: Array
                ) -> tuple[IndexCore, Array]:
    """Tombstone a padded batch of row ids (-1 = ignored). O(graph) = 0."""
    mut, n_new = delete_rows(core.mut, padded_ids, core.n_valid)
    return replace(core, mut=mut), n_new


def core_consolidate(core: IndexCore, *, params: ConstructionParams,
                     refine: bool = True) -> tuple[IndexCore, dict]:
    """Graph repair around tombstoned rows; frees their slots (host driver,
    shard-local — no cross-shard coordination is ever needed)."""
    graph, mut, stats = consolidate_graph(
        core.vectors, core.graph, core.mut, params=params, refine=refine,
        vec_sqnorm=core.vec_sqnorm)
    return replace(with_graph(core, graph), mut=mut), stats


def core_take_free_slots(core: IndexCore, want: int
                         ) -> tuple[IndexCore, np.ndarray]:
    """Pop up to `want` reusable slots (host-side: shapes downstream)."""
    mut, taken = take_free_slots(core.mut, want)
    return replace(core, mut=mut), taken


def core_grow(core: IndexCore, new_capacity: int) -> IndexCore:
    """Copy-extend every buffer to a larger capacity. Nothing re-encodes:
    all arrays are capacity-major, so the resident prefix (packed codes
    included) is byte-identical after the grow."""
    if new_capacity == core.capacity:
        return core
    codes = core.codes
    if codes is not None:
        codes = replace(codes, rows=grow_rows(codes.rows, new_capacity, 0))
    return replace(
        core,
        vectors=grow_rows(core.vectors, new_capacity, 0.0),
        vec_sqnorm=grow_rows(core.vec_sqnorm, new_capacity, 0.0),
        adjacency=grow_rows(core.adjacency, new_capacity, -1),
        mut=grow_state(core.mut, new_capacity),
        codes=codes)


# ---------------------------------------------------------------------------
# Host-side inspection helpers (shared by both drivers)
# ---------------------------------------------------------------------------

def core_size(core: IndexCore) -> int:
    """Number of LIVE rows (high-water mark minus tombstoned/freed)."""
    return (int(core.n_valid) - int(core.mut.n_deleted)
            - int(core.mut.n_free))


def core_live_mask(core: IndexCore) -> np.ndarray:
    """bool[capacity] of currently live rows (host copy)."""
    dense = np.asarray(unpack_bitmap(core.mut.tombstone_bits, core.capacity))
    return (np.arange(core.capacity) < int(core.n_valid)) & ~dense


def core_live_locals(core: IndexCore) -> np.ndarray:
    """Ascending local ids of the live rows — the canonical per-shard row
    order resharding and rebalancing deal from (host copy)."""
    return np.where(core_live_mask(core))[0].astype(np.int64)


def bitmap_test_np(tombstone_bits: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Host-side per-id bit test over the PACKED bytes (one byte gather +
    shift/mask per id) — the single definition of the bitmap encoding on
    the host; every delete-validation / serving-contract check goes
    through here so the encoding can never silently diverge.

    Out-of-domain ids read as NOT SET: the `-1` dead-id sentinel (used by
    `IdTranslation`, masked frontiers, and padded merges) used to wrap via
    numpy's arithmetic shift (`-1 >> 3 == -1`) into the LAST byte and
    return that row's bit — garbage liveness. Ids past the bitmap (e.g. a
    global id against a smaller shard bitmap) were an index error waiting
    to happen; both are now clamped and masked to a defined False.
    """
    ids = np.asarray(ids)
    bits = np.asarray(tombstone_bits)
    n_bits = bits.size * 8
    in_domain = (ids >= 0) & (ids < n_bits)
    safe = np.clip(ids, 0, max(n_bits - 1, 0))
    return (((bits[safe >> 3] >> (safe & 7)) & 1) == 1) & in_domain


def tombstoned_lookup(tombstone_bits: np.ndarray, n_valid: int,
                      ids: np.ndarray) -> np.ndarray:
    """Host-side per-id deadness test: True where an id is tombstoned/freed,
    past the high-water mark, or not a real row at all (negative sentinel).
    The serving layer's contract check — the bitmap never unpacks densely."""
    ids = np.asarray(ids)
    return bitmap_test_np(tombstone_bits, ids) | (ids >= n_valid) | (ids < 0)


# ---------------------------------------------------------------------------
# Checkpoint form — ONE array-dict format for 1..N shards
# ---------------------------------------------------------------------------

def core_to_arrays(core: IndexCore) -> dict[str, np.ndarray]:
    """The canonical .npz payload (JasperIndex and every shard of
    ShardedJasperIndex serialize through this one function)."""
    arrays = {
        "vectors": np.asarray(core.vectors),
        "adjacency": np.asarray(core.adjacency),
        "n_valid": np.asarray(core.n_valid),
        "medoid": np.asarray(core.medoid),
        "tombstone_bits": np.asarray(core.mut.tombstone_bits),
        "labels": np.asarray(core.mut.labels),
        "free_ids": np.asarray(core.mut.free_ids),
        "n_free": np.asarray(core.mut.n_free),
        "n_deleted": np.asarray(core.mut.n_deleted),
        "generation": np.asarray(core.mut.generation),
    }
    if core.codes is not None:
        arrays |= {
            "rq_packed": np.asarray(core.codes.packed),
            "rq_add": np.asarray(core.codes.data_add),
            "rq_rescale": np.asarray(core.codes.data_rescale),
            "rq_rotation": np.asarray(core.rq_params.rotation),
            "rq_centroid": np.asarray(core.rq_params.centroid),
        }
    return arrays


def core_from_arrays(data: Mapping, *, bits: int, store_dims: int,
                     quantized: bool) -> IndexCore:
    """Inverse of core_to_arrays (accepts legacy unpacked `rq_codes`)."""
    vectors = jnp.asarray(data["vectors"])
    mut_kwargs = {}
    if "tombstone_bits" in data:
        mut_kwargs = dict(
            tombstone_bits=jnp.asarray(data["tombstone_bits"]),
            # pre-label-plane checkpoints: all-zero rows (match no filter)
            labels=(jnp.asarray(data["labels"]) if "labels" in data
                    else jnp.zeros((vectors.shape[0], N_LABEL_BYTES),
                                   jnp.uint8)),
            free_ids=jnp.asarray(data["free_ids"]),
            n_free=jnp.asarray(data["n_free"]),
            n_deleted=jnp.asarray(data["n_deleted"]),
            generation=jnp.asarray(data["generation"]))
        mut = MutationState(**mut_kwargs)
    else:   # pre-mutation-engine checkpoint: everything is prefix-live
        mut = init_mutation_state(vectors.shape[0])
    codes = rq_params = None
    has_codes = "rq_packed" in data or "rq_codes" in data
    if quantized and has_codes:
        rq_params = RaBitQParams(rotation=jnp.asarray(data["rq_rotation"]),
                                 centroid=jnp.asarray(data["rq_centroid"]),
                                 bits=bits)
        if "rq_packed" in data:
            packed = jnp.asarray(data["rq_packed"])
        else:   # legacy checkpoint with unpacked uint8[N, D] codes
            packed = pack_codes(jnp.asarray(data["rq_codes"]), bits)
        codes = RaBitQCodes.from_parts(packed, jnp.asarray(data["rq_add"]),
                                       jnp.asarray(data["rq_rescale"]),
                                       bits, store_dims)
    return IndexCore(
        vectors=vectors,
        vec_sqnorm=jnp.sum(vectors * vectors, axis=-1),
        adjacency=jnp.asarray(data["adjacency"]),
        n_valid=jnp.asarray(data["n_valid"]),
        medoid=jnp.asarray(data["medoid"]),
        mut=mut, codes=codes, rq_params=rq_params)

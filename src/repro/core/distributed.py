"""ShardedJasperIndex — the IndexCore driver, shard_map-wrapped per row-shard.

Since the IndexCore unification there is exactly ONE index implementation:
the pure core ops in `core.index_core`. This module runs them under
`shard_map` over the mesh's row axes, so an S-shard index is S independent
cores plus a k-way merge — and the single-device `JasperIndex` is literally
the 1-shard case (both drivers call the same `core_search`,
`core_insert_at`, `core_delete`, `core_consolidate`, `core_grow`; no
search or insert logic lives here).

Layout (FAISS/ScaNN-style shard-and-merge, scaled for 100M–100B rows):

  * database rows are dealt over the row axes — each device owns an
    INDEPENDENT core (graph edges never cross shards, so construction and
    consolidation have zero cross-device traffic). Every capacity-major
    array stacks to the sharded global form: vectors (S*cap, D), packed
    RaBitQ codes (S*cap, P), tombstone bitmaps (S*cap/8,) — per-shard
    liveness is a bitmap slice, so shard-local deletes need NO
    coordination and ride into the fused kernel epilogue per shard;
  * `rq_params` (rotation/centroid) is dataset-level state, replicated;
  * queries shard over the `model` axis (query parallelism);
  * search: shard-local `core_search` (packed codes through the fused
    Pallas `rabitq_search_step` scorer, per-shard tombstone masking,
    shard-local exact rerank) -> local top-k -> all_gather over each row
    axis in turn -> partial top-k merge. The collective moves only
    Q*k*8 bytes per hop, which is why the roofline stays memory-local.

Adjacency entries and free pools hold SHARD-LOCAL ids; global ids are
`shard * id_stride + local`, reconstructed at merge time. `id_stride` is
FIXED at construction (default 4x the initial per-shard capacity), so the
ids handed to clients are layout-independent: capacity can grow (per-shard
copy-extension, packed codes bit-identical) without invalidating a single
outstanding id. Growing past the stride raises — choose a larger
`id_stride` up front for more headroom. All graph arithmetic stays int32
even at 100B rows per pod (the GANNS int32-overflow failure the paper
reports cannot happen here).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.beam_search import SearchTelemetry
from repro.core.construction import MAX_BATCH, ConstructionParams
from repro.core.index_core import (
    IndexCore,
    attach_quantizer,
    bitmap_test_np,
    core_bootstrap,
    core_consolidate,
    core_delete,
    core_from_arrays,
    core_insert_at,
    core_search,
    core_set_labels,
    core_to_arrays,
    init_core,
)
from repro.core.mutations import MutationState, pack_label_rows
from repro.core.rabitq import RaBitQCodes, RaBitQParams, rabitq_train
from repro.core.resharding import pow2_rung
from repro.core.search_spec import PlanCache, SearchSpec, SearchSurface
from repro.core.storage import (
    TIER_STAT_KEYS,
    VectorStore,
    build_sharded_host_rerank_plan,
    rows_staged,
    tier_memory_stats,
)
from repro.obs.tracing import span as obs_span

Array = jax.Array


def _pow2_pad_pairs(ids: np.ndarray, rows: np.ndarray
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Pad an (ids, rows) insert batch to a power-of-two rung by repeating
    the first pair — a duplicate insert_at is an idempotent re-link, so
    uneven rebalance batches reuse one executable per rung."""
    extra = pow2_rung(ids.size) - ids.size
    return (np.concatenate([ids, np.repeat(ids[:1], extra)]),
            np.concatenate([rows, np.repeat(rows[:1], extra, axis=0)]))


@dataclass(frozen=True)
class ShardSpec:
    """Static sharding geometry.

    row_axes:   mesh axes that shard database rows (e.g. ("pod", "data"))
    query_axis: mesh axis that shards the query batch (e.g. "model")
    """

    row_axes: tuple[str, ...] = ("data",)
    query_axis: str | None = "model"


# ---------------------------------------------------------------------------
# Core layout: PartitionSpec / NamedSharding pytrees mirroring IndexCore
# ---------------------------------------------------------------------------

def _core_layout(template: IndexCore, row_axes, wrap):
    """IndexCore-shaped pytree of `wrap(spec)` — row-major arrays shard
    over the row axes, per-shard scalars are (S,) vectors on the same axes,
    and dataset-level quantizer state is replicated."""
    row2 = wrap(P(row_axes, None))
    row1 = wrap(P(row_axes))
    repl = wrap(P())
    mut = MutationState(tombstone_bits=row1, labels=row2, free_ids=row1,
                        n_free=row1, n_deleted=row1, generation=row1)
    codes = None
    if template.codes is not None:
        codes = RaBitQCodes(packed=row2, data_add=row1, data_rescale=row1,
                            bits=template.codes.bits,
                            dims=template.codes.dims)
    rq = None
    if template.rq_params is not None:
        rq = RaBitQParams(rotation=repl, centroid=repl,
                          bits=template.rq_params.bits)
    # rows evicted to the host tier are None leaves (core/storage.py) —
    # the layout pytree must mirror the structure exactly
    return IndexCore(
        vectors=None if template.vectors is None else row2,
        vec_sqnorm=None if template.vec_sqnorm is None else row1,
        adjacency=row2, n_valid=row1, medoid=row1, mut=mut, codes=codes,
        rq_params=rq)


def core_partition_specs(template: IndexCore, spec: ShardSpec) -> IndexCore:
    return _core_layout(template, spec.row_axes, lambda p: p)


def core_shardings(mesh: Mesh, template: IndexCore,
                   spec: ShardSpec) -> IndexCore:
    return _core_layout(template, spec.row_axes,
                        lambda p: NamedSharding(mesh, p))


def _local_core(stacked: IndexCore) -> IndexCore:
    """Inside shard_map: turn the local block (scalars arrive as (1,)
    vectors) into a proper per-shard IndexCore."""
    return replace(
        stacked, n_valid=stacked.n_valid[0], medoid=stacked.medoid[0],
        mut=replace(stacked.mut, n_free=stacked.mut.n_free[0],
                    n_deleted=stacked.mut.n_deleted[0],
                    generation=stacked.mut.generation[0]))


def _restack(core: IndexCore) -> IndexCore:
    """Inverse of `_local_core` for shard_map outputs."""
    return replace(
        core, n_valid=core.n_valid[None], medoid=core.medoid[None],
        mut=replace(core.mut, n_free=core.mut.n_free[None],
                    n_deleted=core.mut.n_deleted[None],
                    generation=core.mut.generation[None]))


def _shard_index(row_axes, axis_sizes) -> Array:
    """Linear shard index of this device along the row axes.

    axis_sizes: static {axis: size} (mesh.shape) — axis extents are mesh
    constants, so no in-graph axis_size query is needed."""
    idx = jnp.int32(0)
    mult = 1
    for ax in reversed(row_axes):
        idx = idx + jax.lax.axis_index(ax) * mult
        mult *= axis_sizes[ax]
    return idx


def merge_topk(gids: Array, dists: Array, row_axes, k: int
               ) -> tuple[Array, Array]:
    """Hierarchical shard merge: all_gather along each row axis in turn
    keeps per-hop payload at S_axis*Q_loc*k instead of S_total*Q_loc*k."""
    n_q = gids.shape[0]
    for ax in row_axes:
        gd = jax.lax.all_gather(dists, ax, axis=0)       # (s, Q, k)
        gi = jax.lax.all_gather(gids, ax, axis=0)
        gd = jnp.moveaxis(gd, 0, 1).reshape(n_q, -1)
        gi = jnp.moveaxis(gi, 0, 1).reshape(n_q, -1)
        neg, pos = jax.lax.top_k(-gd, k)
        dists = -neg
        gids = jnp.take_along_axis(gi, pos, axis=1)
    return gids, dists


# ---------------------------------------------------------------------------
# shard_map-wrapped core ops
# ---------------------------------------------------------------------------

def sharded_search_fn(mesh: Mesh, shard_spec: ShardSpec,
                      template: IndexCore, *, id_stride: int, spec,
                      filter_tombstones: bool = True, trace_counter=None):
    """Build the jit'd sharded search step: shard-local `core_search`
    (IDENTICAL to the single-device hot path — fused Pallas scorer over
    packed codes, per-shard tombstone bitmap, shard-local exact rerank)
    followed by the all_gather merge. fn(core_stacked, queries) ->
    (GLOBAL ids (Q, k), dists (Q, k), n_hops (Q,)), sharded over the
    query axis.

    spec: a `ResolvedSearchSpec` — the ONE static search configuration
    object, shared verbatim with the single-device plan builder (defaults
    and validation live in `SearchSpec.resolve`, never here).
    n_hops is the max over shards: the slowest shard's walk is the hop
    cost the query actually paid. With spec.telemetry == "on" a fourth
    `SearchTelemetry` output is the SUM over shards (total work the query
    caused across the fleet — each shard walks its own graph, so counts
    add; occupancy sums per hop the same way), and it equals the sum of
    the shards' own single-device counters exactly (conformance lane).
    trace_counter: optional zero-arg hook bumped at trace time (the plan
    cache's retrace counter).

    With spec.filtered the step takes a third operand — the uint8[NB]
    filter byte mask, REPLICATED (P()) so every shard evaluates the same
    label predicate in its own kernel epilogue. Filter-off plans keep
    their exact two-operand signature (bit-identical plan, same cache
    entry as pre-filter builds).
    """
    row_axes = shard_spec.row_axes
    tel_on = spec.telemetry == "on"
    filtered = spec.filtered

    def local_search(core_stacked, queries, *maybe_fb):
        if trace_counter is not None:
            trace_counter()
        core = _local_core(core_stacked)
        out = core_search(
            core, queries, spec=spec, filter_tombstones=filter_tombstones,
            filter_bytes=maybe_fb[0] if filtered else None)
        ids, dists, n_hops = out[:3]
        row0 = _shard_index(row_axes, dict(mesh.shape)) * id_stride
        gids = jnp.where(ids >= 0, ids + row0, -1)
        gids, dists = merge_topk(gids, dists, row_axes, spec.k)
        for ax in row_axes:
            n_hops = jax.lax.pmax(n_hops, ax)
        if tel_on:
            tel = out[3]
            tel = type(tel)(*(jax.lax.psum(t, row_axes) for t in tel))
            return gids, dists, n_hops, tel
        return gids, dists, n_hops

    q_spec = P(shard_spec.query_axis, None)
    h_spec = P(shard_spec.query_axis)
    out_specs = (q_spec, q_spec, h_spec)
    if tel_on:
        # SearchTelemetry: three (Q,) counters + one (Q, max_iters) log
        out_specs = out_specs + (
            SearchTelemetry(h_spec, h_spec, h_spec, q_spec),)
    in_specs = (core_partition_specs(template, shard_spec), q_spec)
    in_shardings = (core_shardings(mesh, template, shard_spec),
                    NamedSharding(mesh, q_spec))
    if filtered:
        in_specs = in_specs + (P(),)
        in_shardings = in_shardings + (NamedSharding(mesh, P()),)
    fn = jax.shard_map(
        local_search, mesh=mesh,
        in_specs=in_specs, out_specs=out_specs, check_vma=False)
    return jax.jit(fn, in_shardings=in_shardings)


def sharded_traversal_fn(mesh: Mesh, shard_spec: ShardSpec,
                         template: IndexCore, *, spec,
                         filter_tombstones: bool = True,
                         trace_counter=None):
    """Host-tier stage 1: the shard-local `core_search` traversal ONLY
    (with `spec.rerank_source == "host"` it returns the full-width
    estimator frontier — no rows operand, no in-graph rerank, no merge).
    Outputs are stacked per shard via a leading row-axes dimension:
    fn(core_stacked, queries[, fb]) -> (local frontier ids (S, Q, L),
    estimator dists (S, Q, L), n_hops (S, Q)[, SearchTelemetry stacked
    the same way]). S is ordered exactly like `_shard_index` (row-major
    over row_axes) — the order the host gather and the sharded host
    rerank plan (core/storage.py) assume."""
    row_axes = shard_spec.row_axes
    tel_on = spec.telemetry == "on"
    filtered = spec.filtered

    def local_traverse(core_stacked, queries, *maybe_fb):
        if trace_counter is not None:
            trace_counter()
        core = _local_core(core_stacked)
        out = core_search(
            core, queries, spec=spec, filter_tombstones=filter_tombstones,
            filter_bytes=maybe_fb[0] if filtered else None)
        ids, dists, n_hops = out[:3]
        res = (ids[None], dists[None], n_hops[None])
        if tel_on:
            tel = out[3]
            res = res + (type(tel)(*(t[None] for t in tel)),)
        return res

    q_axis = shard_spec.query_axis
    s3 = P(row_axes, q_axis, None)
    s2 = P(row_axes, q_axis)
    out_specs = (s3, s3, s2)
    if tel_on:
        out_specs = out_specs + (SearchTelemetry(s2, s2, s2, s3),)
    in_specs = (core_partition_specs(template, shard_spec),
                P(q_axis, None))
    in_shardings = (core_shardings(mesh, template, shard_spec),
                    NamedSharding(mesh, P(q_axis, None)))
    if filtered:
        in_specs = in_specs + (P(),)
        in_shardings = in_shardings + (NamedSharding(mesh, P()),)
    fn = jax.shard_map(
        local_traverse, mesh=mesh,
        in_specs=in_specs, out_specs=out_specs, check_vma=False)
    return jax.jit(fn, in_shardings=in_shardings)


def sharded_insert_fn(mesh: Mesh, spec: ShardSpec, template: IndexCore, *,
                      params: ConstructionParams):
    """Build the jit'd sharded insert step: every shard links its own batch
    via `core_insert_at` (rows + LOCAL slot ids already dealt by the host)
    — pure data parallelism, zero collectives."""

    def local_insert(core_stacked, ids, rows):
        core = core_insert_at(_local_core(core_stacked), ids[0], rows[0],
                              params=params)
        return _restack(core)

    specs = core_partition_specs(template, spec)
    fn = jax.shard_map(
        local_insert, mesh=mesh,
        in_specs=(specs, P(spec.row_axes, None), P(spec.row_axes, None, None)),
        out_specs=specs, check_vma=False)
    return jax.jit(fn)


def sharded_bootstrap_fn(mesh: Mesh, spec: ShardSpec, template: IndexCore, *,
                         n0: int, params: ConstructionParams):
    def local_boot(core_stacked, rows):
        core = core_bootstrap(_local_core(core_stacked), rows[0],
                              n0=n0, params=params)
        return _restack(core)

    specs = core_partition_specs(template, spec)
    fn = jax.shard_map(
        local_boot, mesh=mesh,
        in_specs=(specs, P(spec.row_axes, None, None)),
        out_specs=specs, check_vma=False)
    return jax.jit(fn)


def sharded_delete_fn(mesh: Mesh, spec: ShardSpec, template: IndexCore):
    """Build the jit'd sharded delete: each shard tombstones its own batch
    of LOCAL ids (-1 padded) in its own bitmap — no coordination."""

    def local_delete(core_stacked, ids):
        core, n_new = core_delete(_local_core(core_stacked), ids[0])
        return _restack(core), n_new[None]

    specs = core_partition_specs(template, spec)
    fn = jax.shard_map(
        local_delete, mesh=mesh,
        in_specs=(specs, P(spec.row_axes, None)),
        out_specs=(specs, P(spec.row_axes)), check_vma=False)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Host driver — same role as JasperIndex, one core per shard
# ---------------------------------------------------------------------------

class ShardedJasperIndex(SearchSurface):
    """Row-sharded Jasper index: the IndexCore driver on a device mesh."""

    def __init__(self, mesh: Mesh, dims: int, capacity_per_shard: int, *,
                 spec: ShardSpec | None = None, metric: str = "l2",
                 construction: ConstructionParams | None = None,
                 quantization: str | None = None, bits: int = 4,
                 seed: int = 0, id_stride: int | None = None,
                 plan_cache_capacity: int | None = None,
                 rows_tier: str = "device"):
        """id_stride: global ids are shard*id_stride + local, fixed for the
        index lifetime (default 4x capacity_per_shard) — capacity can grow
        up to the stride without invalidating outstanding ids."""
        if metric not in ("l2", "mips"):
            raise ValueError(f"metric must be l2|mips, got {metric!r}")
        if quantization not in (None, "rabitq"):
            raise ValueError(
                "sharded quantization must be None or 'rabitq' "
                "(PQ is a deprecated single-device comparison baseline)")
        if capacity_per_shard % 8:
            raise ValueError(
                "capacity_per_shard must be a multiple of 8 so per-shard "
                f"tombstone bitmaps stack cleanly, got {capacity_per_shard}")
        self.id_stride = id_stride or 4 * capacity_per_shard
        if self.id_stride < capacity_per_shard:
            raise ValueError(
                f"id_stride {self.id_stride} < capacity_per_shard "
                f"{capacity_per_shard}")
        self.mesh = mesh
        self.spec = spec or ShardSpec(
            row_axes=tuple(a for a in mesh.axis_names if a != "model")
            or (mesh.axis_names[0],),
        )
        if (self.spec.query_axis is not None
                and self.spec.query_axis not in mesh.axis_names):
            # fall back to replicated queries on meshes without a model axis
            self.spec = ShardSpec(self.spec.row_axes, None)
        self.dims = dims
        self.metric = metric
        # MIPS reduces to L2 with one augmented dimension (paper §6.3);
        # the augmentation max-norm is GLOBAL (one host fold over each
        # batch before rows deal to shards), so every shard augments
        # against the same bound and the reduction stays exact
        self.store_dims = dims + 1 if metric == "mips" else dims
        self._mips_max_sqnorm: float | None = None
        self.cap = capacity_per_shard
        self.params = construction or ConstructionParams()
        self.quantization = quantization
        self.bits = bits
        self.seed = seed
        self.n_shards = 1
        for ax in self.spec.row_axes:
            self.n_shards *= mesh.shape[ax]

        self.core = self._device_put(self._empty_stacked_core())
        # compiled-executable cache (search plans + insert/boot/delete
        # steps) with hit/miss/trace counters — the same PlanCache the
        # single-device driver owns; Searcher sessions share it.
        # plan_cache_capacity bounds it LRU-style (None = unbounded)
        self.plans = PlanCache(capacity=plan_cache_capacity)
        # old->new IdTranslation of the last shard-count-changing load
        # (None after a same-count restore or a fresh construction)
        self.reshard_translation = None
        # tiered storage (core/storage.py): host rows are the stacked
        # (S*cap, D) array, so per-shard rows are contiguous slices and
        # the frontier gather addresses shard*cap + local directly
        self.store = VectorStore()
        if rows_tier == "host":
            self.evict_rows_to_host()
        elif rows_tier != "device":
            raise ValueError(
                f"rows_tier must be device|host, got {rows_tier!r}")

    # ------------------------------------------------------------ tiered rows
    @property
    def rows_tier(self) -> str:
        """Where the f32 rows live ("device" | "host") — see
        JasperIndex.rows_tier; the sharded form stacks host rows
        (S*cap, D) so each shard's rows are one contiguous slice."""
        return self.store.tier

    def evict_rows_to_host(self) -> "ShardedJasperIndex":
        """device -> host across every shard: packed codes (+ graph and
        metadata) stay device-resident per shard; the f32 rows move to
        one stacked host array. See JasperIndex.evict_rows_to_host."""
        if self.quantization != "rabitq":
            raise ValueError(
                "evict_rows_to_host requires quantization='rabitq': "
                "without device-resident packed codes there is nothing "
                "left to traverse on (an exact-only core cannot serve "
                "any search with its rows evicted)")
        self.core = self.store.evict(self.core)
        self.plans.clear()
        return self

    def restore_rows_to_device(self) -> "ShardedJasperIndex":
        """host -> device: re-attach the rows, sharded over the row axes
        again (classic fully-device-resident layout)."""
        self.core = self._device_put(self.store.restore(self.core))
        self.plans.clear()
        return self

    # --------------------------------------------------------------- stacking
    def _empty_stacked_core(self) -> IndexCore:
        s, cap = self.n_shards, self.cap
        core = init_core(s * cap, self.store_dims, self.params.degree_bound)
        return replace(
            core,
            n_valid=jnp.zeros((s,), jnp.int32),
            medoid=jnp.zeros((s,), jnp.int32),
            mut=replace(core.mut,
                        n_free=jnp.zeros((s,), jnp.int32),
                        n_deleted=jnp.zeros((s,), jnp.int32),
                        generation=jnp.zeros((s,), jnp.int32)))

    def _device_put(self, core: IndexCore) -> IndexCore:
        return jax.device_put(core,
                              core_shardings(self.mesh, core, self.spec))

    def shard_core(self, s: int) -> IndexCore:
        """Host-side view of shard s as a plain (local-id) IndexCore —
        the unit of consolidation and of checkpoint I/O."""
        cap = self.cap
        rows = slice(s * cap, (s + 1) * cap)
        bits = slice(s * (cap // 8), (s + 1) * (cap // 8))
        c = self.core
        codes = None
        if c.codes is not None:
            codes = RaBitQCodes(packed=c.codes.packed[rows],
                                data_add=c.codes.data_add[rows],
                                data_rescale=c.codes.data_rescale[rows],
                                bits=c.codes.bits, dims=c.codes.dims)
        return IndexCore(
            vectors=c.vectors[rows], vec_sqnorm=c.vec_sqnorm[rows],
            adjacency=c.adjacency[rows], n_valid=c.n_valid[s],
            medoid=c.medoid[s],
            mut=MutationState(tombstone_bits=c.mut.tombstone_bits[bits],
                              labels=c.mut.labels[rows],
                              free_ids=c.mut.free_ids[rows],
                              n_free=c.mut.n_free[s],
                              n_deleted=c.mut.n_deleted[s],
                              generation=c.mut.generation[s]),
            codes=codes, rq_params=c.rq_params)

    def _stack_cores(self, locals_: list[IndexCore]) -> IndexCore:
        """Assemble S per-shard (local-id) cores into the stacked device
        core — ONE concatenation + device_put per buffer, so restoring or
        repairing all shards moves the index once, not once per shard."""
        def cat(get):
            return jnp.concatenate([get(c) for c in locals_], axis=0)

        def vec(get):
            return jnp.stack([jnp.asarray(get(c), jnp.int32)
                              for c in locals_])

        codes = None
        if locals_[0].codes is not None:
            c0 = locals_[0].codes
            codes = RaBitQCodes(
                packed=cat(lambda c: c.codes.packed),
                data_add=cat(lambda c: c.codes.data_add),
                data_rescale=cat(lambda c: c.codes.data_rescale),
                bits=c0.bits, dims=c0.dims)
        core = IndexCore(
            vectors=cat(lambda c: c.vectors),
            vec_sqnorm=cat(lambda c: c.vec_sqnorm),
            adjacency=cat(lambda c: c.adjacency),
            n_valid=vec(lambda c: c.n_valid),
            medoid=vec(lambda c: c.medoid),
            mut=MutationState(
                tombstone_bits=cat(lambda c: c.mut.tombstone_bits),
                labels=cat(lambda c: c.mut.labels),
                free_ids=cat(lambda c: c.mut.free_ids),
                n_free=vec(lambda c: c.mut.n_free),
                n_deleted=vec(lambda c: c.mut.n_deleted),
                generation=vec(lambda c: c.mut.generation)),
            codes=codes, rq_params=locals_[0].rq_params)
        return self._device_put(core)

    # ------------------------------------------------------------------ util
    @property
    def size(self) -> int:
        return int(np.sum(np.asarray(self.core.n_valid))
                   - np.sum(np.asarray(self.core.mut.n_deleted))
                   - np.sum(np.asarray(self.core.mut.n_free)))

    @property
    def capacity(self) -> int:
        """Total row capacity across shards."""
        return self.n_shards * self.cap

    @property
    def generation(self) -> int:
        """Sum of per-shard generation counters (monotonic under every
        mutation on any shard) — serving layers stamp results with it."""
        return int(np.sum(np.asarray(self.core.mut.generation)))

    @property
    def n_deleted(self) -> int:
        return int(np.sum(np.asarray(self.core.mut.n_deleted)))

    @property
    def deleted_fraction(self) -> float:
        n = (int(np.sum(np.asarray(self.core.n_valid)))
             - int(np.sum(np.asarray(self.core.mut.n_free))))
        return self.n_deleted / n if n else 0.0

    @property
    def _filter_tombstones(self) -> bool:
        return (self.n_deleted != 0
                or int(np.sum(np.asarray(self.core.mut.n_free))) != 0)

    def shard_live_counts(self) -> np.ndarray:
        """int64[S] live rows per shard (skewed deletes drift these apart;
        `rebalance` levels them)."""
        return (np.asarray(self.core.n_valid, np.int64)
                - np.asarray(self.core.mut.n_deleted, np.int64)
                - np.asarray(self.core.mut.n_free, np.int64))

    @property
    def shard_imbalance(self) -> float:
        """(max - min) / mean of per-shard live counts — the load-skew
        metric serving layers trigger `rebalance` on (0.0 = level)."""
        c = self.shard_live_counts()
        m = float(c.mean())
        return float(c.max() - c.min()) / m if m > 0 else 0.0

    def global_row(self, shard: int, local_id: int) -> int:
        return shard * self.id_stride + local_id

    def tombstoned(self, ids) -> np.ndarray:
        """Host-side deadness test for GLOBAL ids (the serving-contract
        check). The bit position in the stacked capacity-major bitmap is
        shard*cap + local; the bit test itself is the shared
        `bitmap_test_np` (one encoding, one definition). Ids whose local
        part falls outside the per-shard capacity are dead by definition."""
        ids = np.asarray(ids)
        shard, local = ids // self.id_stride, ids % self.id_stride
        in_cap = local < self.cap
        bit_pos = shard * self.cap + np.minimum(local, self.cap - 1)
        dead = bitmap_test_np(np.asarray(self.core.mut.tombstone_bits),
                              bit_pos)
        n_valid = np.asarray(self.core.n_valid)
        return dead | ~in_cap | (local >= n_valid[shard])

    def _template(self) -> IndexCore:
        return self.core

    # ----------------------------------------------------------------- mips
    def _prep_data(self, x) -> Array:
        """Metric prep BEFORE rows deal to shards: for MIPS, augment with
        the GLOBAL max-norm (host fold — the 'one all-reduce' of the
        roadmap item, folded on the host where batches already live). A
        later batch that raises the max re-augments every written row on
        every shard, so the MIPS->L2 reduction stays exact under
        streaming."""
        x = jnp.asarray(x, jnp.float32)
        if self.metric != "mips":
            return x
        sq = jnp.sum(x * x, axis=-1)
        m2 = float(jnp.max(sq))                 # global: whole host batch
        if self._mips_max_sqnorm is None:
            self._mips_max_sqnorm = m2
        elif m2 > self._mips_max_sqnorm:
            old = self._mips_max_sqnorm
            self._mips_max_sqnorm = m2
            self._reaugment_mips(old, m2)
        extra = jnp.sqrt(jnp.maximum(self._mips_max_sqnorm - sq, 0.0))
        return jnp.concatenate([x, extra[..., None]], axis=-1)

    def _reaugment_mips(self, old_m2: float, new_m2: float) -> None:
        """Closed-form re-augmentation of every written row on every shard
        (same identity as the single-device driver: e' = sqrt(e^2 + delta))
        + re-encode of the packed codes — the quantizer rotation/centroid
        is dataset-level and untouched, so codes re-derive in place."""
        from repro.core.rabitq import rabitq_encode
        c = self.core
        delta = new_m2 - old_m2
        rows = self.n_shards * self.cap
        written = (jnp.arange(rows) % self.cap
                   < jnp.repeat(c.n_valid, self.cap))
        last = c.vectors[:, -1]
        vectors = c.vectors.at[:, -1].set(
            jnp.where(written, jnp.sqrt(last * last + delta), last))
        sqnorm = jnp.where(written, c.vec_sqnorm + delta, c.vec_sqnorm)
        codes = c.codes
        if codes is not None:
            enc = rabitq_encode(c.rq_params, vectors)
            codes = RaBitQCodes(
                packed=jnp.where(written[:, None], enc.packed, codes.packed),
                data_add=jnp.where(written, enc.data_add, codes.data_add),
                data_rescale=jnp.where(written, enc.data_rescale,
                                       codes.data_rescale),
                bits=codes.bits, dims=codes.dims)
        self.core = self._device_put(replace(
            c, vectors=vectors, vec_sqnorm=sqnorm, codes=codes))

    def _prep_query(self, q) -> Array:
        q = jnp.asarray(q, jnp.float32)
        if self.metric == "mips":
            from repro.core.distances import mips_augment_query
            q = mips_augment_query(q)
        return q

    # ------------------------------------------------------------ build/insert
    def _ensure_quantizer(self, rows: Array) -> None:
        if self.quantization == "rabitq" and self.core.rq_params is None:
            params = rabitq_train(jax.random.PRNGKey(self.seed), rows,
                                  bits=self.bits)
            self.core = self._device_put(attach_quantizer(self.core, params))
            self.plans.clear()          # core structure changed

    def build(self, data, *, labels=None) -> "ShardedJasperIndex":
        """Bulk build. data: (N, D) with N divisible by n_shards — rows are
        dealt contiguously to shards (shard s owns data[s*per:(s+1)*per]).
        labels: optional per-row label sets (see `set_labels`), in the
        same dealt order as data."""
        with obs_span("index.build", n=int(np.asarray(data).shape[0]),
                      sharded=True), rows_staged(self):
            self._build_impl(data)
            if labels is not None:
                n = int(np.asarray(data).shape[0])
                per = n // self.n_shards
                gids = (np.arange(self.n_shards)[:, None] * self.id_stride
                        + np.arange(per)[None, :]).astype(np.int64)
                self.set_labels(gids.reshape(-1), labels)
            return self

    def _build_impl(self, data) -> "ShardedJasperIndex":
        data = self._prep_data(data)
        n = data.shape[0]
        if n % self.n_shards:
            raise ValueError(f"N={n} not divisible by n_shards={self.n_shards}")
        per = n // self.n_shards
        if per > self.cap:
            raise ValueError(f"{per} rows/shard exceed capacity {self.cap}")
        self._ensure_quantizer(data)
        # reset graph + mutation state (generation keeps advancing), keep
        # the trained quantizer — mirrors JasperIndex.build
        fresh = self._empty_stacked_core()
        self.core = self._device_put(replace(
            self.core, adjacency=fresh.adjacency, n_valid=fresh.n_valid,
            medoid=fresh.medoid,
            mut=replace(fresh.mut,
                        generation=self.core.mut.generation + 1)))
        dealt = data.reshape(self.n_shards, per, -1)

        n0 = min(1024, per)
        boot = self._fn("boot", n0=n0)
        self.core = boot(self.core, dealt[:, :n0])

        # prefix-doubling schedule up to MAX_BATCH rows (device memory),
        # every rung inserted into EVERY shard
        inserted = n0
        while inserted < per:
            remaining = per - inserted
            b = min(max(256, 1 << (inserted.bit_length() - 1)), MAX_BATCH,
                    remaining)
            if b != remaining:
                b = 1 << (b.bit_length() - 1)
            ids = jnp.tile(jnp.arange(inserted, inserted + b,
                                      dtype=jnp.int32)[None], (self.n_shards, 1))
            self.core = self._fn("insert", b=b)(
                self.core, ids, dealt[:, inserted:inserted + b])
            inserted += b
        jax.block_until_ready(self.core.adjacency)
        return self

    def insert(self, data, *, labels=None) -> np.ndarray:
        """Streaming insert of (S, b, D) — b rows per shard — or (N, D)
        with N divisible by n_shards (dealt contiguously).

        Slot ids are derived PER SHARD from each shard's own free pool and
        high-water mark, so uneven shards (after deletes on some shards
        only) allocate correctly. Returns the GLOBAL row ids, shaped like
        the input batch ((S, b) or (N,)).

        labels: optional label sets for the batch (one label id, one
        sequence per row, or one shared set — see `set_labels`), in the
        flat dealt order.
        """
        data = jnp.asarray(data, jnp.float32)
        flat_in = data.ndim == 2
        if flat_in:
            n = data.shape[0]
            if n % self.n_shards:
                raise ValueError(
                    f"insert size {n} must be divisible by n_shards "
                    f"{self.n_shards}")
            data = data.reshape(self.n_shards, n // self.n_shards, -1)
        elif data.shape[0] != self.n_shards:
            raise ValueError(
                f"(S, b, D) insert must have S == n_shards "
                f"{self.n_shards}, got {data.shape[0]}")
        if self.size == 0:
            # empty index: a clean per-shard build beats stitching onto a
            # dead graph (mirrors the single-device driver)
            s, b = data.shape[0], data.shape[1]
            self.build(data.reshape(s * b, -1), labels=labels)
            ids = (np.arange(s)[:, None] * self.id_stride
                   + np.arange(b)[None, :]).astype(np.int32)
            return ids.reshape(-1) if flat_in else ids
        with rows_staged(self):
            data = self._prep_data(data)  # (S, b, D[+1]): global-max augment
            local_ids, global_ids = self._allocate_slots_per_shard(
                data.shape[1])
            self.core = self._fn("insert", b=data.shape[1])(
                self.core, jnp.asarray(local_ids), data)
            if labels is not None:
                self.set_labels(global_ids.reshape(-1), labels)
            jax.block_until_ready(self.core.adjacency)
        return global_ids.reshape(-1) if flat_in else global_ids

    def set_labels(self, ids, labels) -> None:
        """Assign label bitsets to GLOBAL ids: one label id, one sequence
        of label ids per row, or one shared set for the whole batch
        (`core.mutations.pack_label_rows` semantics). Rows keep their
        labels through consolidate/grow/rebalance/reshard."""
        ids = np.atleast_1d(np.asarray(ids)).astype(np.int64).ravel()
        rows = pack_label_rows(labels, ids.size)
        pos = (ids // self.id_stride) * self.cap + ids % self.id_stride
        lab = self.core.mut.labels.at[jnp.asarray(pos, jnp.int32)].set(
            jnp.asarray(rows))
        self.core = self._device_put(replace(
            self.core, mut=replace(self.core.mut, labels=lab)))

    def _allocate_slots_per_shard(self, b: int
                                  ) -> tuple[np.ndarray, np.ndarray]:
        """Per-shard slot allocation: each shard pops its OWN free pool
        (ascending), then advances its OWN tail. Returns (local (S, b),
        global (S, b)) id arrays. Grows every shard when any tail overflows
        (uniform capacity keeps the stacked layout)."""
        s, cap = self.n_shards, self.cap
        n_free = np.asarray(self.core.mut.n_free).copy()
        n_valid = np.asarray(self.core.n_valid)
        take = np.minimum(b, n_free)
        need = n_valid + (b - take)
        if need.max() > cap:
            new_cap = cap
            while need.max() > new_cap:
                new_cap *= 2
            self.grow(new_cap)
            cap = self.cap
        free_ids = np.asarray(self.core.mut.free_ids).reshape(s, cap).copy()
        bits = np.asarray(self.core.mut.tombstone_bits).copy()
        labels = np.asarray(self.core.mut.labels).copy()
        local = np.empty((s, b), np.int32)
        for i in range(s):
            t = int(take[i])
            reused = free_ids[i, :t].copy()
            local[i, :t] = reused
            local[i, t:] = n_valid[i] + np.arange(b - t, dtype=np.int32)
            # pop: shift the pool, clear the popped slots' tombstone bits
            # and their stale label rows (slots recycle label-clean)
            free_ids[i] = np.concatenate(
                [free_ids[i, t:], np.full((t,), -1, np.int32)])
            g = reused.astype(np.int64) + i * cap
            clear = (~(np.int64(1) << (g & 7)) & 0xFF).astype(np.uint8)
            np.bitwise_and.at(bits, g >> 3, clear)
            labels[g] = 0
        mut = replace(self.core.mut,
                      tombstone_bits=jnp.asarray(bits),
                      labels=jnp.asarray(labels),
                      free_ids=jnp.asarray(free_ids.reshape(-1)),
                      n_free=jnp.asarray((n_free - take).astype(np.int32)))
        self.core = self._device_put(replace(self.core, mut=mut))
        global_ids = local + (np.arange(s, dtype=np.int32)
                              * self.id_stride)[:, None]
        return local, global_ids

    # ---------------------------------------------------------- delete/repair
    def delete(self, ids) -> int:
        """Batched tombstone delete of GLOBAL ids. Each shard tombstones
        its own rows in its own bitmap — shard-local, no coordination.
        Raises on ids that are not currently live. Returns rows deleted."""
        ids_np = np.atleast_1d(np.asarray(ids)).astype(np.int64).ravel()
        if ids_np.size == 0:
            return 0
        bad = ids_np[(ids_np < 0)
                     | (ids_np >= self.n_shards * self.id_stride)]
        if bad.size:
            raise ValueError(f"ids out of range: {bad[:8].tolist()}")
        dead = ids_np[self.tombstoned(ids_np)]
        if dead.size:
            raise ValueError(
                f"ids already deleted, freed, or unwritten: "
                f"{dead[:8].tolist()}")
        shard = ids_np // self.id_stride
        local = ids_np % self.id_stride
        counts = np.bincount(shard, minlength=self.n_shards)
        # pad every shard's batch to one power-of-two rung (-1 = ignored)
        # so uneven delete batches reuse one executable per rung
        rung = pow2_rung(int(counts.max()))
        padded = np.full((self.n_shards, rung), -1, np.int32)
        for i in range(self.n_shards):
            mine = local[shard == i]
            padded[i, :mine.size] = mine
        self.core, n_new = self._fn("delete", rung=rung)(
            self.core, jnp.asarray(padded))
        return int(np.sum(np.asarray(n_new)))

    def consolidate(self, *, refine: bool = True) -> dict:
        """Per-shard graph repair (host-driven, like build): each shard
        with tombstones runs the SAME `core_consolidate` the single-device
        driver uses — repair never crosses shards."""
        n_del = np.asarray(self.core.mut.n_deleted)
        if not n_del.any():
            return {"n_freed": 0, "n_repaired": 0}
        total = {"n_freed": 0, "n_repaired": 0}
        with rows_staged(self):
            locals_ = []
            for s in range(self.n_shards):
                local = self.shard_core(s)
                if int(n_del[s]):
                    local, stats = core_consolidate(
                        local, params=self.params, refine=refine)
                    total["n_freed"] += stats["n_freed"]
                    total["n_repaired"] += stats["n_repaired"]
                locals_.append(local)
            self.core = self._stack_cores(locals_)
        return total

    def grow(self, new_capacity_per_shard: int | None = None
             ) -> "ShardedJasperIndex":
        """Grow every shard's capacity by copy-extension. Per-shard buffers
        (packed codes included) are bit-identical after the grow, and
        GLOBAL ids are untouched (the shard*id_stride + local encoding is
        capacity-independent) — growing past the fixed id_stride raises."""
        new_cap = new_capacity_per_shard or 2 * self.cap
        if new_cap < self.cap:
            raise ValueError(f"cannot shrink {self.cap} -> {new_cap}")
        if new_cap % 8:
            raise ValueError("capacity_per_shard must be a multiple of 8")
        if new_cap > self.id_stride:
            raise ValueError(
                f"capacity_per_shard {new_cap} would exceed id_stride "
                f"{self.id_stride}: outstanding global ids would collide "
                "across shards. Construct the index with a larger "
                "id_stride for more growth headroom.")
        if new_cap == self.cap:
            return self
        with rows_staged(self):
            self._grow_impl(new_cap)
        return self

    def _grow_impl(self, new_cap: int) -> None:
        s, cap = self.n_shards, self.cap

        def per_shard_pad(arr, fill):
            shaped = arr.reshape((s, -1) + arr.shape[1:])
            # exact for both row arrays (cap -> new_cap) and the bitmap
            # (cap/8 -> new_cap/8): both caps are multiples of 8
            new_len = shaped.shape[1] * new_cap // cap
            widths = ([(0, 0), (0, new_len - shaped.shape[1])]
                      + [(0, 0)] * (arr.ndim - 1))
            return jnp.pad(shaped, widths, constant_values=fill
                           ).reshape((-1,) + arr.shape[1:])

        c = self.core
        codes = c.codes
        if codes is not None:
            codes = RaBitQCodes(packed=per_shard_pad(codes.packed, 0),
                                data_add=per_shard_pad(codes.data_add, 0.0),
                                data_rescale=per_shard_pad(
                                    codes.data_rescale, 0.0),
                                bits=codes.bits, dims=codes.dims)
        self.core = self._device_put(replace(
            c,
            vectors=per_shard_pad(c.vectors, 0.0),
            vec_sqnorm=per_shard_pad(c.vec_sqnorm, 0.0),
            adjacency=per_shard_pad(c.adjacency, -1),
            mut=replace(c.mut,
                        tombstone_bits=per_shard_pad(c.mut.tombstone_bits, 0),
                        labels=per_shard_pad(c.mut.labels, 0),
                        free_ids=per_shard_pad(c.mut.free_ids, -1),
                        generation=c.mut.generation + 1),
            codes=codes))
        self.cap = new_cap
        self.plans.clear()              # row0 offsets / shapes changed

    def rebalance(self, *, tolerance: float = 0.05) -> dict:
        """Level per-shard live counts: round-robin live rows off overfull
        shards onto underfull ones (skewed deletes drift shards uneven;
        this is the online remedy — `consolidate` repairs graphs in
        place, `rebalance` moves load).

        Host-driven like consolidate: rows move via the SAME core ops the
        drivers already use — `core_insert_at` on the receiver (whose
        fused encode re-derives the packed code bit-identically, because
        the quantizer rotation/centroid is replicated dataset-level
        state) and `core_delete` + per-shard `core_consolidate` on the
        donor. Moved rows get new global ids; the returned
        ``translation`` (IdTranslation, identity off-table) remaps
        outstanding tickets. No-op inside `tolerance` imbalance.
        """
        from repro.core.index_core import (core_live_locals,
                                           core_take_free_slots)
        from repro.core.resharding import IdTranslation, rebalance_plan

        # liveness is consolidate-invariant, so the plan (and the no-op
        # early return: nothing mutated, nothing stamped) comes first
        with rows_staged(self):
            return self._rebalance_impl(tolerance)

    def _rebalance_impl(self, tolerance: float) -> dict:
        from repro.core.index_core import (core_live_locals,
                                           core_take_free_slots)
        from repro.core.resharding import IdTranslation, rebalance_plan

        live = [core_live_locals(self.shard_core(s))
                for s in range(self.n_shards)]
        plan = rebalance_plan(live, tolerance=tolerance)
        base = {"counts_before": plan.counts_before.tolist(),
                "counts_after": plan.counts_after.tolist(),
                "imbalance": self.shard_imbalance}
        if plan.n_moved == 0:
            return base | {"n_moved": 0, "translation": None}
        if self.n_deleted:
            # tombstoned slots cannot receive rows — free them first (a
            # rebalance implies consolidation, never the other way round)
            self.consolidate()

        vecs = np.asarray(self.core.vectors).reshape(
            self.n_shards, self.cap, -1)
        labs = np.asarray(self.core.mut.labels).reshape(
            self.n_shards, self.cap, -1)
        locals_ = [self.shard_core(s) for s in range(self.n_shards)]
        old_gids, new_gids = [], []
        # 1. receivers first (rows must exist somewhere at every point)
        for dst, pairs in plan.moves.items():
            rows = np.stack([vecs[s, l] for s, l in pairs])
            lab_rows = np.stack([labs[s, l] for s, l in pairs])
            core = locals_[dst]
            core, reused = core_take_free_slots(core, len(pairs))
            hw = int(core.n_valid)
            fresh = np.arange(hw, hw + len(pairs) - reused.size,
                              dtype=np.int32)
            ids = np.concatenate([reused, fresh]).astype(np.int32)
            pad = _pow2_pad_pairs(ids, rows)
            locals_[dst] = core_insert_at(
                core, jnp.asarray(pad[0]), jnp.asarray(pad[1]),
                params=self.params)
            # moved rows keep their label rows bit-identically
            locals_[dst] = core_set_labels(locals_[dst], jnp.asarray(ids),
                                           jnp.asarray(lab_rows))
            old_gids += [s * self.id_stride + l for s, l in pairs]
            new_gids += (dst * self.id_stride + ids.astype(np.int64)).tolist()
        # 2. tombstone the moved-out rows on their donors, then repair
        by_src: dict[int, list[int]] = {}
        for pairs in plan.moves.values():
            for s, l in pairs:
                by_src.setdefault(s, []).append(l)
        for src, locs in by_src.items():
            ids = np.asarray(sorted(locs), np.int32)
            padded = np.full((pow2_rung(ids.size),), -1, np.int32)
            padded[:ids.size] = ids
            locals_[src], _ = core_delete(locals_[src], jnp.asarray(padded))
            locals_[src], _ = core_consolidate(locals_[src],
                                               params=self.params)
        self.core = self._stack_cores(locals_)
        return base | {
            "n_moved": plan.n_moved,
            "translation": IdTranslation.build(old_gids, new_gids,
                                               default="identity")}

    # ------------------------------------------------------------------ search
    # searcher()/recall() come from SearchSurface — the one shared copy
    def _search_plan(self, rspec, q_shape, filt: bool):
        """Plan-cache lookup/build: `(queries, filter_bytes) -> (GLOBAL
        ids, dists, n_hops)` — the shard_map'd search step + all_gather
        merge. Filter VALUES ride as a replicated runtime operand; only
        `rspec.filtered` (presence) is part of the key, so tenant
        switches never split the plan cache."""
        key = ("search", self.cap, rspec, tuple(q_shape), filt)

        def build():
            if rspec.rerank_source == "host":
                return sharded_traversal_fn(
                    self.mesh, self.spec, self._template(), spec=rspec,
                    filter_tombstones=filt,
                    trace_counter=self.plans.count_trace)
            return sharded_search_fn(
                self.mesh, self.spec, self._template(),
                id_stride=self.id_stride, spec=rspec,
                filter_tombstones=filt,
                trace_counter=self.plans.count_trace)

        fn = self.plans.get(key, build)
        if rspec.rerank_source == "host":
            # Two-stage plan: device traversal over packed codes yields
            # per-shard estimator frontiers; the host store gathers only
            # the frontier rows; a separately-keyed jitted plan reranks
            # exactly and merges to global top-k. Telemetry (stacked per
            # shard by the traversal) sums eagerly — int32 adds, so it
            # matches the fused plan's in-graph psum bit-for-bit.
            rkey = ("rerank_host", self.cap, rspec, tuple(q_shape))
            rplan = self.plans.get(rkey, lambda: build_sharded_host_rerank_plan(
                rspec,
                axis_sizes=tuple(self.mesh.shape[ax]
                                 for ax in self.spec.row_axes),
                id_stride=self.id_stride,
                trace_counter=self.plans.count_trace))
            store, cap = self.store, self.cap

            def run_host(queries, fb=None):
                out = (fn(self.core, queries, jnp.asarray(fb, jnp.uint8))
                       if rspec.filtered else fn(self.core, queries))
                f_ids = out[0]
                ids_np = np.asarray(f_ids)
                shard = np.arange(ids_np.shape[0]).reshape(-1, 1, 1)
                positions = np.where(ids_np >= 0, shard * cap + ids_np, -1)
                rows, sq = store.gather(positions)
                merged = rplan(queries, f_ids, jnp.asarray(rows),
                               jnp.asarray(sq), out[2])
                if len(out) > 3:
                    tel = out[3]
                    merged = merged + (
                        type(tel)(*(jnp.sum(t, axis=0) for t in tel)),)
                return merged

            return run_host
        if rspec.filtered:
            return lambda queries, fb=None: fn(self.core, queries,
                                               jnp.asarray(fb, jnp.uint8))
        return lambda queries, fb=None: fn(self.core, queries)

    def search(self, queries, k: int = 10, *, beam_width: int | None = None,
               max_iters: int | None = None, expand: int = 1,
               quantized: bool = False, rerank: bool = True,
               use_kernels: bool = False, merge: str = "topk",
               traverse_deleted: bool = True) -> tuple[Array, Array]:
        """Global top-k over all shards — legacy kwargs shim over
        `searcher(SearchSpec(...))`. queries: (Q, D), Q divisible by the
        query-axis size (or any Q when queries are replicated). Returns
        (GLOBAL ids (Q, k), dists (Q, k))."""
        res = self.searcher(SearchSpec(
            k=k, beam_width=beam_width, max_iters=max_iters, expand=expand,
            quantized=quantized, rerank=rerank, use_kernels=use_kernels,
            merge=merge, traverse_deleted=traverse_deleted)).search(queries)
        return res.ids, res.dists

    def search_rabitq(self, queries, k: int = 10, **kw) -> tuple[Array, Array]:
        """Quantized search (serving-layer symmetry with JasperIndex)."""
        if self.core.codes is None:
            raise RuntimeError("index was not built with quantization='rabitq'")
        return self.search(queries, k, quantized=True, **kw)

    def brute_force(self, queries, k: int = 10) -> tuple[Array, Array]:
        """Exact top-k over all LIVE rows of all shards (recall ground
        truth) — host-side full scan over the stacked arrays."""
        from repro.core.distances import pairwise_l2_squared
        from repro.core.mutations import unpack_bitmap
        q = self._prep_query(queries)
        with rows_staged(self):
            out = self._brute_force_impl(q, k, pairwise_l2_squared,
                                         unpack_bitmap)
            jax.block_until_ready(out)   # computed before rows detach
        return out

    def _brute_force_impl(self, q, k, pairwise_l2_squared, unpack_bitmap):
        d = pairwise_l2_squared(q, self.core.vectors, self.core.vec_sqnorm)
        rows = self.n_shards * self.cap
        local = jnp.arange(rows) % self.cap
        nv = jnp.repeat(self.core.n_valid, self.cap)
        mask = ((local < nv)
                & ~unpack_bitmap(self.core.mut.tombstone_bits, rows))
        d = jnp.where(mask[None, :], d, jnp.inf)
        neg, pos = jax.lax.top_k(-d, k)
        # stacked array position -> layout-independent global id
        gids = (pos // self.cap) * self.id_stride + pos % self.cap
        return gids.astype(jnp.int32), -neg

    # ----------------------------------------------------------------- memory
    def memory_stats(self) -> dict[str, float]:
        """Per-tier resident bytes over the stacked (all-shard) arrays —
        same TIER_STAT_KEYS contract as the single-device driver."""
        return dict(tier_memory_stats(
            self.core, self.store, capacity=self.capacity,
            store_dims=self.store_dims))

    def storage_stats(self) -> dict:
        """Tier residence + host-fetch counters for the `storage.*`
        metrics namespace (obs/metrics.py `storage_stats_collector`)."""
        out = dict(self.memory_stats())
        out.update({f"fetch_{k}": v
                    for k, v in self.store.fetch_stats.as_dict().items()})
        return out

    # ----------------------------------------------------------- plan cache
    def _fn(self, kind: str, **key):
        """Mutation-step plans (insert/boot/delete) in the shared
        PlanCache; search plans go through `_search_plan`."""
        ck = (kind, self.cap, tuple(sorted(key.items())))

        def build():
            t = self._template()
            if kind == "insert":
                return sharded_insert_fn(self.mesh, self.spec, t,
                                         params=self.params)
            if kind == "boot":
                return sharded_bootstrap_fn(self.mesh, self.spec, t,
                                            n0=key["n0"], params=self.params)
            if kind == "delete":
                return sharded_delete_fn(self.mesh, self.spec, t)
            raise ValueError(kind)

        return self.plans.get(ck, build)

    # -------------------------------------------------------------- save/load
    def save(self, path: str) -> None:
        """Checkpoint: one single-device-format .npz PER SHARD
        (`{path}.shard{K}`, each individually readable by JasperIndex.load)
        plus a `{path}.meta.json` manifest. Tombstones + free pools
        round-trip exactly."""
        from dataclasses import asdict

        from repro.core.index import save_npz_atomic
        meta = {
            "n_shards": self.n_shards, "dims": self.dims,
            "metric": self.metric,
            "capacity_per_shard": self.cap, "id_stride": self.id_stride,
            "quantization": self.quantization, "bits": self.bits,
            "seed": self.seed,
            "construction": asdict(self.params),
            "row_axes": list(self.spec.row_axes),
            "query_axis": self.spec.query_axis,
            "mips_max_sqnorm": self._mips_max_sqnorm,
            "rows_tier": self.rows_tier,
        }
        shard_meta = {
            "dims": self.dims, "metric": self.metric, "capacity": self.cap,
            "quantization": self.quantization, "bits": self.bits,
            "seed": self.seed,
            "construction": asdict(self.params),
            "mips_max_sqnorm": self._mips_max_sqnorm,
            # each shard file is JasperIndex-loadable; carrying the tier
            # means a shard restored single-device re-evicts too
            "rows_tier": self.rows_tier,
        }
        with rows_staged(self):
            # host-tier rows stage back in: shard payloads keep the ONE
            # cross-driver format, the manifest records the tier layout
            for s in range(self.n_shards):
                save_npz_atomic(f"{path}.shard{s}",
                                core_to_arrays(self.shard_core(s)),
                                shard_meta)
        with open(path + ".meta.json", "w") as f:
            json.dump(meta, f)

    @classmethod
    def load(cls, mesh: Mesh, path: str, *, spec: ShardSpec | None = None,
             n_shards: int | None = None) -> "ShardedJasperIndex":
        """Restore a checkpoint at WHATEVER shard count the mesh provides.

        Same count as saved -> bit-exact restore (tombstones + free pools
        round-trip). Different count -> elastic reshard (core/resharding):
        live rows re-partition into capacity-balanced cores, packed codes
        bit-identical, adjacency remapped + repaired, and the old->new id
        map lands on ``idx.reshard_translation`` for outstanding tickets
        (None on an exact restore). `n_shards` is an optional guard: raise
        rather than silently reshard to an unintended count.
        """
        with open(path + ".meta.json") as f:
            meta = json.load(f)
        metric = meta.get("metric", "l2")
        store_dims = meta["dims"] + 1 if metric == "mips" else meta["dims"]
        if (spec is None and meta.get("row_axes")
                and all(a in mesh.axis_names for a in meta["row_axes"])):
            qa = meta["query_axis"]
            spec = ShardSpec(row_axes=tuple(meta["row_axes"]),
                             query_axis=qa if qa in mesh.axis_names else None)
        params = ConstructionParams(**meta["construction"])
        quantized = meta["quantization"] == "rabitq"
        locals_ = [core_from_arrays(
            np.load(f"{path}.shard{s}"), bits=meta["bits"],
            store_dims=store_dims, quantized=quantized)
            for s in range(meta["n_shards"])]

        # resolve the target shard count from mesh+spec WITHOUT
        # constructing: the constructor device-allocates a full empty
        # stacked core, and on the reshard path capacity/stride are only
        # known after the resplit — one construction, at the final shape
        row_axes = (spec.row_axes if spec is not None
                    else (tuple(a for a in mesh.axis_names if a != "model")
                          or (mesh.axis_names[0],)))
        target = 1
        for ax in row_axes:
            target *= mesh.shape[ax]
        if n_shards is not None and target != n_shards:
            raise ValueError(
                f"mesh provides {target} row shards but n_shards="
                f"{n_shards} was requested — pass a mesh/spec with "
                f"{n_shards} row shards")
        translation = None
        cap, stride = meta["capacity_per_shard"], meta.get("id_stride")
        if target != meta["n_shards"]:
            from repro.core.resharding import reshard_cores
            res = reshard_cores(
                locals_,
                old_id_stride=stride or 4 * cap,
                n_shards=target, params=params)
            cap, stride = res.capacity_per_shard, res.id_stride
            locals_, translation = res.cores, res.translation
        idx = cls(mesh, meta["dims"], cap, id_stride=stride, spec=spec,
                  metric=metric, construction=params,
                  quantization=meta["quantization"], bits=meta["bits"],
                  seed=meta["seed"])
        idx._mips_max_sqnorm = meta.get("mips_max_sqnorm")
        idx.core = idx._stack_cores(locals_)
        idx.reshard_translation = translation
        idx.plans.clear()
        if meta.get("rows_tier", "device") == "host":
            idx.evict_rows_to_host()    # restore the checkpoint's tier
        return idx

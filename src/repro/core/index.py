"""JasperIndex — thin host driver over one IndexCore.

Mirrors the paper's system surface: bulk build, streaming batch insertion
AND batched deletion (the "built for change" half), exact and RaBitQ-
quantized search (the "quantized for speed" half), plus save/load for fault
tolerance.

Since the IndexCore extraction, every hot path lives in
`core.index_core` as a pure op over the core pytree — `core_search`,
`core_insert_at`, `core_delete`, `core_consolidate`, `core_grow` — and
this class only supplies the HOST policy around them: slot allocation,
capacity-doubling, lazy quantizer training, MIPS augmentation, checkpoint
I/O. `ShardedJasperIndex` (core/distributed.py) drives the *same* ops with
the core shard_map-wrapped per row-shard; single-device is the 1-shard
case, not a separate implementation.

The full mutation lifecycle (core.mutations):

    build/insert -> LIVE -> delete (tombstone) -> consolidate (graph repair,
    slot freed) -> insert reuses the slot; capacity grows by buffer doubling
    when the tail runs out (copy-extension only — packed codes, vec_sqnorm,
    and adjacency never re-encode).

Searches never return tombstoned ids: every search path filters its final
frontier through the packed tombstone bitmap, and `traverse_deleted=False`
additionally masks deleted rows inside the scoring epilogues (the cheap
mode once `consolidate` has repaired the graph around them).
"""

from __future__ import annotations

import json
import os
import warnings
from dataclasses import asdict, replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.beam_search import beam_search, make_exact_scorer
from repro.core.construction import ConstructionParams
from repro.core.distances import mips_augment_query
from repro.core.index_core import (
    IndexCore,
    attach_quantizer,
    core_brute_force,
    core_build,
    core_consolidate,
    core_delete,
    core_from_arrays,
    core_grow,
    core_insert_at,
    core_live_mask,
    core_search,
    core_set_labels,
    core_size,
    bitmap_test_np,
    core_take_free_slots,
    core_to_arrays,
    init_core,
    tombstoned_lookup,
)
from repro.core.mutations import MutationState, pack_label_rows
from repro.core.pq import make_pq_scorer, pq_encode, pq_train
from repro.core.search_spec import PlanCache, SearchSpec, SearchSurface
from repro.core.storage import (
    TIER_STAT_KEYS,
    VectorStore,
    build_host_rerank_plan,
    rows_staged,
    tier_memory_stats,
)
from repro.obs.tracing import span as obs_span
from repro.core.rabitq import (
    RaBitQCodes,
    RaBitQParams,
    packed_bytes_per_vector,
    rabitq_encode,
    rabitq_train,
)
from repro.core.vamana import VamanaGraph

Array = jax.Array

_INF = float("inf")


@partial(jax.jit, static_argnames=("k", "beam_width", "max_iters", "rerank",
                                   "expand", "merge", "traverse_deleted"))
def _search_pq(vectors, vec_sqnorm, graph, pparams, pcodes, tomb_bits,
               queries, *, k, beam_width, max_iters, rerank, expand=1,
               merge="topk", traverse_deleted=True):
    score = make_pq_scorer(pparams, pcodes, queries)
    res = beam_search(graph, score, queries.shape[0],
                      beam_width=beam_width, max_iters=max_iters,
                      expand_per_iter=expand, merge_strategy=merge,
                      tombstone_bits=tomb_bits,
                      traverse_deleted=traverse_deleted)
    f_ids, f_dists = res.frontier_ids, res.frontier_dists
    if rerank:
        exact = make_exact_scorer(vectors, queries, graph.n_valid,
                                  vec_sqnorm)(f_ids)
        exact = jnp.where(f_ids >= 0, exact, _INF)
        f_dists, f_ids = jax.lax.sort((exact, f_ids), dimension=1,
                                      is_stable=True, num_keys=1)
    return f_ids[:, :k], f_dists[:, :k], res.n_hops


class JasperIndex(SearchSurface):
    """Updatable TPU-native ANNS index (Vamana graph + optional RaBitQ)."""

    def __init__(self, dims: int, capacity: int, *, metric: str = "l2",
                 quantization: str | None = None, bits: int = 4,
                 construction: ConstructionParams | None = None,
                 seed: int = 0, plan_cache_capacity: int | None = None,
                 rows_tier: str = "device"):
        if metric not in ("l2", "mips"):
            raise ValueError(f"metric must be l2|mips, got {metric!r}")
        if quantization not in (None, "rabitq", "pq"):
            raise ValueError(
                "quantization must be None, 'rabitq', or 'pq' "
                "(explicit opt-in; PQ is deprecated)")
        if quantization == "pq":
            warnings.warn(
                "quantization='pq' is the paper's NEGATIVE result: the "
                "unpacked LUT-based PQ path scatters over memory and has no "
                "kernel backing. It is kept only as a comparison baseline — "
                "use quantization='rabitq' for the kernel-backed quantized "
                "search path.", DeprecationWarning, stacklevel=2)
        self.dims = dims
        self.metric = metric
        # MIPS reduces to L2 with one augmented dimension (paper §6.3)
        self.store_dims = dims + 1 if metric == "mips" else dims
        self.quantization = quantization
        self.bits = bits
        self.params = construction or ConstructionParams()
        self.seed = seed

        self.core: IndexCore = init_core(capacity, self.store_dims,
                                         self.params.degree_bound)
        # compiled search plans keyed on (resolved spec, query shape,
        # liveness mode) — the single-device twin of the sharded driver's
        # plan cache; Searcher sessions and the legacy shims share it.
        # plan_cache_capacity bounds it LRU-style (None = unbounded) —
        # serving traffic with many (spec, shape) pairs should set it
        self.plans = PlanCache(capacity=plan_cache_capacity)
        # PQ is the deprecated comparison baseline — it rides as driver-side
        # side arrays, deliberately OUTSIDE the core (the sharded backend
        # and the kernel stack only ever see RaBitQ)
        self.pq_params = None
        self.pq_codes: Array | None = None
        self._mips_max_sqnorm: float | None = None
        # tiered storage (core/storage.py): where the f32 rows live.
        # "device" keeps them core pytree leaves (classic); "host" evicts
        # them to host numpy so only packed codes stay device-resident
        self.store = VectorStore()
        if rows_tier == "host":
            self.evict_rows_to_host()
        elif rows_tier != "device":
            raise ValueError(
                f"rows_tier must be device|host, got {rows_tier!r}")

    # -------------------------------------------------------- core delegation
    @property
    def capacity(self) -> int:
        return self.core.capacity

    @property
    def vectors(self) -> Array:
        return self.core.vectors

    @property
    def vec_sqnorm(self) -> Array:
        return self.core.vec_sqnorm

    @property
    def graph(self) -> VamanaGraph:
        return self.core.graph

    @graph.setter
    def graph(self, g: VamanaGraph) -> None:
        self.core = replace(self.core, adjacency=g.adjacency,
                            n_valid=g.n_valid, medoid=g.medoid)

    @property
    def mut(self) -> MutationState:
        return self.core.mut

    @mut.setter
    def mut(self, m: MutationState) -> None:
        self.core = replace(self.core, mut=m)

    @property
    def rabitq_codes(self) -> RaBitQCodes | None:
        return self.core.codes

    @property
    def rabitq_params(self) -> RaBitQParams | None:
        return self.core.rq_params

    # ---------------------------------------------------------- tiered rows
    @property
    def rows_tier(self) -> str:
        """Where the f32 rows live: "device" (core pytree leaves) or
        "host" (evicted to `self.store`; traversal runs on packed codes
        only and rerank fetches the frontier's rows host-side)."""
        return self.store.tier

    def evict_rows_to_host(self) -> "JasperIndex":
        """device -> host: move the f32 rows off the device, leaving only
        packed codes (+ graph/metadata) device-resident. Searches must
        then use `rerank_source="host"` (bit-identical) or "none";
        mutations keep working through write-through staging. Compiled
        plans are dropped (the core pytree structure changes)."""
        if self.quantization != "rabitq":
            raise ValueError(
                "evict_rows_to_host requires quantization='rabitq': "
                "without device-resident packed codes there is nothing "
                "left to traverse on (an exact-only core cannot serve "
                "any search with its rows evicted)")
        self.core = self.store.evict(self.core)
        self.plans.clear()
        return self

    def restore_rows_to_device(self) -> "JasperIndex":
        """host -> device: re-attach the f32 rows as core pytree leaves
        (classic fully-device-resident layout)."""
        self.core = self.store.restore(self.core)
        self.plans.clear()
        return self

    # ------------------------------------------------------------------ util
    @property
    def size(self) -> int:
        """Number of LIVE rows (high-water mark minus tombstoned/freed)."""
        return core_size(self.core)

    @property
    def generation(self) -> int:
        """Monotonic mutation counter (bumped by insert/delete/consolidate/
        grow) — serving layers stamp search results with it."""
        return int(self.core.mut.generation)

    @property
    def n_deleted(self) -> int:
        """Tombstoned-but-not-yet-consolidated rows."""
        return int(self.core.mut.n_deleted)

    @property
    def deleted_fraction(self) -> float:
        """Tombstone load factor — serving layers consolidate past a bound."""
        n = int(self.core.n_valid) - int(self.core.mut.n_free)
        return int(self.core.mut.n_deleted) / n if n else 0.0

    def live_mask(self) -> np.ndarray:
        """bool[capacity] of currently live rows (host copy)."""
        return core_live_mask(self.core)

    def tombstoned(self, ids) -> np.ndarray:
        """Host-side per-id deadness test (serving-contract check): True
        where an id is tombstoned/freed or past the high-water mark."""
        return tombstoned_lookup(np.asarray(self.core.mut.tombstone_bits),
                                 int(self.core.n_valid), ids)

    @property
    def _filter_tombstones(self) -> bool:
        """False while no bit can be set (nothing tombstoned, nothing
        freed), so the delete-free workload keeps filter-free executables."""
        return (int(self.core.mut.n_deleted) != 0
                or int(self.core.mut.n_free) != 0)

    def _prep_data(self, x: np.ndarray | Array) -> Array:
        x = jnp.asarray(x, dtype=jnp.float32)
        if self.metric == "mips":
            # Use a fixed global max-norm so streaming inserts stay consistent;
            # when a later batch RAISES the max, previously written rows are
            # re-augmented in place (see _reaugment_mips) — otherwise their
            # stale augmented coordinate silently corrupts the reduction.
            sq = jnp.sum(x * x, axis=-1)
            m2 = float(jnp.max(sq))
            if self._mips_max_sqnorm is None:
                self._mips_max_sqnorm = m2
            elif m2 > self._mips_max_sqnorm:
                old = self._mips_max_sqnorm
                self._mips_max_sqnorm = m2
                self._reaugment_mips(old, m2)
            extra = jnp.sqrt(jnp.maximum(self._mips_max_sqnorm - sq, 0.0))
            x = jnp.concatenate([x, extra[:, None]], axis=-1)
        return x

    def _reaugment_mips(self, old_m2: float, new_m2: float) -> None:
        """Re-augment all written rows after the global max-norm rose.

        Every written row was augmented under old_m2 (this method maintains
        that invariant inductively), so the update is closed-form on the
        augmented coordinate: e' = sqrt(e^2 + delta), |row'|^2 = |row|^2 +
        delta. Quantized codes re-encode from the updated vectors — the
        rotation/centroid are dimension-state, not norm-state, so the
        quantizer itself is untouched.
        """
        core = self.core
        n = int(core.n_valid)
        if n == 0:
            return
        delta = new_m2 - old_m2
        row = jnp.arange(core.capacity) < n
        last = core.vectors[:, -1]
        new_last = jnp.sqrt(last * last + delta)
        vectors = core.vectors.at[:, -1].set(jnp.where(row, new_last, last))
        sqnorm = jnp.where(row, core.vec_sqnorm + delta, core.vec_sqnorm)
        core = replace(core, vectors=vectors, vec_sqnorm=sqnorm)
        if core.codes is not None:
            # re-encode only the written prefix (n is a host int, so this
            # is a static slice — the zero tail never hits the rotation)
            enc = rabitq_encode(core.rq_params, vectors[:n])
            c = core.codes
            core = replace(core, codes=replace(
                c, rows=c.rows.at[:n].set(enc.rows)))
        self.core = core
        if self.pq_codes is not None:
            self.pq_codes = self.pq_codes.at[:n].set(
                pq_encode(self.pq_params, vectors[:n]))

    def _prep_query(self, q: np.ndarray | Array) -> Array:
        q = jnp.asarray(q, dtype=jnp.float32)
        if self.metric == "mips":
            q = mips_augment_query(q)
        return q

    def _ensure_quantizer(self, rows: Array) -> None:
        """Lazy quantizer training on the first written batch."""
        if self.quantization == "rabitq" and self.core.rq_params is None:
            key = jax.random.PRNGKey(self.seed)
            self.core = attach_quantizer(
                self.core, rabitq_train(key, rows, bits=self.bits))
        elif self.quantization == "pq" and self.pq_params is None:
            for nsub in (16, 8, 4, 2, 1):
                if self.store_dims % nsub == 0:
                    break
            self.pq_params = pq_train(jax.random.PRNGKey(self.seed), rows,
                                      n_subspaces=nsub)
            self.pq_codes = jnp.zeros(
                (self.capacity, self.pq_params.n_subspaces), jnp.uint8)

    def _pq_write(self, ids: Array, rows: Array) -> None:
        if self.pq_codes is not None:
            self.pq_codes = self.pq_codes.at[ids].set(
                pq_encode(self.pq_params, rows))

    # ------------------------------------------------------------- build/insert
    def build(self, data: np.ndarray | Array, *, labels=None,
              refine: bool = False, progress_fn=None) -> "JasperIndex":
        """Bulk construction over `data` (rows 0..N). Resets the graph and
        all mutation state (the generation counter keeps advancing).
        `labels`: optional per-row label ids (scalar or per-row sets) for
        filtered search — see docs/filtered_search.md."""
        with obs_span("index.build", n=int(np.asarray(data).shape[0]),
                      sharded=False), rows_staged(self):
            x = self._prep_data(data)
            self._ensure_quantizer(x)
            self.core = core_build(self.core, x, params=self.params,
                                   refine=refine, progress_fn=progress_fn)
            if labels is not None:
                self.set_labels(np.arange(x.shape[0], dtype=np.int32),
                                labels)
            self._pq_write(jnp.arange(x.shape[0], dtype=jnp.int32), x)
        return self

    def _grow_to_fit(self, n_rows: int) -> None:
        """Double capacity until n_rows fit (no-op when they already do)."""
        if n_rows <= self.capacity:
            return
        new_cap = self.capacity
        while n_rows > new_cap:
            new_cap *= 2
        self.grow(new_cap)

    def _allocate_slots(self, b: int) -> np.ndarray:
        """Claim b slot ids: freed slots first (ascending), then fresh tail
        ids past the high-water mark; the capacity auto-doubles when the
        tail runs out. Popped slots' tombstone bits are cleared."""
        self.core, reused = core_take_free_slots(self.core, b)
        fresh_needed = b - reused.size
        hw = int(self.core.n_valid)
        self._grow_to_fit(hw + fresh_needed)
        fresh = np.arange(hw, hw + fresh_needed, dtype=np.int32)
        return np.concatenate([reused, fresh])

    def insert(self, data: np.ndarray | Array, *,
               labels=None) -> np.ndarray:
        """Streaming batch insertion ("built for change").

        Freed slots are reused before the tail advances; the index grows by
        buffer doubling if the batch would overflow capacity. Returns the
        assigned row ids, int32[B] (the ids searches will report).
        `labels`: optional label ids for the batch (scalar = every row, or
        one entry/set per row) — set atomically with the rows, so a
        filtered search never sees an unlabeled live row.
        """
        if np.shape(data)[0] == 0:       # empty tick from a stream: no-op
            return np.empty((0,), np.int32)
        with rows_staged(self):
            x = self._prep_data(data)
            b = x.shape[0]
            if self.size == 0:
                # empty index (fresh, or everything was deleted): a clean
                # build over this batch beats stitching onto a dead graph
                self._grow_to_fit(b)
                self._ensure_quantizer(x)
                self.core = core_build(self.core, x, params=self.params)
                ids = np.arange(b, dtype=np.int32)
                if labels is not None:
                    self.set_labels(ids, labels)
                self._pq_write(jnp.arange(b, dtype=jnp.int32), x)
                return ids
            ids = self._allocate_slots(b)
            ids_dev = jnp.asarray(ids, jnp.int32)
            self.core = core_insert_at(self.core, ids_dev, x,
                                       params=self.params)
            if labels is not None:
                self.set_labels(ids, labels)
            self._pq_write(ids_dev, x)
            jax.block_until_ready(self.core.adjacency)  # storage semantics
        return ids

    def set_labels(self, ids, labels) -> None:
        """Assign per-row label bitsets (filtered search / tenant
        namespaces). `labels` is a scalar label id (applied to every row),
        one label id per row, or one label-id set per row; ids must
        address rows of this index."""
        ids = np.atleast_1d(np.asarray(ids)).astype(np.int32).ravel()
        rows = pack_label_rows(labels, ids.size)
        self.core = core_set_labels(self.core, ids, rows)

    # ------------------------------------------------------------- delete/repair
    def delete(self, ids) -> int:
        """Batched tombstone delete. Returns the number of rows deleted.

        O(1) graph work: rows are tombstoned in the packed bitmap, stay
        traversable (their edges keep the graph connected) but are never
        returned by any search. `consolidate()` later repairs the graph and
        recycles the slots. Raises on ids that are not currently live.
        """
        ids_np = np.atleast_1d(np.asarray(ids)).astype(np.int64).ravel()
        if ids_np.size == 0:
            return 0
        hw = int(self.core.n_valid)
        bad = ids_np[(ids_np < 0) | (ids_np >= hw)]
        if bad.size:
            raise ValueError(f"ids out of range [0, {hw}): {bad[:8].tolist()}")
        # validate against the PACKED bytes (cap/8 host copy + per-id bit
        # test) — never unpack the dense bitmap on the delete path
        bits = np.asarray(self.core.mut.tombstone_bits)
        dead = ids_np[bitmap_test_np(bits, ids_np)]
        if dead.size:
            raise ValueError(
                f"ids already deleted or freed: {dead[:8].tolist()}")
        # pad to a power-of-two rung (-1 = ignored) so varying delete batch
        # sizes reuse one executable per rung
        rung = 1 << max(0, int(ids_np.size - 1).bit_length())
        padded = np.full((rung,), -1, np.int32)
        padded[:ids_np.size] = ids_np
        self.core, n = core_delete(self.core, jnp.asarray(padded))
        return int(n)

    def consolidate(self, *, refine: bool = True) -> dict:
        """Batched graph repair over neighborhoods touched by deleted rows.

        Every live vertex with an edge into a tombstoned vertex gets its
        edge list rebuilt through alpha-RobustPrune — refine=True (default)
        re-links it by snapshot beam search against the tombstoned graph
        (recall back at fresh-build level), refine=False does the cheaper
        one-hop local repair (candidates: its live neighbors ∪ the deleted
        neighbors' live neighbors). Deleted rows then lose their adjacency,
        their slots join the free pool, and the medoid refreshes over live
        rows. Returns {"n_freed", "n_repaired"}.
        """
        with rows_staged(self):
            self.core, stats = core_consolidate(self.core,
                                                params=self.params,
                                                refine=refine)
        return stats

    def grow(self, new_capacity: int | None = None) -> "JasperIndex":
        """Grow capacity by pure copy-extension (default: doubling).

        Nothing re-encodes: packed RaBitQ codes, vec_sqnorm, adjacency, the
        tombstone bitmap, and the free pool are all capacity-major, so the
        resident prefix of every buffer is byte-identical after the grow.
        """
        new_cap = new_capacity or 2 * self.capacity
        if new_cap < self.capacity:
            raise ValueError(f"cannot shrink {self.capacity} -> {new_cap}")
        if new_cap == self.capacity:
            return self
        with rows_staged(self):
            self.core = core_grow(self.core, new_cap)
            if self.pq_codes is not None:
                from repro.core.mutations import grow_rows
                self.pq_codes = grow_rows(self.pq_codes, new_cap, 0)
        return self

    # ------------------------------------------------------------------ search
    # searcher()/recall() come from SearchSurface — the one shared copy
    def _search_plan(self, rspec, q_shape, filt: bool):
        """Plan-cache lookup/build: `(queries, filter_bytes) ->
        (ids, dists, n_hops)`. The filter VALUE is a runtime operand of
        the filtered plan — the key carries only its presence (inside
        `rspec.filtered`), so every filter value shares one executable."""
        key = ("search", rspec, tuple(q_shape), filt)

        def build():
            plans = self.plans

            # the program's stable name: `jit_jasper_search` in a
            # profiler trace, whatever the spec
            if rspec.filtered:
                def jasper_search(core, queries, fb):
                    plans.count_trace()   # runs at trace time only
                    return core_search(core, queries, spec=rspec,
                                       filter_tombstones=filt,
                                       filter_bytes=fb)
            else:
                def jasper_search(core, queries):
                    plans.count_trace()   # runs at trace time only
                    return core_search(core, queries, spec=rspec,
                                       filter_tombstones=filt)
            return jax.jit(jasper_search)

        fn = self.plans.get(key, build)
        if rspec.rerank_source == "host":
            # two-stage host-tier plan: the traversal plan above returns
            # the FULL-width estimator frontier (core_search skips the
            # in-graph rerank — the core has no rows operand), then the
            # frontier's rows are fetched from the host tier and reranked
            # by a separately-keyed compiled plan. Bit-identical to the
            # device tier (see core/storage.py).
            rkey = ("rerank_host", rspec, tuple(q_shape))
            rplan = self.plans.get(
                rkey,
                lambda: build_host_rerank_plan(rspec,
                                               self.plans.count_trace))
            store = self.store

            def run_host(queries, fb=None):
                out = (fn(self.core, queries, jnp.asarray(fb, jnp.uint8))
                       if rspec.filtered else fn(self.core, queries))
                f_ids = out[0]
                rows, sq = store.gather(np.asarray(f_ids))
                ids, dists = rplan(queries, f_ids, jnp.asarray(rows),
                                   jnp.asarray(sq))
                return (ids, dists, out[2]) + tuple(out[3:])

            return run_host
        if rspec.filtered:
            return lambda queries, fb=None: fn(
                self.core, queries, jnp.asarray(fb, jnp.uint8))
        return lambda queries, fb=None: fn(self.core, queries)

    def search(self, queries: np.ndarray | Array, k: int = 10, *,
               beam_width: int | None = None, max_iters: int | None = None,
               expand: int = 1, use_kernels: bool = False,
               merge: str = "topk",
               traverse_deleted: bool = True) -> tuple[Array, Array]:
        """Exact-distance beam search — legacy kwargs shim over
        `searcher(SearchSpec(...))`; returns (ids (Q,k), dists (Q,k))."""
        res = self.searcher(SearchSpec(
            k=k, beam_width=beam_width, max_iters=max_iters, expand=expand,
            use_kernels=use_kernels, merge=merge,
            traverse_deleted=traverse_deleted)).search(queries)
        return res.ids, res.dists

    def search_rabitq(self, queries: np.ndarray | Array, k: int = 10, *,
                      beam_width: int | None = None,
                      max_iters: int | None = None, rerank: bool = True,
                      expand: int = 1, use_kernels: bool = False,
                      merge: str = "topk",
                      traverse_deleted: bool = True) -> tuple[Array, Array]:
        """RaBitQ estimated-distance beam search (the paper's §5.1 hot
        path) — legacy kwargs shim over `searcher(SearchSpec(...))`."""
        if self.core.codes is None:
            raise RuntimeError("index was not built with quantization='rabitq'")
        res = self.searcher(SearchSpec(
            k=k, beam_width=beam_width, max_iters=max_iters, expand=expand,
            quantized=True, rerank=rerank, use_kernels=use_kernels,
            merge=merge, traverse_deleted=traverse_deleted)).search(queries)
        return res.ids, res.dists

    def search_pq(self, queries: np.ndarray | Array, k: int = 10, *,
                  beam_width: int | None = None,
                  max_iters: int | None = None, rerank: bool = True,
                  expand: int = 1, merge: str = "topk",
                  traverse_deleted: bool = True) -> tuple[Array, Array]:
        """PQ LUT-based beam search — DEPRECATED comparison baseline.

        The paper's negative result (§5, Fig 12): scattered 256-entry table
        lookups, no kernel backing, kept only so benchmarks can reproduce
        the comparison. Requires the explicit quantization='pq' opt-in.
        (Deliberately NOT a core op or a SearchSpec mode: the sharded
        backend and the Searcher surface never see PQ.)
        """
        if self.pq_codes is None:
            raise RuntimeError("index was not built with quantization='pq'")
        warnings.warn(
            "search_pq is deprecated (the paper's negative-result baseline); "
            "use quantization='rabitq' with searcher(SearchSpec(quantized="
            "True)) for the kernel-backed quantized path.",
            DeprecationWarning, stacklevel=2)
        # defaults resolve through the ONE definition site (SearchSpec)
        rspec = SearchSpec(
            k=k, beam_width=beam_width, max_iters=max_iters, expand=expand,
            merge=merge, traverse_deleted=traverse_deleted).resolve()
        q = self._prep_query(queries)
        tomb = (self.core.mut.tombstone_bits if self._filter_tombstones
                else None)
        ids, dists, _ = _search_pq(self.core.vectors, self.core.vec_sqnorm,
                                   self.core.graph, self.pq_params,
                                   self.pq_codes, tomb, q,
                                   k=k, beam_width=rspec.beam_width,
                                   max_iters=rspec.max_iters,
                                   rerank=rerank, expand=expand, merge=merge,
                                   traverse_deleted=traverse_deleted)
        return ids, dists

    def brute_force(self, queries: np.ndarray | Array, k: int = 10
                    ) -> tuple[Array, Array]:
        """Exact top-k by full scan over LIVE rows (ground truth for recall)."""
        q = self._prep_query(queries)
        with rows_staged(self):
            out = core_brute_force(self.core, q, k=k)
            jax.block_until_ready(out)   # computed before rows detach
        return out


    # ----------------------------------------------------------------- memory
    def memory_stats(self) -> dict[str, float]:
        full = self.store_dims * 4
        stats = {
            "vector_bytes_per_row": float(full),
            "graph_bytes_per_row": float(self.params.degree_bound * 4),
            # mutation metadata: 1 bit/row tombstones + 4 B/row free pool
            "tombstone_bitmap_bytes": float(self.core.mut.tombstone_bits.size),
            "free_pool_bytes": float(self.core.mut.free_ids.size * 4),
        }
        if self.quantization == "rabitq":
            stats["rabitq_bytes_per_row"] = float(
                packed_bytes_per_vector(self.store_dims, self.bits))
            stats["compression_ratio"] = full / stats["rabitq_bytes_per_row"]
            if self.core.codes is not None:
                # actual code rows resident in HBM (not the formula):
                # packed codes + the two f32 factors, capacity rows
                resident = self.core.codes.rows.nbytes
                stats["rabitq_resident_bytes"] = float(resident)
                stats["rabitq_resident_bytes_per_row"] = (
                    resident / self.capacity)
        stats.update(tier_memory_stats(
            self.core, self.store, capacity=self.capacity,
            store_dims=self.store_dims))
        return stats

    def storage_stats(self) -> dict:
        """Tier residence + host-fetch counters for the `storage.*`
        metrics namespace (obs/metrics.py `storage_stats_collector`)."""
        ms = self.memory_stats()
        out = {k: ms[k] for k in TIER_STAT_KEYS if k in ms}
        out.update({f"fetch_{k}": v
                    for k, v in self.store.fetch_stats.as_dict().items()})
        return out

    # -------------------------------------------------------------- save/load
    def _meta(self) -> dict:
        return {
            "dims": self.dims, "metric": self.metric,
            "capacity": self.capacity,
            "quantization": self.quantization, "bits": self.bits,
            "seed": self.seed, "construction": asdict(self.params),
            "mips_max_sqnorm": self._mips_max_sqnorm,
            "rows_tier": self.rows_tier,
        }

    def save(self, path: str) -> None:
        """Atomic checkpoint (tmp + rename): graph, vectors, quantizer,
        mutation state (tombstones + free pool round-trip exactly).

        The array payload is `core_to_arrays` — the SAME format every shard
        of a ShardedJasperIndex serializes through, so shard files and
        single-device checkpoints are mutually readable.
        """
        with rows_staged(self):
            # host-tier rows stage back in so the payload keeps the ONE
            # cross-driver format; the meta records the tier layout and
            # load() re-evicts
            arrays = core_to_arrays(self.core)
        if self.pq_codes is not None:
            arrays |= {
                "pq_codes": np.asarray(self.pq_codes),
                "pq_codebooks": np.asarray(self.pq_params.codebooks),
            }
        save_npz_atomic(path, arrays, self._meta())

    @classmethod
    def load(cls, path: str) -> "JasperIndex":
        with open(path + ".meta.json") as f:
            meta = json.load(f)
        data = np.load(path)
        with warnings.catch_warnings():
            # loading a PQ checkpoint should not re-fire the opt-in warning
            warnings.simplefilter("ignore", DeprecationWarning)
            idx = cls(meta["dims"], meta["capacity"], metric=meta["metric"],
                      quantization=meta["quantization"], bits=meta["bits"],
                      construction=ConstructionParams(**meta["construction"]),
                      seed=meta["seed"])
        idx._mips_max_sqnorm = meta["mips_max_sqnorm"]
        idx.core = core_from_arrays(
            data, bits=meta["bits"], store_dims=idx.store_dims,
            quantized=meta["quantization"] == "rabitq")
        if meta["quantization"] == "pq" and "pq_codes" in data:
            from repro.core.pq import PQParams
            idx.pq_params = PQParams(
                codebooks=jnp.asarray(data["pq_codebooks"]))
            idx.pq_codes = jnp.asarray(data["pq_codes"])
        if meta.get("rows_tier", "device") == "host":
            idx.evict_rows_to_host()    # restore the checkpoint's tier
        return idx


def save_npz_atomic(path: str, arrays: dict, meta: dict) -> None:
    """Atomic .npz + .meta.json checkpoint write (tmp + rename).

    The tmp name always carries the ".npz" suffix np.savez would otherwise
    append implicitly, so the final os.replace is deterministic (no
    exists() race on the suffixed name). Shared by both index drivers.
    """
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp.npz"
    np.savez(tmp, **arrays)
    os.replace(tmp, path)
    with open(path + ".meta.json", "w") as f:
        json.dump(meta, f)

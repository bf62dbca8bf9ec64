"""One query surface: declarative SearchSpec + compiled Searcher sessions.

The paper's throughput story (§5: fused estimator + optimized greedy
search) used to hide behind a kwarg explosion — `search/search_rabitq/
search_pq` on two drivers, the service, the dry-run launcher, and every
benchmark each re-declared the same ~8 tuning knobs and copy-pasted the
default formulas. This module makes the query configuration a first-class
object (the online-serving literature treats it as a scheduling object —
cf. the real-time adaptive multi-stream GPU ANNS system, arXiv:2408.02937):

  * `SearchSpec` — frozen, hashable, JSON-serializable description of ONE
    search configuration. `resolve()` is the single definition site of
    every default formula and every validation rule in the system: the
    beam-width default, the iteration-budget formula, merge-strategy
    membership, and the up-front "quantized search needs codes" check all
    live here and nowhere else.
  * `ResolvedSearchSpec` — the fully-concrete, normalized form. Frozen and
    hashable, so it is BOTH the static jit argument `core_search` compiles
    against and the plan-cache key.
  * `SearchResult` — what a search returns: ids, dists, per-query hop
    counts (`core_search` always computed n_hops; every driver used to
    drop it), and the snapshot generation. The serving layer's
    `SearchTicket` IS this type.
  * `PlanCache` — executable cache keyed on (resolved spec, query shape,
    liveness mode) with hit/miss/trace counters. Generalizes the `_fn`
    cache that previously existed only in `ShardedJasperIndex` to both
    backends: repeated single-device searches no longer re-enter
    `core_search`'s 11-static-arg dispatch path per call.
  * `Searcher` — a compiled search session from `index.searcher(spec)`:
    resolves the spec once, looks up (or builds) the jitted executable per
    query shape, and supports `submit()/drain()` double-buffered batching
    so a serving loop can overlap host scheduling with device search.
  * `land` — the ONE host landing of a `SearchResult`, which records the
    process-wide `session.*` counters (`obs.registry()`) beside the
    dispatch counters `Searcher` records.

Driver contract (both `JasperIndex` and `ShardedJasperIndex` satisfy it):
`_prep_query`, `_filter_tombstones`, `generation`, `brute_force`, a
`plans: PlanCache`, and `_search_plan(resolved, q_shape, filt)` returning
a callable `(queries, filter_bytes) -> (ids, dists, n_hops)` — with a
fourth `SearchTelemetry` element iff the resolved spec has
`telemetry="on"`. `filter_bytes` is the runtime label-filter operand
(None unless the resolved spec has `filtered=True`). The jitted search
programs are named `jasper_search` (`jit_jasper_search` in a profiler
trace), the host-tier rerank programs `jasper_rerank_host`.
"""

from __future__ import annotations

import json
import numbers
import time
from collections import OrderedDict, deque
from dataclasses import asdict, dataclass, fields, replace
from typing import Any, NamedTuple

import jax
import numpy as np

from repro.core.beam_search import MERGE_STRATEGIES
from repro.core.mutations import N_LABELS, filter_to_bytes
from repro.obs.metrics import registry as obs_registry
from repro.obs.tracing import span as obs_span

SPEC_VERSION = 1

FUSION_MODES = ("none", "hop", "megakernel")

TELEMETRY_MODES = ("off", "on")

# Where the exact rerank reads its f32 rows (the tiered-storage knob —
# see core/storage.py and docs/tiered_storage.md): "device" reranks from
# device-resident core.vectors (the classic path), "host" gathers only
# the final frontier's rows from the host tier (traversal runs entirely
# on packed codes; bit-identical to "device"), "none" skips the rerank
# and serves estimator distances (results flagged
# `SearchResult.estimated`). Resolution collapses quantized rerank=False
# to "none", so (rerank, rerank_source) is always one of
# (True, "device") | (True, "host") | (False, "none") after resolve().
RERANK_SOURCES = ("device", "host", "none")

# Label-filter walk policy, mirroring `traverse_deleted`: "traverse" walks
# through non-matching rows (connectivity) but never returns them;
# "exclude" additionally masks them inside the scoring epilogues.
FILTER_MODES = ("exclude", "traverse")

# The default shape ladder for coalesced serving (serving/scheduler.py):
# standing queries are padded up to the next rung so EVERY dispatched
# batch has one of these shapes — the plan cache then holds at most
# len(ladder) search plans per (spec, liveness) pair and steady-state
# open-loop traffic retraces nothing, whatever the arrival pattern.
BUCKET_LADDER = (1, 8, 32, 128)


def bucket_for(n: int, ladder: tuple = BUCKET_LADDER) -> int:
    """The smallest ladder rung >= n — the padded batch shape a coalesced
    dispatch of n queries uses. n above the top rung returns the top rung
    (callers split oversized batches; the scheduler never dispatches more
    than `ladder[-1]` queries in one launch)."""
    if n < 1:
        raise ValueError(f"bucket_for needs n >= 1, got {n}")
    for b in sorted(ladder):
        if n <= b:
            return int(b)
    return int(max(ladder))


def pad_to_bucket(queries: np.ndarray, ladder: tuple = BUCKET_LADDER
                  ) -> tuple[np.ndarray, int]:
    """Pad a (n, D) query batch up to its ladder rung: returns
    `(padded (bucket, D), n)`. Padding rows repeat the last real query —
    in-distribution values, so the padded rows walk the same graph and
    never poison batchmates (searches are row-independent) — and the
    caller slices results back to the first n rows, so padding never
    leaks into returned tickets (asserted in tests/test_scheduler.py).
    """
    q = np.asarray(queries)
    n = int(q.shape[0])
    bucket = bucket_for(n, ladder)
    if bucket == n:
        return q, n
    pad = np.repeat(q[-1:], bucket - n, axis=0)
    return np.concatenate([q, pad], axis=0), n


def check_quantized_backend(index, *, need_codes: bool = True) -> None:
    """THE quantized-capability check: the index must be a RaBitQ backend
    and (unless `need_codes=False` — e.g. a service constructed before the
    first build/insert trains the quantizer) already hold packed codes.
    `resolve(index)` and the serving layer both call this one function."""
    if getattr(index, "quantization", None) != "rabitq":
        raise ValueError(
            "quantized=True requires an index built with "
            "quantization='rabitq' (this core has no packed codes)")
    core = getattr(index, "core", None)
    if need_codes and core is not None and core.codes is None:
        raise ValueError(
            "quantized=True on a codeless core: this "
            "quantization='rabitq' index has not trained its quantizer "
            "yet — build or insert data before opening a quantized "
            "search session")


def check_rows_tier(index, rerank_source: str) -> None:
    """THE rows-tier capability check: a resolved `rerank_source` must
    match where the index's f32 rows actually live (see core/storage.py).
    `resolve(index)` and the serving layer both call this one function,
    so tier mismatches fail at spec resolution / service construction —
    never mid-trace."""
    tier = getattr(index, "rows_tier", "device")
    if rerank_source == "host" and tier != "host":
        raise ValueError(
            "rerank_source='host' requires the index's f32 rows to be "
            "evicted to the host tier (index.rows_tier == 'host'; call "
            "evict_rows_to_host()) — this index's rows are "
            "device-resident, so use rerank_source='device' "
            "(bit-identical) or evict first")
    if rerank_source == "device" and tier != "device":
        raise ValueError(
            "rerank_source='device' needs device-resident f32 rows, but "
            "this index's rows are evicted to the host tier — use "
            "rerank_source='host' (bit-identical exact rerank) or "
            "'none' (estimator-only), or call restore_rows_to_device()")


def _as_int(name: str, value, *, floor: int) -> int:
    """Coerce an integral spec field (python or numpy int — the legacy
    kwargs surface routinely receives numpy scalars) to a plain int;
    bool and everything non-integral are configuration errors."""
    if isinstance(value, bool) or not isinstance(value, numbers.Integral):
        raise ValueError(f"{name} must be an int, got {value!r}")
    value = int(value)
    if value < floor:
        raise ValueError(f"{name} must be >= {floor}, got {value}")
    return value


# ---------------------------------------------------------------------------
# The declarative spec
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SearchSpec:
    """Declarative description of one search configuration.

    k:            results per query.
    beam_width:   frontier size (None -> resolved default).
    max_iters:    greedy-walk iteration budget (None -> resolved default,
                  which scales with beam_width / expand).
    expand:       frontier nodes expanded per iteration (CAGRA-style
                  multi-expansion; E x fewer sequential steps).
    quantized:    beam-search on RaBitQ estimated distances over the packed
                  codes instead of exact distances.
    rerank:       (quantized only) re-score the final frontier exactly.
    rerank_source: (quantized only) where the exact rerank reads its f32
                  rows — "device" (core.vectors, the classic path),
                  "host" (rows evicted to the host tier; only the final
                  frontier's rows are fetched — bit-identical to
                  "device"), or "none" (code-only serving: estimator
                  distances, `SearchResult.estimated=True`). Quantized
                  rerank=False normalizes to "none"; part of the
                  resolved spec, so the plan cache keys it.
    rerank_tile:  query-tile size for the exact rerank gather buffer.
    use_kernels:  route scoring through the fused Pallas kernels.
    merge:        per-hop frontier merge strategy ("topk"|"sort"|"kernel").
    traverse_deleted: tombstone policy — walk through tombstoned rows
                  (connectivity-preserving default) or mask them inside the
                  scoring epilogues. Either way they are never returned.
    fusion:       search-loop fusion level: "none" (kernel-per-step jnp
                  loop), "hop" (ONE fused Pallas launch per hop: gather +
                  score + merge), or "megakernel" (the whole beam loop in
                  ONE persistent launch, frontier resident on-chip).
    beam_schedule: optional per-hop frontier widths (wide early, narrow
                  late). Hop t uses schedule[min(t, len-1)]; beam_width
                  defaults to max(schedule). None = constant beam_width.
    telemetry:    per-search kernel telemetry: "off" (default — a TRUE
                  zero: no extra outputs, unchanged plan-cache keys,
                  bit-identical results) or "on" (the search additionally
                  returns a `SearchTelemetry`: candidates scored,
                  tombstone/filter-masked count, duplicate-visit count,
                  per-hop beam occupancy). Part of the resolved spec, so
                  the plan cache keys it — on/off are separate plans.
    filter:       label filter — a label id (int) or set of label ids;
                  only rows whose label bitset intersects it are returned.
                  None (default) = unfiltered. The VALUE is a runtime
                  operand (a uint8[N_LABEL_BYTES] byte mask fed to the
                  compiled plan), so the plan cache splits only on filter
                  PRESENCE: every filter value shares one executable.
    filter_mode:  walk policy for non-matching rows, mirroring
                  `traverse_deleted`: "traverse" (default) walks through
                  them for connectivity but never returns them; "exclude"
                  additionally masks them inside the scoring epilogues
                  (tighter frontiers at low selectivity, at the cost of
                  routing). Normalized to "traverse" when filter is None.
    """

    k: int = 10
    beam_width: int | None = None
    max_iters: int | None = None
    expand: int = 1
    quantized: bool = False
    rerank: bool = True
    rerank_source: str = "device"
    rerank_tile: int = 512
    use_kernels: bool = False
    merge: str = "topk"
    traverse_deleted: bool = True
    fusion: str = "none"
    beam_schedule: tuple | None = None
    telemetry: str = "off"
    filter: tuple | int | None = None
    filter_mode: str = "traverse"

    # ------------------------------------------------------------- resolve
    def resolve(self, index: Any = None) -> "ResolvedSearchSpec":
        """Fill defaults, validate, normalize — the ONE definition site.

        Every default formula in the search stack lives here: callers
        (drivers, service, benchmarks, launchers) never re-derive them.
        With `index` given, configuration errors that would otherwise
        surface mid-trace are rejected up front (e.g. `quantized=True`
        on a core that has no codes).
        """
        k = _as_int("k", self.k, floor=1)
        expand = _as_int("expand", self.expand, floor=1)
        if self.merge not in MERGE_STRATEGIES:
            raise ValueError(
                f"merge must be one of {MERGE_STRATEGIES}, "
                f"got {self.merge!r}")
        if self.fusion not in FUSION_MODES:
            raise ValueError(
                f"fusion must be one of {FUSION_MODES}, got {self.fusion!r}")
        if self.telemetry not in TELEMETRY_MODES:
            raise ValueError(
                f"telemetry must be one of {TELEMETRY_MODES}, "
                f"got {self.telemetry!r}")
        if self.filter_mode not in FILTER_MODES:
            raise ValueError(
                f"filter_mode must be one of {FILTER_MODES}, "
                f"got {self.filter_mode!r}")
        filt = self.filter
        if filt is not None:
            if isinstance(filt, bool) or (
                    not isinstance(filt, numbers.Integral)
                    and not hasattr(filt, "__iter__")):
                raise ValueError(
                    f"filter must be a label id, a sequence of label ids, "
                    f"or None, got {filt!r}")
            labels = ((filt,) if isinstance(filt, numbers.Integral)
                      else tuple(filt))
            if not labels:
                raise ValueError(
                    "filter must be a non-empty label set or None (an "
                    "empty filter would match no rows; pass None to "
                    "search unfiltered)")
            for lab in labels:
                lab = _as_int("filter labels", lab, floor=0)
                if lab >= N_LABELS:
                    raise ValueError(
                        f"filter label {lab} out of range "
                        f"[0, {N_LABELS})")
        filtered = filt is not None
        # filter_mode is dead without a filter — normalize so unfiltered
        # specs that differ only in mode share one plan-cache entry
        filter_mode = self.filter_mode if filtered else "traverse"
        schedule = self.beam_schedule
        if schedule is not None:
            try:
                schedule = tuple(_as_int("beam_schedule entries", w, floor=1)
                                 for w in schedule)
            except TypeError:
                raise ValueError(
                    f"beam_schedule must be a sequence of ints, "
                    f"got {self.beam_schedule!r}") from None
            if not schedule:
                raise ValueError("beam_schedule must be non-empty or None")
            if min(schedule) < k:
                raise ValueError(
                    f"every beam_schedule entry must be >= k={k}, got "
                    f"{schedule} (a hop narrower than k cannot carry k "
                    "results to the output)")
        bw = (max(schedule) if schedule is not None
              else max(k, 32) if self.beam_width is None
              else _as_int("beam_width", self.beam_width, floor=1))
        if self.beam_width is not None and schedule is not None:
            bw = _as_int("beam_width", self.beam_width, floor=1)
            if max(schedule) > bw:
                raise ValueError(
                    f"beam_schedule entries must be <= beam_width={bw}, "
                    f"got {schedule} (the frontier buffer is beam_width "
                    "wide; a hop cannot be wider than the buffer)")
        if bw < k:
            raise ValueError(
                f"beam_width must be an int >= k={k}, got {bw!r} "
                "(the final frontier is the result buffer: a beam narrower "
                "than k cannot hold k results)")
        mi = ((2 * bw + 8) // expand + 4 if self.max_iters is None
              else _as_int("max_iters", self.max_iters, floor=1))
        rerank_tile = _as_int("rerank_tile", self.rerank_tile, floor=1)
        source = self.rerank_source
        if source not in RERANK_SOURCES:
            raise ValueError(
                f"rerank_source must be one of {RERANK_SOURCES}, "
                f"got {source!r}")
        if not self.quantized:
            if source != "device":
                raise ValueError(
                    f"rerank_source={source!r} requires quantized=True: "
                    "the exact path scores device-resident rows directly "
                    "(there is no estimator to serve and no separate "
                    "rerank stage to redirect)")
            rerank = True
        else:
            rerank = bool(self.rerank)
            if source == "none":
                # code-only serving: "none" IS the rerank-off form
                rerank = False
            elif not rerank:
                if source == "host":
                    raise ValueError(
                        "rerank_source='host' with rerank=False is "
                        "contradictory: the host tier exists to feed the "
                        "exact rerank — use rerank_source='none' for "
                        "code-only serving")
                # quantized rerank=False with the default device source
                # normalizes to the code-only form, so pre-tiering specs
                # keep sharing one plan-cache entry with their twin
                source = "none"
        if index is not None:
            if self.quantized:
                # reject a codeless core up front, not mid-trace
                check_quantized_backend(index)
            check_rows_tier(index, source)
        # normalize fields the exact path never reads, so exact-path specs
        # that differ only in rerank knobs share one plan-cache entry
        if not (self.quantized and rerank):
            rerank_tile = 512
        merge = self.merge
        if self.fusion != "none":
            if expand != 1:
                raise ValueError(
                    f"fusion={self.fusion!r} supports expand=1 only "
                    f"(got expand={expand}): the fused kernels expand one "
                    "frontier node per hop — use fusion='none' for "
                    "multi-expansion")
            # the fused kernels carry their own min-extraction merge; the
            # merge field is dead there, so normalize it and let fused
            # specs that differ only in merge share one compiled plan
            merge = "topk"
        return ResolvedSearchSpec(
            k=k, beam_width=bw, max_iters=mi, expand=expand,
            quantized=bool(self.quantized), rerank=rerank,
            rerank_source=source,
            rerank_tile=rerank_tile, use_kernels=bool(self.use_kernels),
            merge=merge, traverse_deleted=bool(self.traverse_deleted),
            fusion=self.fusion, beam_schedule=schedule,
            telemetry=self.telemetry, filtered=filtered,
            filter_mode=filter_mode)

    # ------------------------------------------------------- serialization
    def to_dict(self) -> dict:
        return {"version": SPEC_VERSION, **asdict(self)}

    def to_json(self) -> str:
        return json.dumps(self.to_dict())

    @classmethod
    def from_dict(cls, d: dict) -> "SearchSpec":
        d = dict(d)
        version = d.pop("version", SPEC_VERSION)
        if version > SPEC_VERSION:
            raise ValueError(f"SearchSpec version {version} is newer than "
                             f"this build supports ({SPEC_VERSION})")
        known = {f.name for f in fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown SearchSpec fields: {sorted(unknown)}")
        if d.get("beam_schedule") is not None:
            # JSON round-trips tuples as lists; the spec form is a tuple
            # (hashable — it is part of the plan-cache key)
            d["beam_schedule"] = tuple(d["beam_schedule"])
        filt = d.get("filter")
        if filt is not None and not isinstance(filt, numbers.Integral):
            d["filter"] = tuple(filt)
        return cls(**d)

    @classmethod
    def from_json(cls, s: str) -> "SearchSpec":
        return cls.from_dict(json.loads(s))

    def with_(self, **kw) -> "SearchSpec":
        """Functional update (specs are frozen)."""
        return replace(self, **kw)

    def filter_bytes(self) -> np.ndarray | None:
        """The runtime operand for `filter`: a uint8[N_LABEL_BYTES] byte
        mask (or None when unfiltered). Fed to the compiled plan at call
        time — never part of the plan-cache key."""
        if self.filter is None:
            return None
        labels = (self.filter,) if isinstance(
            self.filter, numbers.Integral) else tuple(self.filter)
        return filter_to_bytes(labels)


@dataclass(frozen=True)
class ResolvedSearchSpec:
    """Fully-concrete, validated, normalized search configuration.

    Hashable and immutable: this is the static argument `core_search`
    jit-compiles against AND the plan-cache key — one object, one compiled
    executable per distinct configuration.

    `filtered` records filter PRESENCE only: the filter VALUE is a runtime
    operand (`SearchSpec.filter_bytes()`), deliberately stripped here so
    the plan cache never splits on it — every tenant/label value with the
    same presence + mode shares one compiled executable.
    """

    k: int
    beam_width: int
    max_iters: int
    expand: int
    quantized: bool
    rerank: bool
    rerank_source: str
    rerank_tile: int
    use_kernels: bool
    merge: str
    traverse_deleted: bool
    fusion: str
    beam_schedule: tuple | None
    telemetry: str
    filtered: bool
    filter_mode: str

    def to_spec(self) -> SearchSpec:
        """Back to declarative form. Lossy for filtered specs: the resolved
        form carries filter presence, not the value, so the round-trip
        spec is unfiltered."""
        d = asdict(self)
        d.pop("filtered")
        d["filter"] = None
        d["filter_mode"] = "traverse"
        return SearchSpec(**d)


class SearchResult(NamedTuple):
    """One served search batch.

    The serving layer's `SearchTicket` is an alias of this type — the
    core and the service stamp results identically.
    """

    ids: Any        # (Q, k) int32, -1 padded, never tombstoned
    dists: Any      # (Q, k) f32
    n_hops: Any     # (Q,) int32 — greedy-walk hops per query (the paper's
                    # per-query work metric; max over shards when sharded)
    generation: int  # index generation this batch was served at
    telemetry: Any = None  # SearchTelemetry iff spec.telemetry == "on"
                           # (summed over shards when sharded); else None
    estimated: bool = False  # True iff dists are RaBitQ ESTIMATOR values
                             # (rerank_source="none" code-only serving) —
                             # code-only lanes report honestly, never
                             # passing estimates off as exact distances


# ---------------------------------------------------------------------------
# Plan cache — shared executable cache for both backends
# ---------------------------------------------------------------------------

@dataclass
class CacheStats:
    """Counters for the plan cache (monotonic; `clear()` keeps them)."""

    hits: int = 0
    misses: int = 0
    traces: int = 0
    evictions: int = 0

    @property
    def hit_rate(self) -> float:
        """Hits per lookup; 0.0 on a never-used cache (no ZeroDivision)."""
        lookups = self.hits + self.misses
        return self.hits / lookups if lookups else 0.0

    def as_dict(self) -> dict:
        # hit_rate is a property, not in __dict__ — add it explicitly so
        # snapshots carry it, while delta()/snapshot() (which iterate
        # __dict__) keep seeing raw counters only
        return dict(self.__dict__, hit_rate=self.hit_rate)

    def delta(self, since: "CacheStats") -> dict:
        return {k: v - getattr(since, k) for k, v in self.__dict__.items()}

    def snapshot(self) -> "CacheStats":
        return CacheStats(**self.__dict__)


class PlanCache:
    """Executable cache keyed on (kind, resolved spec, shapes, liveness),
    LRU-bounded when given a capacity.

    Both index drivers own one. `get` returns the cached plan or builds
    it; builders bump `stats.traces` from INSIDE the traced function, so
    the counter reflects actual retraces (jit re-entry on a changed core
    structure counts; a cache hit on an unchanged key does not).

    `capacity=None` (the default) keeps every plan forever — fine for a
    benchmark sweep, unbounded growth under mixed-spec serving traffic
    (every (spec, bucket shape) pair is a new executable). With a
    capacity, `get` is LRU: a hit refreshes the key, an insert past
    capacity drops the least-recently-used plan and bumps
    `stats.evictions` (surfaced as `plan_cache.evictions` in the unified
    metrics snapshot). An evicted plan that comes back is a fresh
    miss + retrace — size the capacity above the working set (lanes x
    bucket ladder) so steady state stays at zero retraces.
    """

    def __init__(self, capacity: int | None = None) -> None:
        self._plans: OrderedDict = OrderedDict()
        self.stats = CacheStats()
        self.capacity = capacity

    @property
    def capacity(self) -> int | None:
        return self._capacity

    @capacity.setter
    def capacity(self, capacity: int | None) -> None:
        if capacity is not None and capacity < 1:
            raise ValueError(f"PlanCache capacity must be >= 1 or None, "
                             f"got {capacity}")
        self._capacity = capacity
        self._evict()

    def get(self, key, build):
        try:
            plan = self._plans[key]
            self._plans.move_to_end(key)      # LRU refresh
            self.stats.hits += 1
            return plan
        except KeyError:
            self.stats.misses += 1
            plan = self._plans[key] = build()
            self._evict()
            return plan

    def _evict(self) -> None:
        while (self._capacity is not None
               and len(self._plans) > self._capacity):
            self._plans.popitem(last=False)   # least recently used
            self.stats.evictions += 1

    def count_trace(self) -> None:
        """Call from inside a traced function body: runs once per trace."""
        self.stats.traces += 1

    def clear(self) -> None:
        """Drop compiled plans (index structure changed); stats persist."""
        self._plans.clear()

    def __len__(self) -> int:
        return len(self._plans)


# ---------------------------------------------------------------------------
# The compiled search session
# ---------------------------------------------------------------------------

class Searcher:
    """A compiled search session over one index driver.

    Created via `index.searcher(spec)`. The spec is resolved (validated,
    defaults filled) exactly once, at construction; each distinct query
    shape then compiles at most once into the index's shared `PlanCache`,
    so repeated searches — and every other Searcher or legacy-shim call
    with the same configuration — reuse the same executable.

    `search()` is the synchronous path. `submit()`/`drain()` expose the
    asynchronous dispatch underneath: `submit` enqueues device work and
    returns immediately (JAX dispatch is async), so the host can schedule
    the next batch while the device runs this one; `drain` blocks on the
    transfers and returns completed `SearchResult`s in submission order —
    the double-buffering hook the serving loop batches through.
    """

    def __init__(self, index, spec: SearchSpec):
        self.index = index
        self.spec = spec
        self.resolved = spec.resolve(index)
        # the filter VALUE, lowered once to its runtime byte-mask operand;
        # the resolved spec (and hence the plan) only knows filter PRESENCE
        self._filter_bytes = spec.filter_bytes()
        self._inflight: deque = deque()

    # ----------------------------------------------------------- execution
    def _dispatch(self, queries) -> SearchResult:
        """Prep, plan lookup and async enqueue of one batch. Records
        `session.dispatches` and `session.dispatch_s` (host seconds) for
        a dispatch that did not trace; one that did (`PlanCache.stats
        .traces` moved, which the service snapshot exports as
        `plan_cache.traces`) is left out, so compile time stays out of
        host time."""
        idx = self.index
        traces = idx.plans.stats.traces
        t0 = time.perf_counter()
        q = idx._prep_query(queries)
        generation = idx.generation
        plan = idx._search_plan(self.resolved, q.shape,
                                idx._filter_tombstones)
        out = plan(q, self._filter_bytes)
        if idx.plans.stats.traces == traces:
            _count(dispatches=1, dispatch_s=time.perf_counter() - t0)
        # plans return (ids, dists, n_hops) — plus a SearchTelemetry
        # fourth element iff the resolved spec has telemetry on
        ids, dists, n_hops = out[:3]
        tel = out[3] if len(out) > 3 else None
        return SearchResult(ids=ids, dists=dists, n_hops=n_hops,
                            generation=generation, telemetry=tel,
                            estimated=self.resolved.rerank_source == "none")

    def search(self, queries) -> SearchResult:
        """Synchronous search at the current snapshot generation."""
        return self._dispatch(queries)

    def submit(self, queries) -> int:
        """Enqueue a batch (async dispatch); returns the in-flight depth."""
        with obs_span("searcher.submit", pending=len(self._inflight)):
            self._inflight.append(self._dispatch(queries))
        return len(self._inflight)

    def drain(self, limit: int | None = None) -> list[SearchResult]:
        """Block on the oldest `limit` in-flight batches (None = all);
        results in submission order, host-resident (np arrays)."""
        out = []
        with obs_span("searcher.drain", pending=len(self._inflight)):
            while self._inflight and (limit is None or len(out) < limit):
                out.append(land(self._inflight.popleft()))
        return out

    @property
    def pending(self) -> int:
        return len(self._inflight)

    @property
    def cache_stats(self) -> CacheStats:
        """The index's shared plan-cache counters (hits/misses/traces)."""
        return self.index.plans.stats


# ---------------------------------------------------------------------------
# Session counters — recorded where batches are dispatched and landed
# ---------------------------------------------------------------------------

def _count(**deltas) -> None:
    """Add to the process-wide `session.<name>` counters."""
    reg = obs_registry()
    for name, delta in deltas.items():
        reg.counter(f"session.{name}").inc(delta)


def land(res: SearchResult) -> SearchResult:
    """Host-land one search batch: THE landing of a `SearchResult`
    (`Searcher.drain`, `AnnsService.search`, the scheduler's harvest).

    Blocks until the batch is ready on the device (span
    `searcher.wait`), then copies ids, dists, n_hops and telemetry to
    host numpy arrays (span `searcher.land`). Records into the
    process-wide registry (`obs.registry()`), per batch:

    session.batches   1
    session.rows      rows the loop ran (padding included)
    session.hops      sum of n_hops
    session.trips     max of n_hops: on the unfused while_loop at
                      expand=1 every row is active from trip 0 until it
                      converges, so this is the loop's trip count; on
                      other lanes, the slowest row's hop count
    session.wait_s    seconds blocked until the batch was ready
    session.land_s    seconds of the device-to-host copies (the wait
                      excluded)

    `hops / (rows * trips)` is the share of row-trips that expanded a
    node, the rest waiting on the batch's slowest rows; `wait_s` near
    zero per batch means the device finished before the host asked, so
    the host sets the pace (docs/observability.md).
    """
    tel = res.telemetry
    t0 = time.perf_counter()
    with obs_span("searcher.wait"):
        jax.block_until_ready((res.ids, res.dists, res.n_hops, tel))
    t1 = time.perf_counter()
    with obs_span("searcher.land"):
        if tel is not None:
            tel = type(tel)(*(np.asarray(t) for t in tel))
        out = res._replace(ids=np.asarray(res.ids),
                           dists=np.asarray(res.dists),
                           n_hops=np.asarray(res.n_hops), telemetry=tel)
    t2 = time.perf_counter()
    hops = out.n_hops
    _count(batches=1, rows=hops.shape[0], hops=int(hops.sum()),
           trips=int(hops.max()) if hops.size else 0,
           wait_s=t1 - t0, land_s=t2 - t1)
    return out


# ---------------------------------------------------------------------------
# Shared driver surface — ONE implementation for both drivers
# ---------------------------------------------------------------------------

class SearchSurface:
    """The spec-driven query surface both index drivers inherit.

    Hosts the ONE copy of session opening and recall measurement; the
    driver supplies the execution contract (`_prep_query`,
    `_filter_tombstones`, `generation`, `brute_force`, `plans`,
    `_search_plan`) documented in this module's header.
    """

    def searcher(self, spec: SearchSpec | None = None, **kw) -> Searcher:
        """Open a compiled search session (THE query surface).

        `spec` (or keyword fields building one; keywords alongside a spec
        derive `spec.with_(**kw)`) is resolved — defaults filled,
        validated against this index — exactly once; the session then
        compiles at most one executable per query shape into the index's
        shared plan cache. See docs/search_api.md.
        """
        spec = SearchSpec(**kw) if spec is None else \
            (spec.with_(**kw) if kw else spec)
        return Searcher(self, spec)

    def recall(self, queries, k: int = 10, *,
               beam_width: int | None = None, quantized: bool = False,
               use_kernels: bool = False, expand: int = 1,
               spec: SearchSpec | None = None) -> float:
        """Recall@k vs brute force (paper's Recall k@k) at the exact
        served configuration — delegates to `measure_recall`."""
        spec = spec or SearchSpec(k=k, beam_width=beam_width,
                                  quantized=quantized,
                                  use_kernels=use_kernels, expand=expand)
        return measure_recall(self, queries, spec)


def measure_recall(index, queries, spec: SearchSpec) -> float:
    """Recall@k vs the index's own brute force (paper's Recall k@k), at the
    EXACT configuration described by `spec`.

    This is the single recall implementation both drivers delegate to —
    and unlike the old per-driver copies it honors every spec field
    (`use_kernels`, `expand`, `merge`, ...), so recall is measured on the
    configuration actually being served, not a simplified twin of it.
    """
    gt, _ = index.brute_force(queries, spec.resolve(index).k)
    res = index.searcher(spec).search(queries)
    ids, gt = np.asarray(res.ids), np.asarray(gt)
    hits = (ids[:, :, None] == gt[:, None, :]) & (ids >= 0)[:, :, None]
    return float(np.mean(hits.any(axis=2).sum(axis=1) / gt.shape[1]))

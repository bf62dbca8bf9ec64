"""Tiered vector storage — device-resident packed codes, host-resident rows.

Every shard used to keep BOTH the f32 rows and the ~8x-smaller packed
RaBitQ codes device-resident, so the rows cap dataset size an order of
magnitude before the codes do — directly against the paper's
quantization-for-data-movement thesis. FusionANNS (CPU/GPU cooperative
billion-scale ANNS) and PilotANN (memory-bounded GPU staging) both show
the fix: traverse on device-resident compressed codes, keep the
full-precision rows in host memory, and fetch only the final frontier's
rows for the exact rerank (PAPERS.md).

This module is that storage tier. `VectorStore` manages where one
index's f32 rows live:

  * tier "device" — today's behavior: rows are core pytree leaves
    (`core.vectors` / `core.vec_sqnorm`), rerank runs in-graph,
    bit-identical to every pre-tiering build.
  * tier "host"   — rows live here as host numpy arrays;
    `core.vectors is None` (None is a structurally-empty pytree leaf,
    so compiled plans for the host tier NEVER take an f32-rows operand).
    Traversal runs entirely on the device-resident packed codes; only
    the final top-L frontier ids are gathered host-side (`gather`) and
    shipped back for the tiled exact rerank.

The matching search-time knob is `SearchSpec(rerank_source=...)`:
"device" reranks from core.vectors (requires tier "device"), "host"
reranks from this store (requires tier "host"), "none" serves estimator
distances only (works on either tier; results are flagged
`SearchResult.estimated`). Resolution/validation rules live in
`SearchSpec.resolve` — the ONE definition site — and `check_rows_tier`
is the index-aware half both `resolve(index)` and the serving layer
call.

Write-through contract: mutations (build/insert/consolidate/grow/
rebalance/re-augment) run the UNCHANGED core ops against staged rows —
`rows_staged(index)` attaches the host rows to the core, the op runs
exactly as on the device tier (so graph evolution is bit-identical),
and detach syncs the host tier from the result and strips the rows
back off the device. Capacity growth syncs for free (detach copies
whatever shape the op produced). See docs/tiered_storage.md.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "FetchStats", "VectorStore", "rows_resident", "strip_rows",
    "attach_rows", "rows_staged", "build_host_rerank_plan",
    "build_sharded_host_rerank_plan", "tier_memory_stats",
    "TIER_STAT_KEYS",
]

# The per-tier residence keys both drivers' memory_stats() report
# (satellite: device codes vs device rows vs host rows, plus the
# effective device-memory compression the eviction buys).
TIER_STAT_KEYS = ("rows_tier", "device_rows_bytes", "device_codes_bytes",
                  "host_rows_bytes", "device_compression_ratio")


def tier_memory_stats(core, store, *, capacity: int,
                      store_dims: int) -> dict:
    """Per-tier resident bytes for one core + its VectorStore.

    device_compression_ratio is the EFFECTIVE device-memory compression:
    what the vector payload (f32 rows + sqnorm + packed codes) would cost
    fully device-resident, over what is actually device-resident now —
    1.0 on the device tier, ~(rows+codes)/codes after eviction.
    """
    rows_full = float(capacity * (store_dims + 1) * 4)  # f32 rows + sqnorm
    device_rows = rows_full if rows_resident(core) else 0.0
    codes = 0.0
    if core.codes is not None:
        codes = float(core.codes.rows.nbytes)
    stats = {"rows_tier": store.tier,
             "device_rows_bytes": device_rows,
             "device_codes_bytes": codes,
             "host_rows_bytes": float(store.host_bytes)}
    device_vec = device_rows + codes
    if device_vec:
        stats["device_compression_ratio"] = (rows_full + codes) / device_vec
    return stats


# ---------------------------------------------------------------------------
# Fetch accounting
# ---------------------------------------------------------------------------

@dataclass
class FetchStats:
    """Monotonic host-fetch counters (one per VectorStore).

    n_fetches counts gather calls (one per served host-tier batch);
    n_rows/n_bytes count only VALID frontier entries actually shipped
    (padding/-1 sentinels cost nothing).
    """

    n_fetches: int = 0
    n_rows: int = 0
    n_bytes: int = 0
    total_s: float = 0.0
    last_s: float = 0.0
    last_rows: int = 0

    def record(self, rows: int, nbytes: int, dt: float) -> None:
        self.n_fetches += 1
        self.n_rows += int(rows)
        self.n_bytes += int(nbytes)
        self.total_s += float(dt)
        self.last_s = float(dt)
        self.last_rows = int(rows)

    def as_dict(self) -> dict:
        d = dict(self.__dict__)
        d["bytes_per_fetch"] = (self.n_bytes / self.n_fetches
                                if self.n_fetches else 0.0)
        return d


# ---------------------------------------------------------------------------
# Core row-residence helpers
# ---------------------------------------------------------------------------

def rows_resident(core) -> bool:
    """True when the core's f32 rows are device-resident pytree leaves."""
    return core.vectors is not None


def strip_rows(core):
    """Evicted form of a core: rows become None leaves, so the pytree
    STRUCTURE changes — host-tier compiled plans can never receive an
    f32-rows operand by construction."""
    return replace(core, vectors=None, vec_sqnorm=None)


def attach_rows(core, vectors, vec_sqnorm):
    """Inverse of `strip_rows` (staging / restore)."""
    return replace(core,
                   vectors=jnp.asarray(vectors, jnp.float32),
                   vec_sqnorm=jnp.asarray(vec_sqnorm, jnp.float32))


# ---------------------------------------------------------------------------
# The tier manager
# ---------------------------------------------------------------------------

class VectorStore:
    """Residence manager for one index's f32 rows (see module docstring).

    Owned by the index driver. On tier "device" it is pass-through state
    (no host copy, zero overhead). On tier "host" it holds the canonical
    f32 rows + cached |row|^2 as host numpy arrays, synced from every
    mutation through the staged write-through contract, and serves the
    rerank fetch path via `gather`.

    `fetch_hist` is an optional observability hook (the serving layer
    wires a `Histogram` onto it, like the scheduler's occupancy_hist):
    every gather observes its latency in microseconds.
    """

    def __init__(self, tier: str = "device") -> None:
        if tier not in ("device", "host"):
            raise ValueError(f"rows tier must be device|host, got {tier!r}")
        self.tier = tier
        self._vectors: np.ndarray | None = None
        self._sqnorm: np.ndarray | None = None
        self.fetch_stats = FetchStats()
        self.fetch_hist = None          # optional obs Histogram (us/gather)

    # ------------------------------------------------------------- residence
    def sync_from(self, core) -> None:
        """Write-through: refresh the host rows from a (staged) core."""
        self._vectors = np.asarray(core.vectors)
        self._sqnorm = np.asarray(core.vec_sqnorm)

    def evict(self, core):
        """device -> host: copy the rows here, return the stripped core."""
        if not rows_resident(core):
            raise ValueError("core rows are already evicted")
        self.sync_from(core)
        self.tier = "host"
        return strip_rows(core)

    def restore(self, core):
        """host -> device: re-attach the rows, drop the host copy."""
        if self.tier != "host":
            raise ValueError("rows are already device-resident")
        core = attach_rows(core, self._vectors, self._sqnorm)
        self.tier = "device"
        self._vectors = self._sqnorm = None
        return core

    def attach(self, core):
        """Staging attach (tier stays "host"; detach must follow)."""
        return attach_rows(core, self._vectors, self._sqnorm)

    def detach(self, core):
        """Staging detach: sync the host tier from the mutated core
        (write-through; capacity growth syncs for free) and strip."""
        self.sync_from(core)
        return strip_rows(core)

    # ----------------------------------------------------------- fetch path
    def gather(self, positions: np.ndarray
               ) -> tuple[np.ndarray, np.ndarray]:
        """Fetch frontier rows for the host-tier rerank.

        positions: int array (any shape) of STACKED row positions
        (shard*cap + local on the sharded driver, plain row ids on the
        single-device one); -1 marks invalid/padded frontier slots.
        Returns (rows f32[M, D], sqnorm f32[M]) with M = positions.size,
        in flat order — invalid slots come back as zero rows (the rerank
        masks them to +inf before they can matter). Records fetch
        latency/bytes in `fetch_stats`.
        """
        if self.tier != "host":
            raise ValueError("gather on a device-tier store")
        t0 = time.perf_counter()
        pos = np.asarray(positions).reshape(-1)
        valid = pos >= 0
        safe = np.where(valid, pos, 0)
        rows = self._vectors[safe]
        sq = self._sqnorm[safe]
        rows[~valid] = 0.0
        sq[~valid] = 0.0
        dt = time.perf_counter() - t0
        n_valid = int(valid.sum())
        nbytes = n_valid * (self._vectors.shape[1] + 1) * 4
        self.fetch_stats.record(n_valid, nbytes, dt)
        if self.fetch_hist is not None:
            self.fetch_hist.observe(dt * 1e6)
        return rows, sq

    # ------------------------------------------------------------ accounting
    @property
    def host_bytes(self) -> int:
        """Host-resident row bytes (0 on the device tier)."""
        if self._vectors is None:
            return 0
        return int(self._vectors.nbytes + self._sqnorm.nbytes)

    def stats(self) -> dict:
        return {"tier": self.tier, "host_rows_bytes": self.host_bytes,
                **{f"fetch_{k}": v
                   for k, v in self.fetch_stats.as_dict().items()}}


@contextmanager
def rows_staged(index):
    """Write-through staging for mutations on a host-tier index.

    Attaches the host rows to `index.core`, yields (the mutation runs
    the UNCHANGED core ops — graph evolution is bit-identical to the
    device tier), then syncs the host tier from the result and strips
    the rows back off. Re-entrant: a no-op when the rows are already
    resident (device tier, or an outer staging block).
    """
    store = getattr(index, "store", None)
    if (store is None or store.tier != "host"
            or rows_resident(index.core)):
        yield
        return
    put = getattr(index, "_device_put", lambda c: c)
    index.core = put(store.attach(index.core))
    try:
        yield
    finally:
        index.core = store.detach(index.core)


# ---------------------------------------------------------------------------
# Host-tier rerank plans (the pluggable rerank_frontier seam)
# ---------------------------------------------------------------------------
#
# Bit-identity trick: the device tier reranks with
#   rerank_frontier(core.vectors, core.vec_sqnorm, queries, frontier_ids)
# i.e. per candidate j of query q it gathers row frontier_ids[q, j] and
# scores it against query q. The host tier gathers those SAME rows into a
# (Q*L, D) table host-side, relabels candidate (q, j) to table row q*L+j
# (-1 stays -1), and calls the SAME rerank_frontier on the table: every
# per-candidate computation sees bit-identical inputs through an
# identical op sequence, so exact distances — and the stable sort + k
# slice that follow, keyed on those distances with the ORIGINAL ids as
# payload — are bitwise equal to the device tier on both the jnp and
# Pallas-kernel paths.

def build_host_rerank_plan(rspec, trace_counter=None):
    """Jitted single-device host-tier rerank: (queries (Q, D), frontier
    local ids (Q, L), gathered rows (Q*L, D), gathered sqnorm (Q*L,)) ->
    (ids (Q, k), dists (Q, k)) — the exact epilogue `core_search` runs
    in-graph on the device tier."""
    from repro.core.beam_search import rerank_frontier

    @jax.jit
    def jasper_rerank_host(queries, frontier_ids, table, table_sqnorm):
        if trace_counter is not None:
            trace_counter()
        q_n, l = frontier_ids.shape
        flat = jnp.arange(q_n * l, dtype=jnp.int32).reshape(q_n, l)
        local = jnp.where(frontier_ids >= 0, flat, -1)
        exact_d = rerank_frontier(table, table_sqnorm, queries, local,
                                  tile_q=rspec.rerank_tile,
                                  use_kernels=rspec.use_kernels)
        sd, si = jax.lax.sort((exact_d, frontier_ids), dimension=1,
                              is_stable=True, num_keys=1)
        si = jnp.where(jnp.isfinite(sd), si, -1)
        return si[:, :rspec.k], sd[:, :rspec.k]

    return jasper_rerank_host


def build_sharded_host_rerank_plan(rspec, *, axis_sizes: tuple,
                                   id_stride: int, trace_counter=None):
    """Jitted sharded host-tier rerank + merge.

    Inputs: queries (Q, D), per-shard stacked frontier local ids
    (S, Q, L), gathered rows (S*Q*L, D), gathered sqnorm (S*Q*L,),
    per-shard n_hops (S, Q) — S stacked in `_shard_index` row-major
    device order (the order the traversal's leading-axis out_spec
    produces). Returns (GLOBAL ids (Q, k), dists (Q, k), n_hops (Q,)).

    Each (shard, query) row reranks exactly like the device tier's
    shard-local rerank (see `build_host_rerank_plan`), then the k-wide
    per-shard results merge through the SAME candidate ordering and
    `lax.top_k` reduction `merge_topk` runs per row axis on device —
    axis by axis, in `row_axes` order, (axis index)-major candidate
    layout — so merged ids/dists are bitwise equal to the device tier.

    axis_sizes: per-row-axis shard counts, in row_axes order (their
    product is S).
    """
    from repro.core.beam_search import rerank_frontier

    @jax.jit
    def jasper_rerank_host(queries, frontier_ids, table, table_sqnorm,
                           n_hops):
        if trace_counter is not None:
            trace_counter()
        s, q_n, l = frontier_ids.shape
        k = rspec.k
        flat_ids = frontier_ids.reshape(s * q_n, l)
        flat = jnp.arange(s * q_n * l, dtype=jnp.int32).reshape(s * q_n, l)
        local = jnp.where(flat_ids >= 0, flat, -1)
        q_rep = jnp.tile(queries, (s, 1))
        exact_d = rerank_frontier(table, table_sqnorm, q_rep, local,
                                  tile_q=rspec.rerank_tile,
                                  use_kernels=rspec.use_kernels)
        # per-(shard, query) sort + k-slice: identical to the device
        # tier's shard-local epilogue (stable, keys = dists only, LOCAL
        # ids as payload; global conversion happens after, as on device)
        sd, si = jax.lax.sort((exact_d, flat_ids), dimension=1,
                              is_stable=True, num_keys=1)
        si = jnp.where(jnp.isfinite(sd), si, -1)
        sd, si = sd[:, :k], si[:, :k]
        shard = jnp.arange(s, dtype=jnp.int32)[:, None, None]
        gids = si.reshape(s, q_n, k)
        gids = jnp.where(gids >= 0, gids + shard * id_stride, -1)
        dists = sd.reshape(s, q_n, k)
        # merge_topk emulation: reduce one row axis at a time, leading
        # shard axis first, with the device's (axis index)-major
        # candidate order per query
        d = dists.reshape(tuple(axis_sizes) + (q_n, k))
        i = gids.reshape(tuple(axis_sizes) + (q_n, k))
        for _ in axis_sizes:
            d = jnp.moveaxis(d, 0, -2)
            i = jnp.moveaxis(i, 0, -2)
            d = d.reshape(d.shape[:-2] + (-1,))
            i = i.reshape(i.shape[:-2] + (-1,))
            neg, pos = jax.lax.top_k(-d, k)
            d = -neg
            i = jnp.take_along_axis(i, pos, axis=-1)
        return i, d, jnp.max(n_hops, axis=0)

    return jasper_rerank_host

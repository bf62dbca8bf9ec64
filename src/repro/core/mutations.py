"""Mutation subsystem: tombstone deletes, slot reuse, consolidation, growth.

The paper's "built for change" pillar needs more than streaming insertion:
an evolving dataset deletes as often as it inserts. This module supplies the
missing half as a capacity-allocated state machine over fixed-shape device
arrays (the same discipline as `VamanaGraph`):

  EMPTY ----insert----> LIVE ----delete----> DELETED ----consolidate----> FREE
                          ^                  (tombstoned; data + edges      |
                          |                   intact, traversable but       |
                          +---insert reuses---   never returnable)  <------+

  * ``tombstone_bits`` is a PACKED bitmap (uint8[ceil(capacity/8)], one bit
    per row, little-endian within each byte). A row's bit is set from the
    moment it is deleted until its slot is reused — so "may this id be
    returned?" is always a single bit test, and per-shard validity for the
    sharded-search roadmap item is one bitmap per shard.
  * ``delete_rows`` is a batched jit'd scatter: tombstone the rows, bump the
    generation counter. O(capacity/8) bytes touched, no graph work.
  * ``consolidate`` is the batched repair pass (FreshDiskANN's delete
    consolidation, accelerator-shaped): every live vertex with an edge into
    a deleted vertex re-runs alpha-RobustPrune over (its live neighbors ∪
    the live neighbors of its deleted neighbors), deleted rows' adjacency is
    cleared, their slots join the free pool, and the medoid is recomputed
    over live rows. All repair work is fixed-shape and chunked.
  * ``grow_*`` helpers implement capacity doubling by pure copy-extension:
    packed RaBitQ codes, vec_sqnorm, adjacency, and the bitmap all pad with
    inert values — nothing is re-encoded and no live bytes move.

Searches never return tombstoned ids (`beam_search` filters its final
frontier through the bitmap); whether deleted nodes remain *traversable*
during the walk is the caller's choice (`traverse_deleted`) — keeping them
walkable preserves graph connectivity between consolidations, masking them
in the scoring epilogue is cheaper once the graph has been repaired.

The same machinery generalizes from one liveness bit to a per-row LABEL
BITSET (``labels``: uint8[capacity, N_LABEL_BYTES], 32 label bits): a row
matches a filter when its bitset intersects the filter's byte mask, which
is one extra byte-row gather + AND per candidate in the exact epilogues
where liveness already tests its bit (`label_match_gather` mirrors
`bitmap_gather`). Labels are set at insert, cleared on slot reuse, and
preserved bit-identically through delete/consolidate/grow — filtered and
multi-tenant search (docs/filtered_search.md) ride entirely on this plane.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.medoid import compute_medoid
from repro.core.robust_prune import robust_prune_batch
from repro.core.vamana import VamanaGraph

Array = jax.Array

_INF = float("inf")


# ---------------------------------------------------------------------------
# Packed row bitmap (1 bit per capacity row, little-endian within each byte)
# ---------------------------------------------------------------------------

def bitmap_bytes(capacity: int) -> int:
    return (capacity + 7) // 8


def pack_bitmap(dense: Array) -> Array:
    """bool[N] -> uint8[ceil(N/8)] (bit i of byte j = row 8*j + i)."""
    n = dense.shape[0]
    pad = (-n) % 8
    d = jnp.pad(dense.astype(jnp.uint8), (0, pad))
    d = d.reshape(-1, 8)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    return jnp.sum(d << shifts, axis=-1).astype(jnp.uint8)


def unpack_bitmap(bits: Array, n: int) -> Array:
    """uint8[ceil(N/8)] -> bool[N]."""
    b = bits.astype(jnp.uint8)[:, None]
    shifts = jnp.arange(8, dtype=jnp.uint8)
    dense = ((b >> shifts) & 1).reshape(-1)[:n]
    return dense.astype(jnp.bool_)


def bitmap_gather(bits: Array, ids: Array) -> Array:
    """Per-id bit test: int32[...] -> bool[...] (negative ids -> False).

    One byte gather + shift/mask per id — the hot-path form used by the
    search epilogues (the whole bitmap never unpacks on the search path).
    """
    safe = jnp.maximum(ids, 0)
    byte = bits[safe >> 3].astype(jnp.int32)
    bit = (byte >> (safe & 7)) & 1
    return (bit == 1) & (ids >= 0)


# ---------------------------------------------------------------------------
# Per-row label bitsets (filtered / multi-tenant search)
# ---------------------------------------------------------------------------

# Width of the label plane: 8 * N_LABEL_BYTES label bits per row. 32 bits
# covers tenant namespaces and coarse predicates; widening is a single
# constant change (the plane is capacity-major, so it grows like any row).
N_LABEL_BYTES = 4
N_LABELS = 8 * N_LABEL_BYTES


def _check_label(label: int) -> int:
    label = int(label)
    if not 0 <= label < N_LABELS:
        raise ValueError(f"label id {label} out of range [0, {N_LABELS})")
    return label


def filter_to_bytes(label_ids) -> np.ndarray:
    """Label-id set -> uint8[N_LABEL_BYTES] byte mask (the runtime search
    operand: a row matches when its label row ANDs nonzero against it)."""
    fb = np.zeros((N_LABEL_BYTES,), np.uint8)
    for label in label_ids:
        label = _check_label(label)
        fb[label >> 3] |= np.uint8(1 << (label & 7))
    return fb


def pack_label_rows(labels, n_rows: int) -> np.ndarray:
    """Per-row label sets -> uint8[n_rows, N_LABEL_BYTES] bitset rows.

    `labels` may be None (all-zero rows: the row matches no filter), a
    scalar label id (broadcast to every row), a 1-D int sequence (one
    label per row), or a sequence of per-row label-id iterables.
    """
    out = np.zeros((n_rows, N_LABEL_BYTES), np.uint8)
    if labels is None:
        return out
    if np.isscalar(labels) or getattr(labels, "ndim", None) == 0:
        labels = [labels] * n_rows
    rows = list(labels)
    if len(rows) != n_rows:
        raise ValueError(f"labels: got {len(rows)} rows, want {n_rows}")
    for i, row in enumerate(rows):
        ids = (row,) if np.isscalar(row) else tuple(row)
        for label in ids:
            label = _check_label(label)
            out[i, label >> 3] |= np.uint8(1 << (label & 7))
    return out


def label_match_gather(labels: Array, filter_bytes: Array, ids: Array
                       ) -> Array:
    """Per-id filter test: int32[...] -> bool[...] — True iff the row's
    label bitset intersects `filter_bytes` (negative ids -> False).

    The label twin of `bitmap_gather`: one (N_LABEL_BYTES,)-row gather +
    AND/any per id, fused into the same epilogues liveness uses — the
    dense label plane never unpacks on the search path.
    """
    safe = jnp.maximum(ids, 0)
    rows = labels[safe].astype(jnp.uint8)
    hit = jnp.any((rows & filter_bytes.astype(jnp.uint8)) != 0, axis=-1)
    return hit & (ids >= 0)


# ---------------------------------------------------------------------------
# Mutation state
# ---------------------------------------------------------------------------

@partial(jax.tree_util.register_dataclass,
         data_fields=("tombstone_bits", "labels", "free_ids", "n_free",
                      "n_deleted", "generation"),
         meta_fields=())
@dataclass(frozen=True)
class MutationState:
    """Delete/reuse bookkeeping for one capacity-allocated index.

    tombstone_bits: uint8[ceil(cap/8)]  1 = dead (DELETED or FREE)
    labels:         uint8[cap, NB]      per-row label bitsets (filtered /
                                        multi-tenant search; all-zero rows
                                        match no filter)
    free_ids:       int32[cap]          reusable slots, ascending, -1 padded
    n_free:         int32 scalar        live prefix length of free_ids
    n_deleted:      int32 scalar        tombstoned-but-not-yet-consolidated
    generation:     int32 scalar        bumped by every mutation — searches
                                        stamp results with it so a serving
                                        layer can reason about snapshots
    """

    tombstone_bits: Array
    labels: Array
    free_ids: Array
    n_free: Array
    n_deleted: Array
    generation: Array

    @property
    def capacity(self) -> int:
        return self.free_ids.shape[0]


def init_mutation_state(capacity: int) -> MutationState:
    return MutationState(
        tombstone_bits=jnp.zeros((bitmap_bytes(capacity),), jnp.uint8),
        labels=jnp.zeros((capacity, N_LABEL_BYTES), jnp.uint8),
        free_ids=jnp.full((capacity,), -1, jnp.int32),
        n_free=jnp.int32(0),
        n_deleted=jnp.int32(0),
        generation=jnp.int32(0),
    )


# ---------------------------------------------------------------------------
# Batched delete
# ---------------------------------------------------------------------------

@jax.jit
def delete_rows(state: MutationState, ids: Array, n_valid: Array
                ) -> tuple[MutationState, Array]:
    """Tombstone `ids` (int32[B]); duplicate / out-of-range / already-dead
    entries are ignored. Returns (state', number of rows newly deleted).

    Pure metadata: no vector, code, or adjacency bytes move — that work is
    deferred to `consolidate`, which amortizes it over a batch of deletes.
    """
    cap = state.free_ids.shape[0]
    dense = unpack_bitmap(state.tombstone_bits, cap)
    in_range = (ids >= 0) & (ids < n_valid)
    hit = jnp.zeros((cap,), jnp.bool_).at[
        jnp.where(in_range, ids, cap)].set(True, mode="drop")
    newly = hit & ~dense
    n_new = jnp.sum(newly).astype(jnp.int32)
    return MutationState(
        tombstone_bits=pack_bitmap(dense | newly),
        labels=state.labels,        # deletes keep label rows (cleared on reuse)
        free_ids=state.free_ids,
        n_free=state.n_free,
        n_deleted=state.n_deleted + n_new,
        generation=state.generation + 1,
    ), n_new


# ---------------------------------------------------------------------------
# Consolidation (batched tombstone-neighborhood repair)
# ---------------------------------------------------------------------------

@jax.jit
def _touched_mask(adjacency: Array, deleted_now: Array, live: Array) -> Array:
    """Live rows with at least one out-edge into a freshly deleted row."""
    nbr_dead = (adjacency >= 0) & deleted_now[jnp.maximum(adjacency, 0)]
    return jnp.any(nbr_dead, axis=1) & live


@partial(jax.jit, static_argnames=("degree_bound", "alpha", "chunk"))
def _repair_rows(vectors: Array, adjacency: Array, deleted_dense: Array,
                 live: Array, touched: Array, n_valid: Array, *,
                 degree_bound: int, alpha: float, chunk: int) -> Array:
    """Re-prune one slab of touched rows. touched: int32[T] (-1 padded).

    Candidates for vertex u = (u's live neighbors) ∪ (live neighbors of
    every deleted neighbor of u) — the FreshDiskANN repair rule. Deleted
    candidates are masked through `live` inside RobustPrune, so repaired
    rows never point at tombstoned vertices.
    """
    from repro.core.construction import _adjacency_distances  # lazy: no cycle

    r = degree_bound
    rows = adjacency[jnp.maximum(touched, 0)]                     # (T, R)
    rows = jnp.where((touched >= 0)[:, None], rows, -1)
    dead = (rows >= 0) & deleted_dense[jnp.maximum(rows, 0)]      # (T, R)
    own = jnp.where(dead, -1, rows)
    # neighbors-of-deleted-neighbors: (T, R, R) -> (T, R*R)
    repl = adjacency[jnp.maximum(jnp.where(dead, rows, 0), 0)]
    repl = jnp.where(dead[:, :, None], repl, -1)
    repl = repl.reshape(rows.shape[0], r * r)
    cand = jnp.concatenate([own, repl], axis=1)                   # (T, R+R*R)
    cand_d = _adjacency_distances(vectors, touched, cand, chunk)
    res = robust_prune_batch(vectors, touched, cand, cand_d, n_valid,
                             degree_bound=r, alpha=alpha, chunk_size=chunk,
                             live=live)
    return res.selected_ids


def consolidate(vectors: Array, graph: VamanaGraph, state: MutationState, *,
                params, repair_slab: int = 1024, refine: bool = True,
                vec_sqnorm: Array | None = None
                ) -> tuple[VamanaGraph, MutationState, dict]:
    """Repair the graph around tombstoned rows and free their slots.

    Host-side driver (like build/insert): the touched set is data-dependent,
    so its ids are pulled to host and repaired in fixed-shape batches.
    Returns (graph', state', stats). No-op when nothing is tombstoned.

    Two repair modes (A/B'd in benchmarks/updates.py):

    refine=True (default) — RE-LINK: every touched row re-runs the
    insertion pipeline against the tombstoned graph (beam search traverses
    THROUGH deleted rows — connectivity — while the live mask keeps them
    out of every pruned edge list), via `batch_insert_at(already_inserted=
    True)`, in slabs of at most `construction.MAX_BATCH` rows, each slab
    against the graph the slabs before it left. Globally good candidates,
    post-churn recall at fresh-build level, and ~2x cheaper than a one-hop
    repair + refine stack.

    refine=False — LOCAL one-hop repair (FreshDiskANN's rule): each touched
    row re-prunes over (its live neighbors ∪ its deleted neighbors' live
    neighbors). Cheapest, recall within a couple points; the right mode
    when consolidation must run inside a tight serving budget.
    """
    cap = graph.capacity
    r = params.degree_bound
    n_valid = graph.n_valid
    dense = unpack_bitmap(state.tombstone_bits, cap)
    row = jnp.arange(cap, dtype=jnp.int32) < n_valid
    free_dense = jnp.zeros((cap,), jnp.bool_).at[
        jnp.where(jnp.arange(cap) < state.n_free, state.free_ids, cap)
    ].set(True, mode="drop")
    deleted_now = dense & ~free_dense & row
    del_ids = np.where(np.asarray(deleted_now))[0]
    if del_ids.size == 0:
        return graph, state, {"n_freed": 0, "n_repaired": 0}

    live = row & ~dense
    touched = np.where(np.asarray(
        _touched_mask(graph.adjacency, deleted_now, live)))[0]

    adj = graph.adjacency
    if refine and touched.size:
        from repro.core.construction import (  # lazy: no cycle
            MAX_BATCH, batch_insert_at)
        # re-link in slabs of at most MAX_BATCH rows (one slab over a
        # large graph's touched set would not fit device memory). Each slab
        # pads to a power-of-two rung by repeating a real id — a duplicate
        # re-link is idempotent, while -1 padding would corrupt the
        # adjacency scatter; past one slab every slab takes the full rung,
        # so a consolidation compiles one executable
        rung = (MAX_BATCH if touched.size > MAX_BATCH
                else 1 << max(0, int(touched.size - 1).bit_length()))
        for s in range(0, touched.size, MAX_BATCH):
            slab = touched[s:s + MAX_BATCH]
            t_pad = np.concatenate([slab, np.full((rung - slab.size,),
                                                  slab[0], np.int64)])
            graph = batch_insert_at(vectors, graph,
                                    jnp.asarray(t_pad, jnp.int32),
                                    params=params, already_inserted=True,
                                    vec_sqnorm=vec_sqnorm,
                                    tombstone_bits=state.tombstone_bits)
        adj = graph.adjacency
    elif touched.size:
        # local repair in fixed-shape slabs; chunk bounds the
        # (chunk, R+R*R, D) gathers
        chunk = max(16, min(int(params.prune_chunk), 4096 // max(1, r)))
        for s in range(0, touched.size, repair_slab):
            slab = touched[s:s + repair_slab]
            pad = (-slab.size) % chunk
            slab_ids = jnp.asarray(
                np.pad(slab, (0, pad), constant_values=-1), jnp.int32)
            new_rows = _repair_rows(vectors, adj, deleted_now, live, slab_ids,
                                    n_valid, degree_bound=r,
                                    alpha=params.alpha, chunk=chunk)
            adj = adj.at[jnp.where(slab_ids >= 0, slab_ids, cap)].set(
                new_rows, mode="drop")

    # deleted rows lose their out-edges; nothing points at them any more
    adj = jnp.where(deleted_now[:, None], -1, adj)
    medoid = compute_medoid(vectors, live)
    graph = VamanaGraph(adjacency=adj, n_valid=n_valid, medoid=medoid)

    old_free = np.asarray(state.free_ids)[:int(state.n_free)]
    new_free = np.sort(np.concatenate([old_free, del_ids])).astype(np.int32)
    free_ids = np.full((cap,), -1, np.int32)
    free_ids[:new_free.size] = new_free
    state = MutationState(
        tombstone_bits=state.tombstone_bits,   # bits stay set until reuse
        labels=state.labels,                   # live rows' labels untouched
        free_ids=jnp.asarray(free_ids),
        n_free=jnp.int32(new_free.size),
        n_deleted=jnp.int32(0),
        generation=state.generation + 1,
    )
    jax.block_until_ready(graph.adjacency)     # storage semantics
    return graph, state, {"n_freed": int(del_ids.size),
                          "n_repaired": int(touched.size)}


# ---------------------------------------------------------------------------
# Slot allocation (insert-side reuse) and capacity growth
# ---------------------------------------------------------------------------

def take_free_slots(state: MutationState, want: int
                    ) -> tuple[MutationState, np.ndarray]:
    """Pop up to `want` reusable slots (ascending ids — deterministic).

    Host-side (allocation decides array *shapes* downstream). The popped
    slots' tombstone bits are cleared: they are LIVE again the moment the
    caller writes their rows.
    """
    n_free = int(state.n_free)
    take = min(want, n_free)
    if take == 0:
        return state, np.empty((0,), np.int32)
    free = np.asarray(state.free_ids)
    taken, rest = free[:take], free[take:n_free]
    cap = state.capacity
    free_ids = np.full((cap,), -1, np.int32)
    free_ids[:rest.size] = rest
    dense = unpack_bitmap(state.tombstone_bits, cap)
    dense = dense.at[jnp.asarray(taken)].set(False)
    state = MutationState(
        tombstone_bits=pack_bitmap(dense),
        # reused slots start label-free: the NEW row's labels are whatever
        # the caller writes, never the dead predecessor's
        labels=state.labels.at[jnp.asarray(taken)].set(0),
        free_ids=jnp.asarray(free_ids),
        n_free=jnp.int32(rest.size),
        n_deleted=state.n_deleted,
        generation=state.generation + 1,
    )
    return state, taken.astype(np.int32)


def grow_state(state: MutationState, new_capacity: int) -> MutationState:
    """Copy-extend the mutation state to a larger capacity."""
    old_cap = state.capacity
    if new_capacity < old_cap:
        raise ValueError(f"cannot shrink {old_cap} -> {new_capacity}")
    bits = jnp.zeros((bitmap_bytes(new_capacity),), jnp.uint8)
    bits = bits.at[:state.tombstone_bits.shape[0]].set(state.tombstone_bits)
    free = jnp.full((new_capacity,), -1, jnp.int32)
    free = free.at[:old_cap].set(state.free_ids)
    return MutationState(tombstone_bits=bits,
                         labels=grow_rows(state.labels, new_capacity, 0),
                         free_ids=free,
                         n_free=state.n_free, n_deleted=state.n_deleted,
                         generation=state.generation + 1)


def grow_rows(arr: Array, new_capacity: int, fill) -> Array:
    """Copy-extend a capacity-major array: rows [cap:new_cap) = fill.

    This is the whole "grow re-encodes nothing" story: packed RaBitQ codes,
    vec_sqnorm, and adjacency are all capacity-major, so growth is one pad
    per buffer and the resident prefix is byte-identical.
    """
    old = arr.shape[0]
    if new_capacity < old:
        raise ValueError(f"cannot shrink {old} -> {new_capacity}")
    widths = [(0, new_capacity - old)] + [(0, 0)] * (arr.ndim - 1)
    return jnp.pad(arr, widths, constant_values=fill)

"""Batched greedy beam search (paper Alg. 1 + §4.1/4.2), TPU-adapted.

GPU Jasper assigns one CUDA block per query and keeps the frontier in shared
memory. The TPU analogue (DESIGN.md §2): ALL queries advance in lockstep
under one `lax.while_loop`; per-query state is a set of small fixed-shape
arrays that XLA keeps in VMEM/registers. "Occupancy" becomes the query batch
dimension — the paper's observation that small beams + many concurrent
queries win on low-dim data maps to (small L, large Q).

Faithful simplifications carried over from the paper (§4.2):
  * no visited hash table — the frontier's own visited bit is the only
    dedup state (paper found the lossy table unnecessary on GPU);
  * no deferred merge — every step does a full sort-merge (deterministic);
  * squared distances (no sqrt).

The distance computation is pluggable via `score_fn` so the exact path, the
RaBitQ estimator path, and the Pallas kernel path share one search loop —
this is the "composable module" form of the paper's fused search kernel.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.rabitq import RaBitQCodes, RaBitQQuery, rabitq_estimate
from repro.core.vamana import VamanaGraph

Array = jax.Array
ScoreFn = Callable[[Array], Array]  # (Q, K) int32 ids -> (Q, K) f32 dists

# python scalar, not a device array: module-level jnp constants become
# leaked tracers if the module is first imported inside an active trace
_INF = float("inf")


class SearchTelemetry(NamedTuple):
    """Per-search counters, identical semantics across the unfused loop,
    the ref oracle, and both fused kernels (the ref oracle's values are
    the bit-exact contract — see tests/test_obs.py).

    Per hop, over the expanded nodes' neighbor candidates:
      scored     — in-range, not already in the frontier, not masked
      masked     — in-range, not duplicate, but tombstone/filter-masked
                   (exclude-mode only; always 0 when traversing deleted)
      duplicates — in-range but already present in the frontier
      occupancy  — live frontier slots (id >= 0) AFTER the hop's merge +
                   schedule-narrow, recorded only for hops the row
                   actually expanded (0 otherwise — converged rows stop
                   logging, so values are independent of how long the
                   rest of the batch keeps iterating)
    """

    scored: Array      # (Q,) int32, summed over hops
    masked: Array      # (Q,) int32, summed over hops
    duplicates: Array  # (Q,) int32, summed over hops
    occupancy: Array   # (Q, max_iters) int32, per hop


class BeamSearchResult(NamedTuple):
    frontier_ids: Array     # (Q, L) int32, sorted by distance, -1 padded
    frontier_dists: Array   # (Q, L) f32, +inf padded
    visited_ids: Array      # (Q, max_iters) int32 expansion log, -1 padded
    visited_dists: Array    # (Q, max_iters) f32 distances of expanded nodes
    n_hops: Array           # (Q,) int32 number of expansions performed
    telemetry: SearchTelemetry | None = None  # iff requested


def make_exact_scorer(vectors: Array, queries: Array, n_valid: Array,
                      vec_sqnorm: Array | None = None) -> ScoreFn:
    """Exact squared-L2 scorer over gathered candidate rows.

    The gather + batched dot is the jnp reference path; kernels/distance
    provides the Pallas drop-in with fused HBM->VMEM tile loads.
    """
    v = vectors
    q = queries.astype(jnp.float32)
    q_sq = jnp.sum(q * q, axis=-1)
    if vec_sqnorm is None:
        vec_sqnorm = jnp.sum(v.astype(jnp.float32) * v.astype(jnp.float32), axis=-1)

    def score(ids: Array) -> Array:
        safe = jnp.maximum(ids, 0)
        cand = v[safe].astype(jnp.float32)                    # (Q, K, D)
        dot = jnp.einsum("qkd,qd->qk", cand, q)
        d = q_sq[:, None] - 2.0 * dot + vec_sqnorm[safe]
        return jnp.maximum(d, 0.0)

    return score


def make_rabitq_scorer(codes: RaBitQCodes, query: RaBitQQuery) -> ScoreFn:
    """RaBitQ estimated-distance scorer (paper §5.1)."""

    def score(ids: Array) -> Array:
        return rabitq_estimate(codes, query, ids)

    return score


MERGE_STRATEGIES = ("topk", "sort", "kernel")


def merge_frontier_sort(f_ids, f_dists, f_vis, c_ids, c_dists, beam_width):
    """Reference merge: full sort over the L + E*R concatenation.

    Single stable multi-operand sort — the TPU-native replacement for the
    paper's in-shared-memory insertion (XLA lowers to a fused sort). Kept
    as the reference/fallback. Its float comparison ties -0.0 with 0.0
    (position order), where "topk" puts -0.0 first; apart from signed
    zeros it selects the same frontier.
    """
    all_d = jnp.concatenate([f_dists, c_dists], axis=1)
    all_i = jnp.concatenate([f_ids, c_ids], axis=1)
    all_v = jnp.concatenate([f_vis, jnp.zeros_like(c_ids, dtype=jnp.bool_)], axis=1)
    sd, si, sv = jax.lax.sort((all_d, all_i, all_v), dimension=1,
                              is_stable=True, num_keys=1)
    return si[:, :beam_width], sd[:, :beam_width], sv[:, :beam_width]


def _f32_total_key(x: Array) -> Array:
    """int32 whose signed order is x's IEEE total order (-0.0 before 0.0,
    as lax.top_k orders floats). The map is its own inverse."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def merge_frontier_topk(f_ids, f_dists, f_vis, c_ids, c_dists, beam_width):
    """Top-L merge with ids and visited bits carried inside the selection.

    The frontier's selection is lax.top_k's: the L smallest distances in
    float total order, ties toward the lower position (the frontier
    half). One stable two-operand sort reproduces it exactly: the key is
    the distance's total-order int32 (from which the distance comes back
    bit for bit), the payload the id with the visited bit in its lowest
    bit (ids lie in [-1, 2**30), the bound the multi-expand dedup's
    sentinel already assumes). No gather follows the selection.

    On a TPU v5e at Q = 1000, L = 64 a merge took 1,335 us as top_k plus
    two take_along_axis gathers (element-wise gathers on the lane axis),
    27 us as this sort, and 119 us as top_k plus a one-hot select; at
    E*R = 256, 1,414, 90 and 150 us.
    """
    all_d = jnp.concatenate([f_dists, c_dists], axis=1)
    all_i = jnp.concatenate([f_ids, c_ids], axis=1)
    all_v = jnp.concatenate([f_vis, jnp.zeros_like(c_ids, dtype=jnp.bool_)], axis=1)
    key, payload = jax.lax.sort(
        (_f32_total_key(all_d), (all_i << 1) | all_v.astype(jnp.int32)),
        dimension=1, is_stable=True, num_keys=1)
    key, payload = key[:, :beam_width], payload[:, :beam_width]
    dists = jax.lax.bitcast_convert_type(_f32_total_key(key), jnp.float32)
    return payload >> 1, dists, (payload & 1).astype(jnp.bool_)


def merge_frontier_kernel(f_ids, f_dists, f_vis, c_ids, c_dists, beam_width):
    """Partial top-L merge via the Pallas min-extraction kernel.

    Reuses kernels/topk: L sequential argmin+mask passes over the VMEM
    tile, fully vectorized across the query block. Positions come back
    from the kernel; ids + visited ride along through one gather.
    """
    from repro.kernels.topk.ops import topk

    all_d = jnp.concatenate([f_dists, c_dists], axis=1)
    all_i = jnp.concatenate([f_ids, c_ids], axis=1)
    all_v = jnp.concatenate([f_vis, jnp.zeros_like(c_ids, dtype=jnp.bool_)], axis=1)
    pos_in = jax.lax.broadcasted_iota(jnp.int32, all_d.shape, 1)
    sd, pos = topk(all_d, pos_in, beam_width)
    return (jnp.take_along_axis(all_i, pos, axis=1), sd,
            jnp.take_along_axis(all_v, pos, axis=1))


MERGE_FNS = {
    "sort": merge_frontier_sort,
    "topk": merge_frontier_topk,
    "kernel": merge_frontier_kernel,
}


def expand_schedule(beam_schedule, beam_width: int, max_iters: int
                    ) -> tuple[int, ...]:
    """Static per-hop frontier widths, one entry per iteration.

    Hop t runs at width schedule[min(t, len-1)] — a short schedule's last
    entry extends to the full budget. None means constant beam_width.
    This is THE schedule semantics; the jnp loop, the fused kernels, and
    the ref oracle all expand through here.
    """
    if beam_schedule is None:
        return (beam_width,) * max_iters
    sched = tuple(int(w) for w in beam_schedule)
    return tuple(sched[min(t, len(sched) - 1)] for t in range(max_iters))


def apply_beam_width(f_ids, f_dists, f_vis, w):
    """Narrow a merged frontier to `w` live slots (positions >= w become
    empty: id -1, dist +inf, unvisited). `w` may be traced (a per-hop
    schedule entry); with w == L this is an exact no-op — schedule
    (B,...,B) is bitwise identical to a constant beam."""
    keep = jnp.arange(f_ids.shape[1])[None, :] < w
    return (jnp.where(keep, f_ids, -1),
            jnp.where(keep, f_dists, _INF),
            jnp.where(keep, f_vis, False))


def finalize_frontier(f_ids, f_dists, tombstone_bits, labels=None,
                      filter_bytes=None):
    """Shared search epilogue: drop tombstoned and out-of-filter entries
    to the (+inf, -1) tail and mask unconverged +inf padding back to -1
    ids. Every search path — fused or not — finishes through this one
    function, so the 'never return a deleted id' invariant (and its label
    twin: 'never return an out-of-filter id', in BOTH filter modes) has a
    single definition."""
    with jax.named_scope("search.finalize"):
        drop = None
        if tombstone_bits is not None:
            from repro.core.mutations import bitmap_gather  # no cycle
            drop = bitmap_gather(tombstone_bits, f_ids)
        if labels is not None:
            from repro.core.mutations import label_match_gather
            miss = (~label_match_gather(labels, filter_bytes, f_ids)
                    & (f_ids >= 0))
            drop = miss if drop is None else (drop | miss)
        if drop is not None:
            f_dists = jnp.where(drop, _INF, f_dists)
            f_dists, f_ids = jax.lax.sort((f_dists, f_ids), dimension=1,
                                          is_stable=True, num_keys=1)
        f_ids = jnp.where(jnp.isfinite(f_dists), f_ids, -1)
    return f_ids, f_dists


def beam_search(graph: VamanaGraph, score_fn: ScoreFn, num_queries: int | None = None,
                *, beam_width: int, max_iters: int,
                fixed_trip: bool = False,
                expand_per_iter: int = 1,
                merge_strategy: str = "topk",
                tombstone_bits: Array | None = None,
                traverse_deleted: bool = True,
                labels: Array | None = None,
                filter_bytes: Array | None = None,
                filter_exclude: bool = False,
                beam_schedule: tuple | None = None,
                telemetry: bool = False) -> BeamSearchResult:
    """Run greedy beam search for a batch of queries.

    graph:      VamanaGraph (read-only snapshot — purity gives ParlayANN's
                snapshot semantics for free)
    score_fn:   closure over the query batch; maps (Q, K) ids -> (Q, K) dists
                (invalid ids may be passed clipped; masking happens here)
    beam_width: L — frontier size
    max_iters:  expansion budget (also the visited-log length)
    fixed_trip: True lowers a fori_loop (fixed cost, used by the dry-run);
                False uses while_loop with convergence early-exit.
    expand_per_iter: E > 1 expands the E closest unvisited frontier nodes
                per iteration (CAGRA-style multi-expansion, §Perf #C):
                ~E x fewer merge/sort passes and loop steps for the same
                number of distance computations, at a small recall cost
                from coarser expansion ordering. The visited log records
                only the FIRST pick per iteration — construction uses E=1.
    merge_strategy: "topk" (default — lax.top_k's selection, ids and
                visited bits carried through one sort, no gather; see
                merge_frontier_topk), "sort" (reference full sort-merge on
                the float key), or "kernel" (Pallas min-extraction top-k).
                The three select the same frontier except where -0.0 and
                0.0 distances meet: "topk" orders -0.0 first, the other
                two tie them in position order.
    tombstone_bits: optional packed row bitmap (core.mutations). Tombstoned
                ids are guaranteed absent from the returned frontier.
    traverse_deleted: True (default) keeps tombstoned nodes walkable — they
                occupy beam slots and their out-edges are followed, which
                preserves connectivity between consolidations (FreshDiskANN
                semantics); only the *final* frontier is filtered. False
                masks them during scoring as well (fused into self-masking
                kernel epilogues), the cheaper mode once `consolidate` has
                repaired the graph around them.
    labels / filter_bytes: optional per-row label plane (uint8[cap, NB],
                core.mutations) and query byte mask (uint8[NB]). A row
                matches when its bitset intersects the mask. The FINAL
                frontier is always filtered to matching rows — searches
                never return an out-of-filter id, whatever the walk mode.
    filter_exclude: False (default, mode "traverse") walks through
                non-matching rows for connectivity; True (mode "exclude")
                additionally masks them during scoring, mirroring
                `traverse_deleted=False` (self-masking kernel scorers fold
                the label gather into their epilogues).
    beam_schedule: optional static per-hop frontier widths (wide early,
                narrow late) — hop t merges at full width then narrows to
                `schedule[min(t, len-1)]` slots (see expand_schedule /
                apply_beam_width). None = constant beam_width, and a
                constant schedule (B,...,B) is bitwise identical to None.
    telemetry:  True additionally returns a `SearchTelemetry` (counters +
                per-hop occupancy). False (default) keeps the loop state
                and the result bit-identical to a build without the flag.
    """
    if merge_strategy not in MERGE_STRATEGIES:
        raise ValueError(
            f"merge_strategy must be one of {MERGE_STRATEGIES}, "
            f"got {merge_strategy!r}")
    merge = MERGE_FNS[merge_strategy]
    # scorers that mask invalid ids to +inf themselves (fused kernel
    # epilogues) let the loop skip its jnp masking pass over (Q, E*R)
    self_masking = getattr(score_fn, "self_masking", False)
    # exclude-mode tombstone masking for jnp scorers happens in the loop's
    # own masking pass; self-masking scorers fold the bitmap in-kernel
    exclude_in_body = (tombstone_bits is not None and not traverse_deleted
                       and not self_masking)
    # exclude-mode label filtering for jnp scorers mirrors the tombstone
    # path; self-masking scorers fold the label gather in-kernel
    filter_in_body = (labels is not None and filter_exclude
                      and not self_masking)
    if tombstone_bits is not None or labels is not None:
        from repro.core.mutations import (  # lazy: no cycle
            bitmap_gather, label_match_gather)
    adj = graph.adjacency
    n_valid = graph.n_valid
    degree = adj.shape[1]
    e_exp = expand_per_iter
    # per-hop width table, indexed by the (traced) iteration counter; None
    # skips the narrowing pass entirely so existing plans are unchanged
    sched = (None if beam_schedule is None else
             jnp.asarray(expand_schedule(beam_schedule, beam_width,
                                         max_iters), jnp.int32))

    # Infer Q by probing score_fn shape statically via the medoid column.
    if num_queries is None:
        raise ValueError("num_queries is required")
    q = num_queries

    medoid = graph.medoid
    init_ids = jnp.full((q, beam_width), -1, dtype=jnp.int32)
    init_ids = init_ids.at[:, 0].set(medoid)
    d0 = score_fn(init_ids[:, :1])  # (Q, 1)
    init_dists = jnp.full((q, beam_width), _INF, dtype=jnp.float32)
    init_dists = init_dists.at[:, :1].set(d0)
    init_vis = jnp.zeros((q, beam_width), dtype=jnp.bool_)
    visited_log = jnp.full((q, max_iters), -1, dtype=jnp.int32)
    visited_dlog = jnp.full((q, max_iters), _INF, dtype=jnp.float32)
    n_hops = jnp.zeros((q,), dtype=jnp.int32)

    # exclude-mode masked-candidate counting needs its own bitmap gather:
    # a self-masking kernel scorer hides the tombstone test in-kernel, so
    # the counter cannot ride on `exclude_in_body`
    count_masked = (telemetry and tombstone_bits is not None
                    and not traverse_deleted)
    count_fmasked = telemetry and labels is not None and filter_exclude

    state = (jnp.int32(0), init_ids, init_dists, init_vis,
             visited_log, visited_dlog, n_hops)
    if telemetry:
        state = state + (jnp.zeros((q,), jnp.int32),        # scored
                         jnp.zeros((q,), jnp.int32),        # masked
                         jnp.zeros((q,), jnp.int32),        # duplicates
                         jnp.zeros((q, max_iters), jnp.int32))  # occupancy

    def has_work(st):
        f_ids, f_vis = st[1], st[3]
        return jnp.any((f_ids >= 0) & ~f_vis)

    def cond(st):
        it = st[0]
        return (it < max_iters) & has_work(st)

    # the hop's phases carry named scopes (`hop.*`): they change only op
    # metadata, so a profiler trace maps each fusion to its phase
    def body(st):
        it, f_ids, f_dists, f_vis, vlog, vdlog, hops = st[:7]
        l_width = f_ids.shape[1]
        with jax.named_scope("hop.select"):
            unvis = (f_ids >= 0) & ~f_vis                  # (Q, L)
            # frontier is distance-sorted => first unvisited are the
            # closest; pick the first e_exp unvisited positions per query
            order = jnp.where(unvis, jnp.arange(l_width)[None, :], l_width)
            picks = jnp.sort(order, axis=1)[:, :e_exp]     # (Q, E)
            pick_valid = picks < l_width
            safe_picks = jnp.minimum(picks, l_width - 1)
            cur = jnp.take_along_axis(f_ids, safe_picks, axis=1)  # (Q, E)
            cur = jnp.where(pick_valid, cur, -1)
            cur_d = jnp.take_along_axis(f_dists, safe_picks, axis=1)
            active = pick_valid[:, 0]

            # mark picked as visited (scatter E bits per row)
            hit = jnp.any(jnp.arange(l_width)[None, None, :]
                          == picks[:, :, None], axis=1)
            f_vis = f_vis | (hit & unvis)

            vlog = vlog.at[:, it].set(cur[:, 0])
            vdlog = vdlog.at[:, it].set(jnp.where(active, cur_d[:, 0], _INF))
            hops = hops + jnp.sum(pick_valid, axis=1).astype(jnp.int32)

        with jax.named_scope("hop.adjacency"):
            # expand: gather neighbor lists of all picked nodes
            nbrs = adj[jnp.maximum(cur, 0)]                # (Q, E, R)
            nbrs = jnp.where((cur >= 0)[:, :, None], nbrs, -1)
            nbrs = nbrs.reshape(cur.shape[0], -1)          # (Q, E*R)
        with jax.named_scope("hop.dedup"):
            if e_exp > 1:
                # different expanded nodes may share neighbors: dedup
                # within the candidate row (order is irrelevant — the
                # merge re-sorts)
                big = jnp.int32(2**30)
                key = jnp.sort(jnp.where(nbrs >= 0, nbrs, big), axis=1)
                dup_in_row = jnp.concatenate(
                    [jnp.zeros_like(key[:, :1], dtype=jnp.bool_),
                     key[:, 1:] == key[:, :-1]], axis=1)
                nbrs = jnp.where(dup_in_row | (key >= big), -1, key)
            # drop out-of-range and frontier duplicates
            in_range = (nbrs >= 0) & (nbrs < n_valid)
            dup = jnp.any(nbrs[:, :, None] == f_ids[:, None, :], axis=2)
            valid = in_range & ~dup
            if count_masked or exclude_in_body:
                dead = bitmap_gather(tombstone_bits, nbrs) & valid
            if exclude_in_body:
                valid &= ~dead
            if count_fmasked or filter_in_body:
                # tombstone test FIRST: a dead candidate counts once in
                # `masked`, whatever the filter says about it
                fmiss = ~label_match_gather(labels, filter_bytes, nbrs) & valid
                if (count_masked or exclude_in_body) and not exclude_in_body:
                    fmiss &= ~dead
            if filter_in_body:
                valid &= ~fmiss
            nbrs = jnp.where(valid, nbrs, -1)
            if telemetry:
                scored, masked, dups, occ_log = st[7:]
                dead_n = (jnp.sum(dead, axis=1).astype(jnp.int32)
                          if count_masked else jnp.int32(0))
                fmiss_n = (jnp.sum(fmiss, axis=1).astype(jnp.int32)
                           if count_fmasked else jnp.int32(0))
                # counters naturally stay 0 on converged rows: cur = -1
                # there, so every neighbor is -1 and in_range is
                # all-False
                scored = scored + (jnp.sum(valid, axis=1).astype(jnp.int32)
                                   - (0 if exclude_in_body else dead_n)
                                   - (0 if filter_in_body else fmiss_n))
                masked = masked + dead_n + fmiss_n
                dups = dups + jnp.sum(in_range & dup,
                                      axis=1).astype(jnp.int32)

        with jax.named_scope("hop.score"):
            d = score_fn(nbrs)                             # (Q, E*R)
            if not self_masking:
                # invalid entries carry id -1 (set above), so a
                # self-masking scorer has already written +inf for
                # exactly `~valid`
                d = jnp.where(valid, d, _INF)

        with jax.named_scope("hop.merge"):
            f_ids, f_dists, f_vis = merge(
                f_ids, f_dists, f_vis, nbrs, d, beam_width=l_width)
            if sched is not None:
                # narrow only rows that expanded work this hop: a
                # converged row's frontier is frozen, so its results
                # don't depend on how long the rest of the batch keeps
                # iterating (and the fused megakernel — which retires
                # converged blocks early — agrees)
                ni, nd, nv = apply_beam_width(f_ids, f_dists, f_vis,
                                              sched[it])
                act = jnp.any(pick_valid, axis=1)[:, None]
                f_ids = jnp.where(act, ni, f_ids)
                f_dists = jnp.where(act, nd, f_dists)
                f_vis = jnp.where(act, nv, f_vis)
            out = (it + 1, f_ids, f_dists, f_vis, vlog, vdlog, hops)
            if telemetry:
                # post-merge/narrow live slots, logged only for rows that
                # expanded this hop (see SearchTelemetry docstring)
                occ = jnp.sum(f_ids >= 0, axis=1).astype(jnp.int32)
                occ_log = occ_log.at[:, it].set(jnp.where(active, occ, 0))
                out = out + (scored, masked, dups, occ_log)
        return out

    if fixed_trip:
        # convergence guard: a converged frontier skips the body, so the
        # fixed-trip lowering is bit-identical to the while_loop — same
        # number of body applications, same n_hops accounting (hops count
        # expansions actually performed, never loop trips)
        def fbody(_, st):
            return jax.lax.cond(has_work(st), body, lambda s: s, st)
        state = jax.lax.fori_loop(0, max_iters, fbody, state)
    else:
        state = jax.lax.while_loop(cond, body, state)

    _, f_ids, f_dists, f_vis, vlog, vdlog, hops = state[:7]
    tel = SearchTelemetry(*state[7:]) if telemetry else None
    # returnability filter: tombstoned and out-of-filter frontier entries
    # drop to the tail as (+inf, -1) — searches NEVER return deleted or
    # out-of-filter ids, whatever the traversal/filter mode was
    f_ids, f_dists = finalize_frontier(f_ids, f_dists, tombstone_bits,
                                       labels=labels,
                                       filter_bytes=filter_bytes)
    return BeamSearchResult(frontier_ids=f_ids, frontier_dists=f_dists,
                            visited_ids=vlog, visited_dists=vdlog,
                            n_hops=hops, telemetry=tel)


def rerank_frontier(vectors: Array, vec_sqnorm: Array, queries: Array,
                    ids: Array, *, tile_q: int = 512,
                    use_kernels: bool = False,
                    interpret: bool | None = None) -> Array:
    """Exact distances for a (Q, L) frontier, tiled over the query axis.

    The rerank stage's working set is the gathered (Q, L, D) f32 candidate
    buffer — at serving batch sizes that alone can blow past VMEM-friendly
    footprints and pins the stage to the bandwidth roof. Tiling processes
    `tile_q` queries at a time under `lax.map`, bounding the live gather
    buffer at (tile_q, L, D) regardless of Q; with use_kernels the per-tile
    score runs through the Pallas gather-distance kernel (fused HBM->VMEM
    tile loads), otherwise the jnp gather+einsum reference.

    Invalid ids (< 0) come back +inf. Both drivers' quantized rerank and
    the sharded path's shard-local final rerank go through here.
    """
    q_n, l = ids.shape
    tile_q = max(1, min(tile_q, q_n))
    pad = (-q_n) % tile_q
    q_pad = jnp.pad(queries.astype(jnp.float32), ((0, pad), (0, 0)))
    ids_pad = jnp.pad(ids, ((0, pad), (0, 0)), constant_values=-1)
    n_tiles = (q_n + pad) // tile_q
    q_tiles = q_pad.reshape(n_tiles, tile_q, -1)
    id_tiles = ids_pad.reshape(n_tiles, tile_q, l)

    if use_kernels:
        from repro.kernels.distance.ops import gather_l2_chunked

        def do_tile(args):
            qt, it = args
            return gather_l2_chunked(qt, vectors, vec_sqnorm, it,
                                     interpret=interpret)
    else:
        def do_tile(args):
            qt, it = args
            score = make_exact_scorer(vectors, qt, None, vec_sqnorm)
            return jnp.where(it >= 0, score(it), _INF)

    with jax.named_scope("search.rerank"):
        d = jax.lax.map(do_tile, (q_tiles, id_tiles))
    return d.reshape(-1, l)[:q_n]


def beam_search_quantized(graph: VamanaGraph, codes: RaBitQCodes,
                          query: RaBitQQuery, *, beam_width: int,
                          max_iters: int,
                          rerank_score_fn: ScoreFn | None = None,
                          fixed_trip: bool = False,
                          expand_per_iter: int = 1,
                          use_kernels: bool = False,
                          merge_strategy: str = "topk",
                          tombstone_bits: Array | None = None,
                          traverse_deleted: bool = True,
                          labels: Array | None = None,
                          filter_bytes: Array | None = None,
                          filter_exclude: bool = False,
                          beam_schedule: tuple | None = None,
                          telemetry: bool = False,
                          interpret: bool | None = None) -> BeamSearchResult:
    """Beam search on RaBitQ estimated distances (Jasper RaBitQ).

    use_kernels routes scoring through the fused Pallas estimator kernel
    (in-VMEM unpack + MXU dot + epilogue with invalid-id masking) over the
    canonical packed codes; otherwise the jnp estimator path is used. Both
    read the same packed HBM bytes. expand_per_iter mirrors the exact
    path's multi-expansion (§Perf #C1).

    tombstone_bits/traverse_deleted mirror `beam_search`; in exclude mode
    the kernel path folds the bitmap into the search-step epilogue (one
    byte-gather per candidate rides along with the packed-code gather).
    labels/filter_bytes/filter_exclude mirror `beam_search` the same way:
    exclude-mode label masking rides the identical kernel epilogue, and
    the final frontier (and its exact rerank) is always label-filtered.

    Optionally reranks the final frontier with exact distances — the standard
    RaBitQ recipe for recovering recall lost to the estimator.
    """
    if use_kernels:
        # deferred import: core stays importable without the kernels package
        from repro.kernels.rabitq_dot.ops import make_rabitq_kernel_scorer
        score = make_rabitq_kernel_scorer(
            codes, query, n_valid=graph.n_valid,
            tombstone_bits=(None if traverse_deleted else tombstone_bits),
            labels=(labels if filter_exclude else None),
            filter_bytes=(filter_bytes if filter_exclude else None),
            interpret=interpret)
    else:
        score = make_rabitq_scorer(codes, query)
    res = beam_search(graph, score, query.q_rot.shape[0],
                      beam_width=beam_width, max_iters=max_iters,
                      fixed_trip=fixed_trip, expand_per_iter=expand_per_iter,
                      merge_strategy=merge_strategy,
                      tombstone_bits=tombstone_bits,
                      traverse_deleted=traverse_deleted,
                      labels=labels, filter_bytes=filter_bytes,
                      filter_exclude=filter_exclude,
                      beam_schedule=beam_schedule,
                      telemetry=telemetry)
    if rerank_score_fn is None:
        return res
    exact_d = rerank_score_fn(res.frontier_ids)
    exact_d = jnp.where(res.frontier_ids >= 0, exact_d, _INF)
    sd, si = jax.lax.sort((exact_d, res.frontier_ids), dimension=1,
                          is_stable=True, num_keys=1)
    return BeamSearchResult(frontier_ids=si, frontier_dists=sd,
                            visited_ids=res.visited_ids,
                            visited_dists=res.visited_dists,
                            n_hops=res.n_hops, telemetry=res.telemetry)

"""Batch-parallel lock-free Vamana construction (paper §3.3/§4.3, Alg. 3).

The ParlayANN recipe, restructured for accelerator execution:

  Step 1  beam-search every point of the batch against a READ-ONLY snapshot
          of the graph (purity of JAX makes the snapshot property a theorem,
          not a discipline) — candidate edges = visited set ∪ frontier.
  Step 2  forward prune: RobustPrune each new point's candidates, write its
          adjacency row.
  Step 3  reverse edges: every forward edge (x -> v) proposes (v -> x).
          GPU Jasper replaces ParlayANN's semisort with a FULL SORT by
          (dst, dist) because wide-SIMD machines want load balance (§4.3);
          we inherit that: one `lax.sort` groups edges, segment arithmetic
          builds fixed-shape per-vertex candidate buffers, and a batched
          RobustPrune rewrites every touched adjacency row. No locks, no
          atomics — pure scatter.

All shapes are static: the reverse-edge table is capacity B*R (the true
worst case), and per-vertex incoming candidates are capped at `rev_cap`,
keeping the CLOSEST proposals (the sort puts them first) — principled
truncation, and the fixed-shape analogue of ParlayANN's dynamic buffers.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.beam_search import (_f32_total_key, beam_search,
                                     make_exact_scorer)
from repro.core.robust_prune import robust_prune_batch
from repro.core.vamana import VamanaGraph
from repro.core.medoid import compute_medoid

Array = jax.Array

_INF = float("inf")

# One `batch_insert_at` of B rows into a table of N rows of D floats holds
# about 160 x B x (D + 800) bytes of temporaries, beside about 9 x N x D
# bytes of rows and their working copies (XLA's memory analysis of the
# rung compiled for a TPU v5e, which gives a program 15.75 GB): 9.7 GB of
# temporaries at B = 65,536, N = 10^6, D = 128; 9.2 GB at B = 32,768,
# N = 65,536, D = 960; and 16.3 GB in all at B = 32,768, N = 10^6,
# D = 960, which does not fit, against 13.4 GB at B = 16,384.
_CHIP_BYTES = 15.75e9


def max_batch(n_rows: int, dims: int) -> int:
    """The largest insert batch that fits one chip beside a table of
    `n_rows` x `dims` floats: a power of two, at most 65,536 (65,536 at
    D = 128 for any N up to 10^6; at D = 960, 32,768 up to 262,144 rows
    and 16,384 at 10^6)."""
    free = _CHIP_BYTES - 9 * n_rows * dims
    b = max(256, int(free // (160 * (dims + 800))))
    return min(1 << 16, 1 << (b.bit_length() - 1))


@dataclass(frozen=True)
class ConstructionParams:
    """Static construction hyper-parameters (paper defaults: R=64, alpha=1.2)."""

    degree_bound: int = 64        # R
    alpha: float = 1.2
    beam_width: int = 64          # L during construction
    max_iters: int = 96           # expansion budget / visited-log length
    rev_cap: int = 64             # max incoming reverse-edge candidates kept
    prune_chunk: int = 1024       # vertices per prune chunk (memory knob)


def _adjacency_distances(vectors: Array, pivot_ids: Array, adj_rows: Array,
                         chunk_size: int) -> Array:
    """d2(pivot, each existing neighbor). (V,), (V, R) -> (V, R)."""
    v_total = pivot_ids.shape[0]
    pad = (-v_total) % chunk_size
    if pad:
        pivot_ids = jnp.pad(pivot_ids, (0, pad), constant_values=-1)
        adj_rows = jnp.pad(adj_rows, ((0, pad), (0, 0)), constant_values=-1)

    def do_chunk(args):
        p_ids, rows = args
        pv = vectors[jnp.maximum(p_ids, 0)].astype(jnp.float32)     # (c, D)
        nv = vectors[jnp.maximum(rows, 0)].astype(jnp.float32)      # (c, R, D)
        d = jnp.sum((nv - pv[:, None, :]) ** 2, axis=-1)
        return jnp.where(rows >= 0, d, _INF)

    n_chunks = pivot_ids.shape[0] // chunk_size
    chunked = jax.tree_util.tree_map(
        lambda a: a.reshape((n_chunks, chunk_size) + a.shape[1:]),
        (pivot_ids, adj_rows))
    d = jax.lax.map(do_chunk, chunked)
    d = d.reshape((-1,) + d.shape[2:])
    return d[:v_total] if pad else d


def _f32_order_key(x: Array) -> Array:
    """int32 whose signed order is x's float order (-0.0 folded into 0.0;
    x holds no NaN)."""
    return _f32_total_key(jnp.where(x == 0.0, 0.0, x))


def _group_reverse_edges(dst: Array, src: Array, dist: Array, rev_cap: int
                         ) -> tuple[Array, Array, Array]:
    """Full-sort + segment-scatter edge grouping (the GPU-Thrust analogue).

    dst/src/dist: (E,) flat reverse-edge proposals (-1 dst = dead).
    Returns (touched (E,), in_ids (E, rev_cap), in_dists (E, rev_cap)):
    row u of in_* holds the closest <=rev_cap proposals for vertex
    touched[u]; unused rows have touched = -1.
    """
    e = dst.shape[0]
    big = jnp.int32(2**30)
    key = jnp.where(dst >= 0, dst, big)
    # the order of a stable (dst, dist) sort, as two stable one-key integer
    # sorts (least significant key first): XLA:TPU compiles a sort's
    # comparator network once per key, and a three-key sort of B*R
    # elements took about twice as long to compile as these two together
    pos = jnp.arange(e, dtype=jnp.int32)
    _, by_dist = jax.lax.sort((_f32_order_key(dist), pos), dimension=0,
                              num_keys=1, is_stable=True)
    s_key, perm = jax.lax.sort((key[by_dist], by_dist), dimension=0,
                               num_keys=1, is_stable=True)
    s_dist, s_src = dist[perm], src[perm]
    valid = s_key < big
    new_seg = jnp.concatenate(
        [valid[:1], (s_key[1:] != s_key[:-1]) & valid[1:]])
    seg_id = jnp.cumsum(new_seg.astype(jnp.int32)) - 1          # (E,)
    seg_start = jax.lax.cummax(jnp.where(new_seg, pos, 0))
    rank = pos - seg_start

    touched = jnp.full((e,), -1, dtype=jnp.int32)
    touched = touched.at[jnp.where(new_seg, seg_id, e)].set(s_key, mode="drop")

    keep = valid & (rank < rev_cap)
    row = jnp.where(keep, seg_id, e)                             # drop route
    col = jnp.minimum(rank, rev_cap - 1)
    in_ids = jnp.full((e, rev_cap), -1, dtype=jnp.int32)
    in_ids = in_ids.at[row, col].set(s_src, mode="drop")
    in_dists = jnp.full((e, rev_cap), _INF, dtype=jnp.float32)
    in_dists = in_dists.at[row, col].set(s_dist, mode="drop")
    return touched, in_ids, in_dists


def batch_insert(vectors: Array, graph: VamanaGraph, batch_start: Array,
                 *, batch_size: int, params: ConstructionParams,
                 already_inserted: bool = False,
                 vec_sqnorm: Array | None = None) -> VamanaGraph:
    """Insert vectors[batch_start : batch_start + batch_size] into the graph.

    Contiguous-range wrapper over `batch_insert_at` (the common bulk-build
    case). With already_inserted=True this is a REFINEMENT pass over
    existing vertices (Vamana's second pass): n_valid does not advance and
    the point may rediscover itself (pruned as a self-edge).
    """
    new_ids = batch_start + jnp.arange(batch_size, dtype=jnp.int32)
    return batch_insert_at(vectors, graph, new_ids, params=params,
                           already_inserted=already_inserted,
                           vec_sqnorm=vec_sqnorm)


@partial(jax.jit, static_argnames=("params", "already_inserted"))
def batch_insert_at(vectors: Array, graph: VamanaGraph, new_ids: Array,
                    *, params: ConstructionParams,
                    already_inserted: bool = False,
                    vec_sqnorm: Array | None = None,
                    tombstone_bits: Array | None = None) -> VamanaGraph:
    """Insert the (already written) rows `new_ids` into the graph.

    new_ids need not be contiguous: the mutation subsystem reuses freed
    slots, so a streaming batch is typically [reused ids..., tail ids...].
    n_valid is the HIGH-WATER mark — it advances only past fresh tail ids.
    Reused slots are unreachable in the snapshot (consolidation removed
    every edge into them), so they cannot surface as their own candidates.

    tombstone_bits: packed row bitmap (core.mutations) — tombstoned rows
    stay traversable during candidate search but are excluded from every
    pruned edge list, so new vertices never link to deleted ones.
    """
    r = params.degree_bound
    adj = graph.adjacency
    n_old = graph.n_valid
    batch_size = new_ids.shape[0]
    queries = vectors[new_ids]
    live = None
    if tombstone_bits is not None:
        from repro.core.mutations import unpack_bitmap  # lazy: no cycle
        live = ~unpack_bitmap(tombstone_bits, adj.shape[0])

    # each step under a named scope (`build.search`, `build.prune`,
    # `build.reverse`), so a device trace of a build splits by step
    # ---- Step 1: snapshot beam search ------------------------------------
    with jax.named_scope("build.search"):
        score = make_exact_scorer(vectors, queries, n_old, vec_sqnorm)
        res = beam_search(graph, score, batch_size,
                          beam_width=params.beam_width,
                          max_iters=params.max_iters)
        # candidate edges: visited set ∪ final frontier (both returned)
        cand_ids = jnp.concatenate([res.visited_ids, res.frontier_ids],
                                   axis=1)
        cand_dists = jnp.concatenate([res.visited_dists, res.frontier_dists],
                                     axis=1)

    # ---- Step 2: forward prune -------------------------------------------
    with jax.named_scope("build.prune"):
        fwd = robust_prune_batch(vectors, new_ids, cand_ids, cand_dists,
                                 n_old, degree_bound=r, alpha=params.alpha,
                                 chunk_size=params.prune_chunk, live=live)
        adj = adj.at[new_ids].set(fwd.selected_ids)

    # ---- Step 3: reverse edges (full sort + batched prune) ----------------
    with jax.named_scope("build.reverse"):
        dst = fwd.selected_ids.reshape(-1)                     # (B*R,)
        src = jnp.repeat(new_ids, r)
        dist = fwd.selected_dists.reshape(-1)
        touched, in_ids, in_dists = _group_reverse_edges(dst, src, dist,
                                                         params.rev_cap)

        exist_rows = adj[jnp.maximum(touched, 0)]              # (T, R)
        exist_rows = jnp.where((touched >= 0)[:, None], exist_rows, -1)
        exist_dists = _adjacency_distances(vectors, touched, exist_rows,
                                           params.prune_chunk)

        # high-water mark: contiguous batches advance by B; slot-reusing
        # batches advance only past the largest fresh tail id
        n_after = (n_old if already_inserted
                   else jnp.maximum(n_old, jnp.max(new_ids) + 1))
        cand2_ids = jnp.concatenate([exist_rows, in_ids], axis=1)
        cand2_dists = jnp.concatenate([exist_dists, in_dists], axis=1)
        rev = robust_prune_batch(vectors, touched, cand2_ids, cand2_dists,
                                 n_after.astype(jnp.int32), degree_bound=r,
                                 alpha=params.alpha,
                                 chunk_size=params.prune_chunk, live=live)
        adj = adj.at[jnp.where(touched >= 0, touched, adj.shape[0])].set(
            rev.selected_ids, mode="drop")

    return VamanaGraph(adjacency=adj, n_valid=n_after.astype(jnp.int32),
                       medoid=graph.medoid)


@partial(jax.jit, static_argnames=("n0", "params"))
def bootstrap_graph(vectors: Array, graph: VamanaGraph, *, n0: int,
                    params: ConstructionParams) -> VamanaGraph:
    """All-pairs bootstrap for the first n0 points (empty-graph base case).

    Candidates for each point = its 4R nearest among the bootstrap set, then
    RobustPrune — a dense, high-quality seed graph that incremental batches
    build on (ParlayANN starts from a similar prefix).
    """
    r = params.degree_bound
    ids = jnp.arange(n0, dtype=jnp.int32)
    v = vectors[:n0].astype(jnp.float32)
    sq = jnp.sum(v * v, axis=-1)
    # float32 rows: at default precision a TPU runs this product as one
    # bfloat16 pass, which ranks float rows' candidates below float32
    vv = jnp.matmul(v, v.T, precision=jax.lax.Precision.HIGHEST)
    d = jnp.maximum(sq[:, None] - 2.0 * vv + sq[None, :], 0.0)
    c = min(4 * r, n0)
    sd, si = jax.lax.top_k(-d, c)                           # nearest c
    cand_ids = si.astype(jnp.int32)
    cand_dists = -sd
    res = robust_prune_batch(vectors, ids, cand_ids, cand_dists,
                             jnp.int32(n0), degree_bound=r, alpha=params.alpha,
                             chunk_size=params.prune_chunk)
    adj = graph.adjacency.at[ids].set(res.selected_ids)
    medoid = compute_medoid(vectors, jnp.arange(vectors.shape[0]) < n0)
    return VamanaGraph(adjacency=adj, n_valid=jnp.int32(n0), medoid=medoid)


def build_graph(vectors: Array, n_total: int, *, params: ConstructionParams,
                bootstrap_size: int = 1024, min_batch: int = 256,
                refine: bool = False, progress_fn=None) -> VamanaGraph:
    """Bulk construction: bootstrap + prefix-doubling batch insertion.

    Host-side driver (the paper's Fig. 2 pipeline). Batch sizes double as
    the index grows (ParlayANN schedule) so early batches see a graph of
    comparable size; jit caches one executable per batch size rung.
    """
    from repro.core.vamana import init_graph  # local to avoid cycle

    capacity = vectors.shape[0]
    cap = max_batch(*vectors.shape)
    if n_total > capacity:
        raise ValueError(f"n_total {n_total} exceeds capacity {capacity}")
    graph = init_graph(capacity, params.degree_bound)
    n0 = min(bootstrap_size, n_total)
    graph = bootstrap_graph(vectors, graph, n0=n0, params=params)
    vec_sqnorm = jnp.sum(vectors.astype(jnp.float32) ** 2, axis=-1)

    inserted = n0
    while inserted < n_total:
        remaining = n_total - inserted
        b = min(max(min_batch, 1 << (inserted.bit_length() - 1)), cap)
        b = min(b, remaining)
        # round DOWN to a power of two for executable reuse; exact remainder
        # batches only happen once at the tail of each rung
        if b not in (remaining,):
            b = 1 << (b.bit_length() - 1)
        graph = batch_insert(vectors, graph, jnp.int32(inserted),
                             batch_size=b, params=params,
                             vec_sqnorm=vec_sqnorm)
        inserted += b
        if progress_fn is not None:
            progress_fn(inserted, n_total)

    if refine:  # optional Vamana second pass over everything
        done = 0
        while done < n_total:
            b = min(cap, n_total - done)
            b = 1 << (b.bit_length() - 1) if b != n_total - done else b
            graph = batch_insert(vectors, graph, jnp.int32(done),
                                 batch_size=b, params=params,
                                 already_inserted=True, vec_sqnorm=vec_sqnorm)
            done += b

    # refresh the entry point once construction settles
    medoid = compute_medoid(vectors, jnp.arange(capacity) < graph.n_valid)
    return VamanaGraph(adjacency=graph.adjacency, n_valid=graph.n_valid,
                       medoid=medoid)

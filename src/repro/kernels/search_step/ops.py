"""Public wrappers for the fused search kernels.

`fused_beam_search` is the entry `core_search` routes to when
`spec.fusion != "none"`: it prepares the padded operands, runs either the
per-hop fused kernel under a host-side `while_loop` (fusion="hop") or the
persistent megakernel (fusion="megakernel"), and finishes through the
same `finalize_frontier` epilogue as the unfused loop — so the
'never return a tombstoned id' invariant has one definition everywhere.

`interpret` defaults to auto: real Mosaic lowering on TPU, interpreter on
CPU (this container) — the same convention as every other kernel wrapper.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.beam_search import (
    BeamSearchResult,
    SearchTelemetry,
    expand_schedule,
    finalize_frontier,
    make_exact_scorer,
)
from repro.core.rabitq import RaBitQCodes, RaBitQQuery, rabitq_estimate
from repro.core.vamana import VamanaGraph
from repro.kernels.backend import resolve_interpret
from repro.kernels.search_step.search_step_kernel import (
    fused_hop_pallas,
    fused_planes,
    fused_search_pallas,
)

Array = jax.Array

_INF = float("inf")


def _pad_rows(x: Array, mult: int, value) -> Array:
    pad = (-x.shape[0]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[0] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def fused_beam_search(graph: VamanaGraph, *, mode: str, beam_width: int,
                      max_iters: int, beam_schedule: tuple | None = None,
                      queries: Array | None = None,
                      vectors: Array | None = None,
                      vec_sqnorm: Array | None = None,
                      codes: RaBitQCodes | None = None,
                      rq_query: RaBitQQuery | None = None,
                      tombstone_bits: Array | None = None,
                      traverse_deleted: bool = True,
                      labels: Array | None = None,
                      filter_bytes: Array | None = None,
                      filter_exclude: bool = False,
                      block_q: int = 8,
                      telemetry: bool = False,
                      interpret: bool | None = None) -> BeamSearchResult:
    """Fused greedy beam search — exact (vectors) or quantized (codes).

    mode: "hop" (one fused launch per hop, host-side convergence loop) or
    "megakernel" (one persistent launch, frontier on-chip throughout).
    labels/filter_bytes/filter_exclude: label filtering, mirroring the
    tombstone plumbing — exclude mode gathers each candidate's label row
    in the kernel epilogue; either mode label-filters the final frontier
    through the shared `finalize_frontier`.
    Returns the standard `BeamSearchResult` (visited logs are not
    maintained by the fused paths and come back as empty -1/+inf fills).
    telemetry=True fills `result.telemetry` with the in-kernel counters
    (SearchTelemetry; the ref oracle's exact values) — off, the kernels
    are launched with zero extra outputs.
    """
    interpret = resolve_interpret(interpret)
    if mode not in ("hop", "megakernel"):
        raise ValueError(f"mode must be 'hop' or 'megakernel', got {mode!r}")
    quantized = codes is not None

    # ---- query-side operands (q, qa, qb) + the medoid entry distance,
    # scored with the same jnp reference math as the unfused loop's init
    if quantized:
        num_q = rq_query.q_rot.shape[0]
        init_ids = jnp.full((num_q, 1), graph.medoid, jnp.int32)
        d0 = rabitq_estimate(codes, rq_query, init_ids)
        bits = codes.bits
        p_dim = codes.packed.shape[1]
        d_need = p_dim * (8 // bits)
        q = rq_query.q_rot.astype(jnp.float32)
        if q.shape[1] < d_need:   # unpacked padding dims x zero q = inert
            q = jnp.pad(q, ((0, 0), (0, d_need - q.shape[1])))
        qa = rq_query.query_add.reshape(-1, 1).astype(jnp.float32)
        qb = rq_query.query_sumq.reshape(-1, 1).astype(jnp.float32)
        data = codes.packed
        meta = jnp.stack([codes.data_add, codes.data_rescale], axis=1)
    else:
        num_q = queries.shape[0]
        init_ids = jnp.full((num_q, 1), graph.medoid, jnp.int32)
        d0 = make_exact_scorer(vectors, queries, graph.n_valid,
                               vec_sqnorm)(init_ids)
        bits = 0
        q = queries.astype(jnp.float32)
        qa = jnp.sum(q * q, axis=-1, keepdims=True)
        qb = jnp.zeros_like(qa)
        data = vectors
        meta = vec_sqnorm.reshape(-1, 1).astype(jnp.float32)

    # exclude-mode liveness is gathered in-kernel; traverse mode leaves the
    # walk alone and filters only the final frontier (shared epilogue)
    use_tomb = tombstone_bits is not None and not traverse_deleted
    # same split for the label filter: exclude mode rides the kernel
    # epilogue, traverse mode only label-filters the final frontier
    use_filt = labels is not None and filter_exclude
    # lane-dense HBM views of every gathered plane, built once per search
    views, planes, fbw = fused_planes(
        graph.adjacency, data, meta,
        tombstone_bits.reshape(-1) if use_tomb else None,
        labels if use_filt else None,
        (jnp.asarray(filter_bytes, jnp.int32).reshape(-1)
         if use_filt else None),
        dim=q.shape[1])

    # ---- init frontier (medoid in slot 0), padded to the query block
    f_ids = jnp.full((num_q, beam_width), -1, jnp.int32)
    f_ids = f_ids.at[:, 0].set(graph.medoid)
    f_dists = jnp.full((num_q, beam_width), _INF, jnp.float32)
    f_dists = f_dists.at[:, :1].set(d0)
    f_vis = jnp.zeros((num_q, beam_width), jnp.int32)
    f_ids = _pad_rows(f_ids, block_q, -1)     # padded rows: empty frontier,
    f_dists = _pad_rows(f_dists, block_q, _INF)  # never any work
    f_vis = _pad_rows(f_vis, block_q, 0)
    q = _pad_rows(q, block_q, 0.0)
    qa = _pad_rows(qa, block_q, 0.0)
    qb = _pad_rows(qb, block_q, 0.0)

    sched = jnp.asarray(
        expand_schedule(beam_schedule, beam_width, max_iters), jnp.int32)
    kern = dict(quantized=quantized, bits=bits, use_tomb=use_tomb,
                use_filt=use_filt, block_q=block_q, telemetry=telemetry,
                interpret=interpret)

    tel = None
    if mode == "megakernel":
        out = fused_search_pallas(
            f_ids, f_dists, f_vis, sched, q, qa, qb, views, planes, fbw,
            graph.n_valid, max_iters=max_iters, **kern)
        f_ids, f_dists, hops = out[:3]
        hops = hops[:, 0]
        if telemetry:
            counters, occ_log = out[3:]
            tel = (counters[:, 0], counters[:, 1], counters[:, 2], occ_log)
    else:
        qn = f_ids.shape[0]
        hops = jnp.zeros((qn,), jnp.int32)

        state = (jnp.int32(0), f_ids, f_dists, f_vis, hops)
        if telemetry:
            zc = jnp.zeros((qn,), jnp.int32)
            state = state + (zc, zc, zc,
                             jnp.zeros((qn, max_iters), jnp.int32))

        def cond(st):
            it, fi, _, fv = st[:4]
            return (it < max_iters) & jnp.any((fi >= 0) & (fv == 0))

        def body(st):
            it, fi, fd, fv, hops = st[:5]
            hop = fused_hop_pallas(
                fi, fd, fv, sched[it], q, qa, qb, views, planes, fbw,
                graph.n_valid, **kern)
            nfi, nfd, nfv, inc = hop[:4]
            out = (it + 1, nfi, nfd, nfv, hops + inc[:, 0])
            if telemetry:
                scored, masked, dups, occ_log = st[5:]
                ht = hop[4]
                # the hop kernel's occupancy column lands at the (traced)
                # hop index — the log mirrors the megakernel's scratch
                occ_log = jax.lax.dynamic_update_slice(
                    occ_log, ht[:, 3:4], (0, it))
                out = out + (scored + ht[:, 0], masked + ht[:, 1],
                             dups + ht[:, 2], occ_log)
            return out

        state = jax.lax.while_loop(cond, body, state)
        _, f_ids, f_dists, _, hops = state[:5]
        if telemetry:
            tel = state[5:]

    f_ids, f_dists = f_ids[:num_q], f_dists[:num_q]
    f_ids, f_dists = finalize_frontier(f_ids, f_dists, tombstone_bits,
                                       labels=labels,
                                       filter_bytes=filter_bytes)
    if tel is not None:
        tel = SearchTelemetry(tel[0][:num_q], tel[1][:num_q],
                              tel[2][:num_q], tel[3][:num_q])
    return BeamSearchResult(
        frontier_ids=f_ids, frontier_dists=f_dists,
        visited_ids=jnp.full((num_q, max_iters), -1, jnp.int32),
        visited_dists=jnp.full((num_q, max_iters), _INF, jnp.float32),
        n_hops=hops[:num_q], telemetry=tel)

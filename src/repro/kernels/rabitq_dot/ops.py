"""Public wrapper for the fused RaBitQ estimator kernel."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.rabitq import RaBitQCodes, RaBitQQuery
from repro.kernels.backend import resolve_interpret
from repro.kernels.rabitq_dot.rabitq_kernel import (
    rabitq_distance_pallas,
    rabitq_gather_distance_pallas,
    rabitq_search_step_pallas,
)

Array = jax.Array


def _pad_to(x: Array, mult: int, axis: int, value=0) -> Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _q_to_code_width(q_rot: Array, p: int, bits: int) -> Array:
    """Zero-pad rotated queries to the unpacked width P * 8//bits (padded
    dims meet zero codes and contribute nothing)."""
    q = q_rot.astype(jnp.float32)
    return jnp.pad(q, ((0, 0), (0, p * (8 // bits) - q.shape[1])))


@partial(jax.jit, static_argnames=("bits", "block_q", "block_c", "interpret"))
def rabitq_distance(packed: Array, data_add: Array, data_rescale: Array,
                    q_rot: Array, query_add: Array, query_sumq: Array, *,
                    bits: int, block_q: int = 128, block_c: int = 256,
                    interpret: bool | None = None) -> Array:
    """All-candidates estimated distances: (Q, C) from packed codes."""
    interpret = resolve_interpret(interpret)
    qn = q_rot.shape[0]
    cn, p = packed.shape
    q_pad = _q_to_code_width(q_rot, p, bits)
    q_pad = _pad_to(q_pad, block_q, 0)
    qadd = _pad_to(query_add, block_q, 0)
    qsum = _pad_to(query_sumq, block_q, 0)
    p_pad = _pad_to(packed, block_c, 0)
    dadd = _pad_to(data_add, block_c, 0)
    drs = _pad_to(data_rescale, block_c, 0)
    out = rabitq_distance_pallas(p_pad, dadd, drs, q_pad, qadd, qsum,
                                 bits=bits, block_q=block_q, block_c=block_c,
                                 interpret=interpret)
    return out[:qn, :cn]


@partial(jax.jit, static_argnames=("bits", "block_q", "interpret"))
def rabitq_gather_distance(cand_packed: Array, cand_add: Array,
                           cand_rescale: Array, q_rot: Array,
                           query_add: Array, query_sumq: Array, *, bits: int,
                           block_q: int = 8, interpret: bool | None = None
                           ) -> Array:
    """Beam-search form: (Q, K, P) candidate codes -> (Q, K) estimates."""
    interpret = resolve_interpret(interpret)
    p = cand_packed.shape[2]
    qn = q_rot.shape[0]
    q_pad = _q_to_code_width(q_rot, p, bits)
    q_pad = _pad_to(q_pad, block_q, 0)
    out = rabitq_gather_distance_pallas(
        _pad_to(cand_packed, block_q, 0),
        _pad_to(cand_add, block_q, 0),
        _pad_to(cand_rescale, block_q, 0),
        q_pad,
        _pad_to(query_add, block_q, 0),
        _pad_to(query_sumq, block_q, 0),
        bits=bits, block_q=block_q, interpret=interpret)
    return out[:qn]


@partial(jax.jit, static_argnames=("bits", "block_q", "interpret"))
def rabitq_search_step(cand_packed: Array, cand_add: Array,
                       cand_rescale: Array, ids: Array, n_valid: Array,
                       q_rot: Array, query_add: Array, query_sumq: Array, *,
                       bits: int, block_q: int = 8,
                       live: Array | None = None,
                       interpret: bool | None = None) -> Array:
    """Fused search-step: (Q, K, P) gathered codes + raw beam ids -> (Q, K)
    estimates with invalid-id masking fused into the kernel epilogue.

    live: optional (Q, K) per-candidate tombstone flags (1 = live, 0 = dead
    -> +inf); omitted means every in-range id is live."""
    interpret = resolve_interpret(interpret)
    qn, _, p = cand_packed.shape
    if live is None:
        live = jnp.ones_like(ids, dtype=jnp.int32)
    q_pad = _q_to_code_width(q_rot, p, bits)
    out = rabitq_search_step_pallas(
        _pad_to(cand_packed, block_q, 0),
        _pad_to(cand_add, block_q, 0),
        _pad_to(cand_rescale, block_q, 0),
        _pad_to(ids.astype(jnp.int32), block_q, 0, value=-1),
        _pad_to(live.astype(jnp.int32), block_q, 0),
        jnp.asarray(n_valid, jnp.int32).reshape(1, 1),
        _pad_to(q_pad, block_q, 0),
        _pad_to(query_add, block_q, 0),
        _pad_to(query_sumq, block_q, 0),
        bits=bits, block_q=block_q, interpret=interpret)
    return out[:qn]


def make_rabitq_kernel_scorer(codes: RaBitQCodes, query: RaBitQQuery, *,
                              n_valid: Array,
                              tombstone_bits: Array | None = None,
                              labels: Array | None = None,
                              filter_bytes: Array | None = None,
                              interpret: bool | None = None):
    """Beam-search ScoreFn over the canonical PACKED codes.

    Bulk-gathers candidate code rows in packed form (chunked-load strategy:
    ceil(D*bits/8) + 8 bytes per candidate instead of 4*D), then runs one
    fused unpack + estimator + masking-epilogue kernel per query tile. No
    re-packing ever happens — codes.rows is the HBM-resident array.

    tombstone_bits: optional packed row bitmap (core.mutations) for
    exclude-mode searches — each candidate's bit is gathered alongside its
    code row (1 extra byte per candidate) and masked in the epilogue.
    labels/filter_bytes: optional label plane + query byte mask for
    exclude-mode filtered searches — each candidate's label row is
    gathered the same way (N_LABEL_BYTES extra bytes per candidate, never
    a dense unpack) and non-matching candidates go dead in the epilogue.
    """
    def score(ids: Array) -> Array:
        safe = jnp.maximum(ids, 0)
        # one bulk gather of (Q, K) code rows: packed codes + both factors
        cand, dadd, drs = codes.gather_packed(safe)
        live = None
        if tombstone_bits is not None:
            from repro.core.mutations import bitmap_gather
            live = (~bitmap_gather(tombstone_bits, safe)).astype(jnp.int32)
        if labels is not None:
            from repro.core.mutations import label_match_gather
            hit = label_match_gather(labels, filter_bytes, safe)
            live = (hit.astype(jnp.int32) if live is None
                    else live * hit.astype(jnp.int32))
        return rabitq_search_step(cand, dadd, drs, ids, n_valid,
                                  query.q_rot, query.query_add,
                                  query.query_sumq, bits=codes.bits,
                                  live=live, interpret=interpret)

    # masking happens in the kernel epilogue; beam_search skips its own pass
    score.self_masking = True
    return score

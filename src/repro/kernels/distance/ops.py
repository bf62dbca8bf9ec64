"""Public jit'd wrappers for the distance kernels (padding + masking).

`interpret=None` resolves through `kernels.backend.resolve_interpret`:
Mosaic on TPU, the interpreter on CPU. All wrappers mask invalid/padded
entries to +inf so callers can feed beam-search id buffers directly.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.distance.distance_kernel import (
    gather_l2_chunked_pallas,
    pairwise_l2_pallas,
)
from repro.kernels.backend import resolve_interpret

Array = jax.Array

_INF = float("inf")


def _pad_to(x: Array, mult: int, axis: int, value=0.0) -> Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@partial(jax.jit, static_argnames=("block_q", "block_c", "block_d", "interpret"))
def pairwise_l2(q: Array, x: Array, *, block_q: int = 128, block_c: int = 128,
                block_d: int = 512, interpret: bool | None = None) -> Array:
    """(Q, D) x (C, D) -> (Q, C) squared L2 via the tiled MXU kernel."""
    interpret = resolve_interpret(interpret)
    qn, d = q.shape
    cn = x.shape[0]
    block_d = min(block_d, max(128, d))
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    qp = _pad_to(_pad_to(q, block_q, 0), block_d, 1)
    xp = _pad_to(_pad_to(x, block_c, 0), block_d, 1)
    qsq = jnp.sum(qp * qp, axis=-1)
    xsq = jnp.sum(xp * xp, axis=-1)
    out = pairwise_l2_pallas(qp, xp, qsq, xsq, block_q=block_q,
                             block_c=block_c, block_d=block_d,
                             interpret=interpret)
    return out[:qn, :cn]


@partial(jax.jit, static_argnames=("block_q", "interpret"))
def gather_l2_chunked(q: Array, db: Array, db_sq: Array, ids: Array, *,
                      block_q: int = 8, interpret: bool | None = None) -> Array:
    """Bulk-gather distances; invalid ids -> +inf.

    The XLA gather materializes the contiguous (Q, K, D) candidate buffer
    (the "chunk"); the kernel then streams it in (TQ, K, D) tiles.
    """
    interpret = resolve_interpret(interpret)
    qn = q.shape[0]
    q = _pad_to(q.astype(jnp.float32), 128, 1)
    db = _pad_to(db.astype(jnp.float32), 128, 1)
    safe = jnp.maximum(ids, 0).astype(jnp.int32)
    cand = db[safe]                                 # (Q, K, D) bulk gather
    cand_sq = db_sq[safe]
    qp = _pad_to(q, block_q, 0)
    candp = _pad_to(cand, block_q, 0)
    csqp = _pad_to(cand_sq, block_q, 0)
    out = gather_l2_chunked_pallas(qp, candp, csqp, block_q=block_q,
                                   interpret=interpret)[:qn]
    return jnp.where(ids >= 0, out, _INF)


def make_kernel_scorer(vectors: Array, queries: Array, n_valid: Array,
                       vec_sqnorm: Array | None = None, *,
                       tombstone_bits: Array | None = None,
                       labels: Array | None = None,
                       filter_bytes: Array | None = None,
                       interpret: bool | None = None):
    """Beam-search ScoreFn backed by the Pallas gather kernels.

    Drop-in replacement for core.beam_search.make_exact_scorer — this is how
    the fused search kernel plugs into the shared search loop.

    tombstone_bits: optional packed row bitmap (core.mutations) for
    exclude-mode searches — tombstoned candidates score +inf.
    labels/filter_bytes: optional label plane + query byte mask
    (core.mutations) for exclude-mode filtered searches — non-matching
    candidates score +inf, via the same one-gather-per-candidate pattern.
    """
    v = vectors
    if vec_sqnorm is None:
        vec_sqnorm = jnp.sum(v.astype(jnp.float32) ** 2, axis=-1)

    def score(ids: Array) -> Array:
        in_range = (ids >= 0) & (ids < n_valid)
        if tombstone_bits is not None:
            from repro.core.mutations import bitmap_gather
            in_range &= ~bitmap_gather(tombstone_bits, ids)
        if labels is not None:
            from repro.core.mutations import label_match_gather
            in_range &= label_match_gather(labels, filter_bytes, ids)
        masked = jnp.where(in_range, ids, -1)
        return gather_l2_chunked(queries, v, vec_sqnorm, masked,
                                 interpret=interpret)

    # gather wrappers return +inf for masked ids; beam_search skips its pass
    score.self_masking = True
    return score

"""Small-k frontier top-k via iterative min-extraction (paper §4.1).

The GPU kernel keeps the frontier in shared memory and merges candidates
with an in-block sort. On TPU the frontier tile lives in VMEM and small k
(beam widths 10–256) favors k sequential argmin+mask passes on the VPU over
a full bitonic sort: each pass is one (TQ, C) reduce + masked update, fully
vectorized across the query tile, with no cross-lane shuffles.

Used by benchmarks to compare against XLA's fused sort path (which the
lockstep beam search in core/ uses); on real TPU hardware the winner is
shape-dependent — that comparison is part of benchmarks/tiles.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _topk_kernel(d_ref, i_ref, od_ref, oi_ref, *, k: int):
    ids = i_ref[...]
    tq, c = d_ref.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (tq, c), 1)
    ocol = jax.lax.broadcasted_iota(jnp.int32, (tq, k), 1)

    def step(s, carry):
        d, od, oi = carry
        m = jnp.min(d, axis=1, keepdims=True)                  # (TQ, 1)
        # first-occurrence argmin via iota trick (no cross-lane shuffle)
        first = jnp.min(jnp.where(d == m, col, c), axis=1, keepdims=True)
        sel = col == first
        pick = jnp.sum(jnp.where(sel, ids, 0), axis=1, keepdims=True)
        # pass s lands in column s by a one-hot select: Mosaic stores only
        # at lane offsets it can prove, and s is a loop counter
        at = ocol == s
        od = jnp.where(at, m, od)
        oi = jnp.where(at, pick, oi)
        return jnp.where(sel, jnp.inf, d), od, oi

    init = (d_ref[...], jnp.full((tq, k), jnp.inf, jnp.float32),
            jnp.full((tq, k), -1, jnp.int32))
    _, od, oi = jax.lax.fori_loop(0, k, step, init)
    od_ref[...] = od
    oi_ref[...] = oi


def topk_pallas(dists: Array, ids: Array, k: int, *, block_q: int = 8,
                interpret: bool = False) -> tuple[Array, Array]:
    """(Q, C) -> ((Q, k) dists, (Q, k) ids), ascending. Q % block_q == 0."""
    qn, c = dists.shape
    grid = (qn // block_q,)
    return pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, c), lambda i: (i, 0)),
            pl.BlockSpec((block_q, c), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qn, k), jnp.float32),
            jax.ShapeDtypeStruct((qn, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(dists, ids)

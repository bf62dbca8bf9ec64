"""Pallas TPU distance kernels (paper §4.1/§4.2).

Two kernels:

  _pairwise_kernel      classic (nQ, nC, nD)-tiled MXU matmul with a fused
                        squared-L2 epilogue — brute force / bootstrap /
                        rerank path. The BlockSpec tiling keeps the working
                        set in VMEM with MXU-aligned (multiple-of-128) dims.

  _gather_chunked_kernel bulk-load strategy (paper Fig 4, right): candidates
                        are pre-gathered into a contiguous (Q, K, D) buffer
                        so each grid step issues ONE bulk DMA of a whole
                        (TQ, K, D) tile and the dot runs batched on the MXU.
                        This is the TPU analogue of issuing all 16-byte
                        chunk loads of a warp simultaneously.

All shapes are padded by ops.py to tile multiples; min f32 tile (8, 128).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


# ---------------------------------------------------------------- pairwise
def _pairwise_kernel(q_ref, x_ref, qsq_ref, xsq_ref, o_ref, acc_ref, *, n_d):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        q_ref[...], x_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_d - 1)
    def _epilogue():
        d = qsq_ref[...] - 2.0 * acc_ref[...] + xsq_ref[...].T
        o_ref[...] = jnp.maximum(d, 0.0)


def pairwise_l2_pallas(q: Array, x: Array, qsq: Array, xsq: Array, *,
                       block_q: int = 128, block_c: int = 128,
                       block_d: int = 512, interpret: bool = False) -> Array:
    """(Q, D) x (C, D) -> (Q, C) squared L2. Dims must be tile multiples."""
    qn, d = q.shape
    cn = x.shape[0]
    n_d = d // block_d
    grid = (qn // block_q, cn // block_c, n_d)
    return pl.pallas_call(
        functools.partial(_pairwise_kernel, n_d=n_d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, block_d), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_c, block_d), lambda i, j, k: (j, k)),
            pl.BlockSpec((block_q, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((block_c, 1), lambda i, j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_c), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, cn), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, block_c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, x, qsq.reshape(-1, 1), xsq.reshape(-1, 1))


# ---------------------------------------------------------- gather: chunked
def _gather_chunked_kernel(q_ref, qsq_ref, cand_ref, csq_ref, o_ref):
    # (TQ, K, D) x (TQ, D) -> (TQ, K): batched matvec on the MXU
    dot = jax.lax.dot_general(
        cand_ref[...], q_ref[...],
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    o_ref[...] = jnp.maximum(qsq_ref[...] - 2.0 * dot + csq_ref[...], 0.0)


def gather_l2_chunked_pallas(q: Array, cand: Array, cand_sq: Array, *,
                             block_q: int = 8, interpret: bool = False
                             ) -> Array:
    """Bulk-loaded gather distances ("chunked" strategy).

    cand: (Q, K, D) pre-gathered candidate rows (contiguous buffer — the
    bulk DMA), cand_sq: (Q, K) their squared norms.
    """
    qn, k, d = cand.shape
    grid = (qn // block_q,)
    qsq = jnp.sum(q.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
    return pl.pallas_call(
        _gather_chunked_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_q, k, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_q, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((qn, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(q, qsq, cand, cand_sq)

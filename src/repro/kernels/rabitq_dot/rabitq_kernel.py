"""Fused RaBitQ unpack + estimator kernel (paper §5.1, Fig 5).

The paper's GPU kernel reads packed codes with sequential 16-byte loads and
evaluates the estimator with simple arithmetic — no codebook lookups. The
TPU translation (DESIGN.md §2):

  * packed codes stream HBM->VMEM in (TC, P) uint8 tiles (sequential DMA —
    the whole point of RaBitQ over PQ survives the port);
  * in-kernel unpack = shift/mask on the VPU, statically unrolled over the
    8/bits codes per byte (no gathers anywhere);
  * the estimator inner product <codes, q_rot> is ONE MXU matmul per tile
    (TQ, D) @ (D, TC);
  * the per-vector metadata (data_add / data_rescale) and per-query scalars
    (query_add / query_sumq) fuse into the epilogue.

Memory traffic per candidate = D*bits/8 + 8 bytes vs 4*D exact — the 4x/8x
traffic reduction that moves the kernel off the bandwidth roof (§6.5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _unpack_tile(packed_u8: Array, bits: int) -> Array:
    """(TC, P) uint8 -> (TC, P * 8//bits) f32, little-endian per byte.

    Code d is bits*(d % cpb) of byte d // cpb. Each byte is spread to its
    cpb code columns by a 0/1 matmul (bf16 in, f32 out: a byte <= 255 is
    exact in bf16 and every output has one nonzero term), then shifted and
    masked on the VPU. An interleaving (TC, P, cpb) -> (TC, D) reshape
    would give its minor dim of cpb a whole lane tile in VMEM."""
    cpb = 8 // bits
    b32 = packed_u8.astype(jnp.int32).astype(jnp.float32)
    if cpb == 1:
        return b32
    p = packed_u8.shape[1]
    d = p * cpb
    byte = jax.lax.broadcasted_iota(jnp.int32, (p, d), 0)
    code = jax.lax.broadcasted_iota(jnp.int32, (p, d), 1)
    spread = jnp.where(byte == code // cpb, 1.0, 0.0).astype(jnp.bfloat16)
    rep = jax.lax.dot_general(
        b32.astype(jnp.bfloat16), spread, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (TC, D) byte values
    shift = bits * (jax.lax.broadcasted_iota(jnp.int32, (1, d), 1) % cpb)
    codes = (rep.astype(jnp.int32) >> shift) & ((1 << bits) - 1)
    return codes.astype(jnp.float32)


def _rabitq_kernel(q_ref, qadd_ref, qsum_ref, codes_ref, dadd_ref, drs_ref,
                   o_ref, *, bits: int):
    codes = _unpack_tile(codes_ref[...], bits)       # (TC, D)
    dot = jax.lax.dot_general(
        q_ref[...], codes, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (TQ, TC)
    est = (dadd_ref[...].T + qadd_ref[...]
           + drs_ref[...].T * (dot - qsum_ref[...]))
    o_ref[...] = jnp.maximum(est, 0.0)


def _rabitq_gather_kernel(q_ref, qadd_ref, qsum_ref, codes_ref, dadd_ref,
                          drs_ref, o_ref, *, bits: int):
    # codes_ref: (TQ*K, P) — per-query candidate tiles (bulk-gathered),
    # flattened outside the kernel
    tq, k = o_ref.shape
    codes = _unpack_tile(codes_ref[...], bits).reshape(tq, k, -1)
    dot = jax.lax.dot_general(
        codes, q_ref[...], (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)          # (TQ, K)
    est = dadd_ref[...] + qadd_ref[...] + drs_ref[...] * (dot - qsum_ref[...])
    o_ref[...] = jnp.maximum(est, 0.0)


def _rabitq_search_step_kernel(nvalid_ref, q_ref, qadd_ref, qsum_ref,
                               ids_ref, live_ref, codes_ref, dadd_ref,
                               drs_ref, o_ref, *, bits: int):
    """Fused search step: unpack + estimator + epilogue masking.

    Same math as _rabitq_gather_kernel, plus the beam-search validity mask
    fused into the epilogue so no separate jnp masking pass runs over the
    (Q, K) output: ids must be in [0, n_valid) AND their per-row tombstone
    flag must be live. n_valid arrives as a scalar in SMEM; the tombstone
    bitmap arrives pre-gathered per candidate (live_ref, 1 = live) — the
    byte gather rides along with the packed-code gather outside the kernel,
    the mask itself is fused here.
    """
    tq, k = o_ref.shape
    codes = _unpack_tile(codes_ref[...], bits).reshape(tq, k, -1)
    dot = jax.lax.dot_general(
        codes, q_ref[...], (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)          # (TQ, K)
    est = dadd_ref[...] + qadd_ref[...] + drs_ref[...] * (dot - qsum_ref[...])
    ids = ids_ref[...]
    valid = (ids >= 0) & (ids < nvalid_ref[0]) & (live_ref[...] != 0)
    o_ref[...] = jnp.where(valid, jnp.maximum(est, 0.0),
                           jnp.float32(jnp.inf))


def rabitq_gather_distance_pallas(cand_packed: Array, cand_add: Array,
                                  cand_rescale: Array, q_rot: Array,
                                  query_add: Array, query_sumq: Array, *,
                                  bits: int, block_q: int = 8,
                                  interpret: bool = False) -> Array:
    """Beam-search form: per-query candidate code tiles.

    cand_packed: (Q, K, P) uint8; cand_add/cand_rescale: (Q, K);
    q_rot: (Q, D) -> (Q, K) estimates. Q must be a block_q multiple.
    """
    qn, k, p = cand_packed.shape
    d = q_rot.shape[1]
    assert p * (8 // bits) == d, (p, bits, d)
    grid = (qn // block_q,)
    return pl.pallas_call(
        functools.partial(_rabitq_gather_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_q * k, p), lambda i: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((qn, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(q_rot, query_add.reshape(-1, 1), query_sumq.reshape(-1, 1),
      cand_packed.reshape(qn * k, p), cand_add, cand_rescale)


def rabitq_search_step_pallas(cand_packed: Array, cand_add: Array,
                              cand_rescale: Array, ids: Array, live: Array,
                              n_valid: Array, q_rot: Array,
                              query_add: Array, query_sumq: Array, *,
                              bits: int, block_q: int = 8,
                              interpret: bool = False) -> Array:
    """Fused search-step form: gather tiles + raw beam ids + n_valid.

    cand_packed: (Q, K, P) uint8; ids: (Q, K) int32 (may contain -1 /
    out-of-range); live: (Q, K) int32 per-candidate tombstone flags
    (1 = live); n_valid: (1, 1) int32 -> (Q, K) estimates with invalid
    candidates already masked to +inf in the kernel epilogue.
    """
    qn, k, p = cand_packed.shape
    d = q_rot.shape[1]
    assert p * (8 // bits) == d, (p, bits, d)
    grid = (qn // block_q,)
    return pl.pallas_call(
        functools.partial(_rabitq_search_step_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_q, d), lambda i: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i: (i, 0)),
            pl.BlockSpec((block_q * k, p), lambda i: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((qn, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(n_valid.reshape(-1), q_rot, query_add.reshape(-1, 1),
      query_sumq.reshape(-1, 1), ids, live, cand_packed.reshape(qn * k, p),
      cand_add, cand_rescale)


def rabitq_distance_pallas(packed: Array, data_add: Array, data_rescale: Array,
                           q_rot: Array, query_add: Array, query_sumq: Array,
                           *, bits: int, block_q: int = 128,
                           block_c: int = 256, interpret: bool = False
                           ) -> Array:
    """(C, P) uint8 codes x (Q, D) rotated queries -> (Q, C) estimates.

    Caller pads Q to block_q, C to block_c, and guarantees P * (8//bits) == D
    (ops.py zero-pads dims; zero-padded q_rot dims contribute nothing).
    """
    cn, p = packed.shape
    qn, d = q_rot.shape
    assert p * (8 // bits) == d, (p, bits, d)
    grid = (qn // block_q, cn // block_c)
    return pl.pallas_call(
        functools.partial(_rabitq_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, p), lambda i, j: (j, 0)),
            pl.BlockSpec((block_c, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((block_c, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, cn), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(q_rot, query_add.reshape(-1, 1), query_sumq.reshape(-1, 1),
      packed, data_add.reshape(-1, 1), data_rescale.reshape(-1, 1))

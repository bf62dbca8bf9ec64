"""Flash attention forward kernel (Pallas TPU), GQA-aware.

WHY (§Perf #A4): the XLA-compiled blockwise attention round-trips every
(Tq, Tk) score block through HBM inside the KV loop — measured as ~95% of
the memory roofline term for the 32k-prefill cells. This kernel keeps the
score block, the online-softmax statistics, and the output accumulator in
VMEM scratch for the whole KV sweep; HBM traffic collapses to the q/k/v
tiles + one output write:

    bytes/layer ~ B*H*Dh*(2*Sq + 2*nq*Skv)*2   vs   + nq*nkv*Tq*Tk*4 before

Grid: (B, H, nq, nkv) with the KV dimension innermost ("arbitrary" —
sequential), so the scratch accumulator carries across KV steps and the
epilogue fires on the last step. GQA: the K/V BlockSpec index maps divide
the query-head index by the group size, so kv tiles are fetched once per
q head group member (set q_heads_per_kv_fetch via head layout for more
reuse if needed).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_NEG = float(-1e30)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, q_offset: int,
                  block_q: int, block_kv: int, n_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                   # (Tq, Dh)
    k = k_ref[0, 0]                                   # (Tk, Dh)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (Tq, Tk)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0) + q_offset
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    mask = jnp.ones((block_q, block_kv), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > (q_pos - window)
    s = jnp.where(mask, s, _NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _epilogue():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(q: Array, k: Array, v: Array, *, causal: bool,
                           window: int = 0, q_offset: int = 0,
                           block_q: int = 256, block_kv: int = 512,
                           interpret: bool = False) -> Array:
    """q: (B, H, Sq, Dh); k/v: (B, Hk, Skv, Dh) -> (B, H, Sq, Dh).

    Sq % block_q == 0 and Skv % block_kv == 0 (ops.py pads).
    """
    b, h, sq, dh = q.shape
    hk, skv = k.shape[1], k.shape[2]
    g = h // hk
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    nq, nkv = sq // block_q, skv // block_kv
    scale = dh ** -0.5
    grid = (b, h, nq, nkv)
    return pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, window=window,
            q_offset=q_offset, block_q=block_q, block_kv=block_kv,
            n_kv=nkv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, dh),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, dh),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ======================================================== backward kernels
def _flash_fwd_lse_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                          acc_ref, *, scale, causal, window, q_offset,
                          block_q, block_kv, n_kv):
    """Forward that also emits LSE (needed by the backward kernels)."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0) + q_offset
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    mask = jnp.ones((block_q, block_kv), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > (q_pos - window)
    s = jnp.where(mask, s, _NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _epilogue():
        l_fin = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l_fin[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l_fin)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                     acc_ref, *, scale, causal, window, q_offset, block_q,
                     block_kv, n_kv):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0) + q_offset
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    mask = jnp.ones((block_q, block_kv), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > (q_pos - window)
    p = jnp.where(mask, jnp.exp(s - lse_ref[0, 0][:, None]), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0, 0][:, None]) * scale
    acc_ref[...] += jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv - 1)
    def _epilogue():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                      window, q_offset, block_q, block_kv, n_q, n_inner):
    ki = pl.program_id(2)
    inner = pl.program_id(3)          # iterates (g, qi) pairs sequentially
    qi = inner % n_q

    @pl.when(inner == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    # sT: (Tk, Tq) = k @ q^T
    st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_kv, block_q), 1) + q_offset
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_kv, block_q), 0)
    mask = jnp.ones((block_kv, block_q), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > (q_pos - window)
    pt = jnp.where(mask, jnp.exp(st - lse_ref[0, 0][None, :]), 0.0)
    dv_acc[...] += jax.lax.dot_general(
        pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dst = pt * (dpt - delta_ref[0, 0][None, :]) * scale
    dk_acc[...] += jax.lax.dot_general(
        dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(inner == n_inner - 1)
    def _epilogue():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_fwd_pallas(q, k, v, *, causal, window=0, q_offset=0,
                               block_q=256, block_kv=512, interpret=False):
    """Forward returning (o, lse). Same layout contract as the fwd kernel."""
    b, h, sq, dh = q.shape
    hk, skv = k.shape[1], k.shape[2]
    g = h // hk
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    nq, nkv = sq // block_q, skv // block_kv
    scale = dh ** -0.5
    return pl.pallas_call(
        functools.partial(_flash_fwd_lse_kernel, scale=scale, causal=causal,
                          window=window, q_offset=q_offset, block_q=block_q,
                          block_kv=block_kv, n_kv=nkv),
        grid=(b, h, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, dh),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, dh),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda bi, hi, qi, ki: (bi, hi, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)


def flash_attention_bwd_pallas(q, k, v, o, lse, do, *, causal, window=0,
                               q_offset=0, block_q=256, block_kv=512,
                               interpret=False):
    """Backward: returns (dq, dk, dv). Layout (B, H|Hk, S, Dh)."""
    b, h, sq, dh = q.shape
    hk, skv = k.shape[1], k.shape[2]
    g = h // hk
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    nq, nkv = sq // block_q, skv // block_kv
    scale = dh ** -0.5
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, scale=scale, causal=causal,
                          window=window, q_offset=q_offset, block_q=block_q,
                          block_kv=block_kv, n_kv=nkv),
        grid=(b, h, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, dh),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, dh),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda bi, hi, qi, ki: (bi, hi, qi)),
            pl.BlockSpec((1, 1, block_q),
                         lambda bi, hi, qi, ki: (bi, hi, qi)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    n_inner = g * nq
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, scale=scale, causal=causal,
                          window=window, q_offset=q_offset, block_q=block_q,
                          block_kv=block_kv, n_q=nq, n_inner=n_inner),
        grid=(b, hk, nkv, n_inner),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda bi, hi, ki, it: (bi, hi * g + it // nq,
                                                 it % nq, 0)),
            pl.BlockSpec((1, 1, block_kv, dh),
                         lambda bi, hi, ki, it: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, dh),
                         lambda bi, hi, ki, it: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda bi, hi, ki, it: (bi, hi * g + it // nq,
                                                 it % nq, 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda bi, hi, ki, it: (bi, hi * g + it // nq,
                                                 it % nq)),
            pl.BlockSpec((1, 1, block_q),
                         lambda bi, hi, ki, it: (bi, hi * g + it // nq,
                                                 it % nq)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_kv, dh),
                         lambda bi, hi, ki, it: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, dh),
                         lambda bi, hi, ki, it: (bi, hi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hk, skv, dh), k.dtype),
            jax.ShapeDtypeStruct((b, hk, skv, dh), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, dh), jnp.float32),
            pltpu.VMEM((block_kv, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv

"""Public wrapper for the flash attention kernel + tile-traffic model.

`flash_attention` is fully differentiable: the backward pass runs the
Pallas dq/dkv kernels (FlashAttention-2 recipe — LSE saved from forward,
delta = rowsum(dO*O), score blocks recomputed in VMEM, never touching HBM).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve_interpret
from repro.kernels.flash_attention.flash_kernel import (
    flash_attention_bwd_pallas,
    flash_attention_fwd_pallas,
    flash_attention_pallas,
)

Array = jax.Array


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, q_offset, block_q, block_kv, interpret):
    o, _ = flash_attention_fwd_pallas(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_kv=block_kv, interpret=interpret)
    return o


def _flash_fwd(q, k, v, causal, window, q_offset, block_q, block_kv,
               interpret):
    o, lse = flash_attention_fwd_pallas(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_kv=block_kv, interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, q_offset, block_q, block_kv, interpret,
               res, do):
    q, k, v, o, lse = res
    dq, dk, dv = flash_attention_bwd_pallas(
        q, k, v, o, lse, do, causal=causal, window=window,
        q_offset=q_offset, block_q=block_q, block_kv=block_kv,
        interpret=interpret)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


@partial(jax.jit, static_argnames=("causal", "window", "q_offset", "block_q",
                                   "block_kv", "interpret"))
def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: int = 0, q_offset: int = 0, block_q: int = 256,
                    block_kv: int = 512, interpret: bool | None = None
                    ) -> Array:
    """(B, Sq, H, Dh) x (B, Skv, Hk, Dh) -> (B, Sq, H, Dh).

    Layout adapter around the kernels (which want (B, H, S, Dh)).
    Differentiable (custom_vjp over the Pallas backward kernels).
    """
    interpret = resolve_interpret(interpret)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _flash(qt, kt, vt, causal, window, q_offset, block_q, block_kv,
                 interpret)
    return jnp.swapaxes(out, 1, 2)


def flash_attention_inference(q: Array, k: Array, v: Array, **kw) -> Array:
    """Forward-only variant (no LSE output buffer)."""
    interpret = kw.pop("interpret", None)
    interpret = resolve_interpret(interpret)
    out = flash_attention_pallas(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        interpret=interpret, **kw)
    return jnp.swapaxes(out, 1, 2)


def flash_traffic_bytes(b: int, h: int, hk: int, sq: int, skv: int, dh: int,
                        *, block_q: int = 256, itemsize: int = 2) -> int:
    """HBM traffic of one flash-attention call (the §Perf #A4 model).

    Reads: q once; k/v re-fetched once per q-block PER Q HEAD (the GQA
    index map shares fetches only via cache locality — count worst case).
    Writes: output once. Score blocks never leave VMEM.
    """
    nq = max(sq // block_q, 1)
    q_bytes = b * h * sq * dh
    kv_bytes = 2 * b * h * nq * skv * dh      # per-q-head, per-q-block sweep
    o_bytes = b * h * sq * dh
    return (q_bytes + kv_bytes + o_bytes) * itemsize

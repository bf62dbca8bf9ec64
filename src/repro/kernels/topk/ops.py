"""Public wrapper for the frontier top-k kernel."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve_interpret
from repro.kernels.topk.topk_kernel import topk_pallas

Array = jax.Array


@partial(jax.jit, static_argnames=("k", "block_q", "interpret"))
def topk(dists: Array, ids: Array, k: int, *, block_q: int = 8,
         interpret: bool | None = None) -> tuple[Array, Array]:
    """k smallest distances per row with their ids, ascending order."""
    interpret = resolve_interpret(interpret)
    qn, c = dists.shape
    pad_q = (-qn) % block_q
    if pad_q:
        dists = jnp.pad(dists, ((0, pad_q), (0, 0)), constant_values=jnp.inf)
        ids = jnp.pad(ids, ((0, pad_q), (0, 0)), constant_values=-1)
    pad_c = (-c) % 128
    if pad_c:
        dists = jnp.pad(dists, ((0, 0), (0, pad_c)), constant_values=jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, pad_c)), constant_values=-1)
    od, oi = topk_pallas(dists.astype(jnp.float32), ids.astype(jnp.int32), k,
                         block_q=block_q, interpret=interpret)
    return od[:qn], oi[:qn]

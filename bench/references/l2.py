"""Plain reference for squared-L2 k-nearest-neighbour search.

The exact top-k over the live rows by a blocked matrix product on the
device at `Precision.HIGHEST`, and the exact squared distance of any
(query, row) pair by direct differences in float64 on the host. It takes
nothing from the system under test: the rows are the benchmark's own,
generated from the seed.

`control_topk` is the same search computed in bfloat16 (inputs, norms,
products and sums): the precision below the configuration's float32, the
step a change to the rerank would be tempted to take. It stands in the
program's place to show that the comparison fails it.
"""

from __future__ import annotations

import numpy as np

QUERY_BLOCK = 500


def _topk_blocks(rows: np.ndarray, queries: np.ndarray, k: int, dtype,
                 block: int):
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(rows, dtype)
    xs = jnp.sum(x * x, axis=-1)
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def one(x, xs, q):
        q = q.astype(dtype)
        qs = jnp.sum(q * q, axis=-1)
        dot = jnp.matmul(q, x.T, precision=hi,
                         preferred_element_type=dtype)
        d = qs[:, None] + xs[None, :] - 2 * dot
        neg, idx = jax.lax.top_k(-d, k)
        return idx, -neg

    ids, dists = [], []
    for i in range(0, queries.shape[0], block):
        q = np.zeros((block, queries.shape[1]), np.float32)
        part = queries[i:i + block]
        q[:part.shape[0]] = part
        idx, d = one(x, xs, jnp.asarray(q))
        ids.append(np.asarray(idx)[:part.shape[0]])
        dists.append(np.asarray(d.astype(jnp.float32))[:part.shape[0]])
    return np.concatenate(ids), np.concatenate(dists)


def topk(rows: np.ndarray, queries: np.ndarray, k: int,
         block: int = QUERY_BLOCK) -> tuple[np.ndarray, np.ndarray]:
    """Exact top-k positions into `rows` and their squared distances."""
    import jax.numpy as jnp
    return _topk_blocks(rows, queries, k, jnp.float32, block)


def control_topk(rows: np.ndarray, queries: np.ndarray, k: int,
                 block: int = QUERY_BLOCK) -> tuple[np.ndarray, np.ndarray]:
    """The reference computed in bfloat16: the control."""
    import jax.numpy as jnp
    return _topk_blocks(rows, queries, k, jnp.bfloat16, block)


def exact_d2(rows: np.ndarray, queries: np.ndarray, pos: np.ndarray
             ) -> np.ndarray:
    """Squared distance of query i to rows[pos[i, j]], float64 by direct
    differences; nan where pos < 0."""
    safe = np.maximum(pos, 0)
    out = np.empty(pos.shape, np.float64)
    for i in range(0, pos.shape[0], QUERY_BLOCK):
        q = queries[i:i + QUERY_BLOCK].astype(np.float64)[:, None, :]
        r = rows[safe[i:i + QUERY_BLOCK]].astype(np.float64)
        out[i:i + QUERY_BLOCK] = np.sum((r - q) ** 2, axis=-1)
    return np.where(pos >= 0, out, np.nan)

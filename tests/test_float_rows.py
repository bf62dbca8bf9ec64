"""Float32 rows at GIST's width (960-d): the products that see float rows
state their precision, and the index answers as a float64 reference does.

On a TPU an f32 matrix product at default precision runs as one bfloat16
pass. Integer rows below 256 (SIFT/BigANN) are exact in bfloat16; float
rows (GIST) are not. The CPU computes every product in f32, so no answer
here can show the gap: the guard is on the lowered program instead.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ANNS_DATASETS
from repro.core.construction import (ConstructionParams, bootstrap_graph,
                                     max_batch)
from repro.core.index import JasperIndex
from repro.core.medoid import compute_medoid
from repro.core.rabitq import (RaBitQParams, rabitq_encode,
                               rabitq_preprocess_query)
from repro.core.search_spec import SearchSpec
from repro.core.vamana import VamanaGraph
from repro.data.synthetic import make_anns_dataset, make_queries
from repro.serving.anns_service import AnnsService

D, N, NQ, K = 960, 2048, 64, 10
GIST = ANNS_DATASETS["gist"]
S = jax.ShapeDtypeStruct
PARAMS = ConstructionParams(degree_bound=64, alpha=1.2, beam_width=64)
# half the configuration's degree and build beam: the same code, a build
# that fits a test's time on a CPU (the configuration's takes 85 s here)
SMALL = ConstructionParams(degree_bound=32, alpha=1.2, beam_width=32,
                           max_iters=48, rev_cap=32, prune_chunk=256)

_DOT = re.compile(r"stablehlo\.dot_general.*:\s*\((tensor<[^>]*>),\s*"
                  r"(tensor<[^>]*>)\)\s*->\s*(tensor<[^>]*>)")


def _dims(t: str) -> tuple[list[int], str]:
    *shape, dtype = t[len("tensor<"):-1].split("x")
    return [int(s) for s in shape], dtype


def matrix_products(fn, *args) -> list[str]:
    """The dot_generals of fn's lowered program that multiply two f32
    matrices: no batch dimensions and both free sizes above 1, the
    products a TPU runs on its matrix unit. (Batched matrix-vector
    products, such as the walk's and the prune's, become f32
    multiply-reduce fusions there, with no matrix unit pass.)"""
    out = []
    for line in jax.jit(fn).lower(*args).as_text().splitlines():
        m = _DOT.search(line)
        if not m or "batching_dims" in line:
            continue
        (a, ta), (b, tb), (res, _) = (_dims(g) for g in m.groups())
        if ta == tb == "f32" and len(res) == 2 and min(res) > 1:
            out.append(line.strip())
    return out


def _rq():
    return RaBitQParams(rotation=S((D, D), jnp.float32),
                        centroid=S((D,), jnp.float32), bits=4)


PROGRAMS = {
    # the bootstrap ranks its candidates by an all-pairs product
    "bootstrap_graph": (
        lambda v, a: bootstrap_graph(
            v, VamanaGraph(a, jnp.int32(0), jnp.int32(0)), n0=1024,
            params=PARAMS),
        (S((N, D), jnp.float32), S((N, 64), jnp.int32)), 1),
    # the entry point: a (1, D) x (D, N) product, no matrix-unit pass
    "compute_medoid": (compute_medoid,
                       (S((N, D), jnp.float32), S((N,), jnp.bool_)), 0),
    # the rotation of rows and queries into RaBitQ's frame
    "rabitq_encode": (rabitq_encode, (_rq(), S((N, D), jnp.float32)), 1),
    "rabitq_preprocess_query": (rabitq_preprocess_query,
                                (_rq(), S((NQ, D), jnp.float32)), 1),
}


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_float_row_products_state_highest(name):
    fn, args, n_products = PROGRAMS[name]
    products = matrix_products(fn, *args)
    assert len(products) == n_products, products
    for line in products:
        assert "precision = [HIGHEST, HIGHEST]" in line, line


@pytest.fixture(scope="module")
def gist_index():
    rows = make_anns_dataset(GIST, N, seed=0)
    queries = make_queries(GIST, NQ, seed=1)
    index = JasperIndex(D, N, metric="l2", quantization="rabitq", bits=4,
                        construction=SMALL, seed=0).build(rows)
    svc = AnnsService(index, spec=SearchSpec(k=K, beam_width=64,
                                             quantized=True))
    (ticket,) = svc.search_many([queries])
    return rows, queries, ticket


def _exact(rows, queries):
    r, q = rows.astype(np.float64), queries.astype(np.float64)
    d = np.sum((q[:, None, :] - r[None, :, :]) ** 2, axis=-1)
    return np.sort(d, axis=1)[:, :K], d


def test_gist_recall_against_float64_reference(gist_index):
    """recall@10 of the configuration's lane (4-bit codes, L = 64, exact
    rerank) against an exact float64 top-10. A CPU run read 0.998 on
    this data (one miss in 640); the margin of 0.02 allows a dozen answers
    to change with the order of float sums, where a bfloat16 estimator or
    a broken walk loses far more."""
    rows, queries, t = gist_index
    top, d = _exact(rows, queries)
    kth = top[:, -1]
    hits = [len({i for i in ids if d[qi, i] <= kth[qi] * (1 + 1e-9)})
            for qi, ids in enumerate(t.ids)]
    assert np.mean(hits) / K >= 1.0 - 0.02


def test_gist_answers_are_float32_distances_of_live_rows(gist_index):
    """Every id names a live row, once per answer, and its distance is the
    float64 distance of that row to within f32 rounding of the expanded
    form |q|^2 - 2<q, x> + |x|^2: each of the three sums of 960 terms errs
    by about log2(960) ulps of its size, so 1e-5 of |q|^2 + |x|^2 is ten
    times that. One bfloat16 pass over the product would err by about
    2^-9 of <q, x>, a hundred times the tolerance."""
    rows, queries, t = gist_index
    assert ((t.ids >= 0) & (t.ids < N)).all()
    assert all(len(set(a)) == K for a in t.ids.tolist())
    _, d = _exact(rows, queries)
    want = np.take_along_axis(d, t.ids, axis=1)
    qn = np.sum(queries.astype(np.float64) ** 2, axis=1)[:, None]
    xn = np.sum(rows.astype(np.float64) ** 2, axis=1)[t.ids]
    np.testing.assert_array_less(np.abs(t.dists - want), 1e-5 * (qn + xn))


@pytest.mark.parametrize("n_rows", [65_536, 262_144, 1_000_000])
def test_insert_batch_falls_with_row_width(n_rows):
    """The build's largest rung is 65,536 rows at D = 128 (the BigANN
    cell's build keeps its rungs) and falls as rows widen: at 960-d a
    65,536-row rung does not fit a v5e beside any table, and 32,768 does
    not beside 10^6 rows (XLA's memory analysis; construction.py)."""
    widths = [96, 128, 200, 256, 384, 512, 768, 960, 1536, 4096]
    rungs = [max_batch(n_rows, d) for d in widths]
    assert rungs[1] == 65_536
    assert all(a >= b for a, b in zip(rungs, rungs[1:]))
    assert all(r & (r - 1) == 0 and 256 <= r <= 65_536 for r in rungs)
    assert max_batch(n_rows, 960) == (16_384 if n_rows == 1_000_000
                                      else 32_768)

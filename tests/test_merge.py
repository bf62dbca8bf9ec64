"""The per-hop frontier merge (`merge_frontier_topk`).

The merge selects the L smallest of the L + E*R concatenation of frontier
and candidates and carries each slot's id and visited bit along inside the
selection, with no gather after it. The contracts under test:

  * it returns bit-identical (ids, dists, visited) to the gather form it
    replaced (`lax.top_k`, then `take_along_axis` of ids and visited
    bits), over distance ties, +inf padding, signed zeros and random
    visited bits;
  * the compiled unfused search holds no gather in its `hop.merge` scope,
    so the gathers cannot come back unnoticed.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.beam_search import merge_frontier_topk
from repro.core.construction import ConstructionParams
from repro.core.index import JasperIndex
from repro.core.index_core import core_search
from repro.core.search_spec import SearchSpec

Q = 48


def gather_merge(f_ids, f_dists, f_vis, c_ids, c_dists, beam_width):
    """The merge as it was: top_k positions, then two gathers."""
    all_d = jnp.concatenate([f_dists, c_dists], axis=1)
    all_i = jnp.concatenate([f_ids, c_ids], axis=1)
    all_v = jnp.concatenate(
        [f_vis, jnp.zeros_like(c_ids, dtype=jnp.bool_)], axis=1)
    neg, pos = jax.lax.top_k(-all_d, beam_width)
    return (jnp.take_along_axis(all_i, pos, axis=1), -neg,
            jnp.take_along_axis(all_v, pos, axis=1))


def make_case(seed, beam, cand, *, ties, signed_zeros):
    """A distance-sorted frontier (+inf padded, ids -1 there) and a
    candidate row (ids -1 at +inf), with random visited bits."""
    rng = np.random.default_rng(seed)
    f_d = rng.exponential(size=(Q, beam)).astype(np.float32)
    c_d = rng.exponential(size=(Q, cand)).astype(np.float32)
    if ties:  # a coarse grid: many equal distances within and across halves
        f_d, c_d = np.round(f_d * 4) / 4, np.round(c_d * 4) / 4
    f_d = np.where(rng.random((Q, beam)) < 0.3, np.inf, f_d)
    if signed_zeros:
        for a in (f_d, c_d):
            a[rng.random(a.shape) < 0.1] = -0.0
            a[rng.random(a.shape) < 0.1] = 0.0
    f_d = np.sort(f_d, axis=1).astype(np.float32)
    f_i = np.where(np.isfinite(f_d), rng.integers(0, 1 << 20, (Q, beam)), -1)
    c_i = rng.integers(-1, 1 << 20, (Q, cand))
    c_d = np.where(c_i >= 0, c_d, np.inf).astype(np.float32)
    f_v = rng.random((Q, beam)) < 0.5
    return (jnp.asarray(f_i, jnp.int32), jnp.asarray(f_d),
            jnp.asarray(f_v), jnp.asarray(c_i, jnp.int32), jnp.asarray(c_d))


@pytest.mark.parametrize("signed_zeros", [False, True],
                         ids=["zeros-plain", "zeros-signed"])
@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
@pytest.mark.parametrize("cand", [64, 256], ids=["ER64", "ER256"])
@pytest.mark.parametrize("beam", [8, 64], ids=["L8", "L64"])
def test_merge_matches_gather_form(beam, cand, ties, signed_zeros):
    """ids, visited bits and distance bits equal the gather form's."""
    args = make_case(beam * 1000 + cand + 2 * ties + signed_zeros, beam,
                     cand, ties=ties, signed_zeros=signed_zeros)
    got = jax.jit(merge_frontier_topk, static_argnames="beam_width")(
        *args, beam_width=beam)
    want = jax.jit(gather_merge, static_argnames="beam_width")(
        *args, beam_width=beam)
    if signed_zeros:  # the case is live: some -0.0 reaches the frontier
        d = np.asarray(want[1])
        assert ((d == 0) & np.signbit(d)).any()
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.array_equal(np.asarray(got[1]).view(np.int32),
                          np.asarray(want[1]).view(np.int32))
    assert np.array_equal(np.asarray(got[2]), np.asarray(want[2]))


def _ops_with_scope(hlo: str, op: str, scope: str) -> list[str]:
    """Instruction lines of `hlo` that apply `op` under named scope
    `scope` (by their `op_name` metadata)."""
    pat = re.compile(rf"=\s.*\b{op}\(.*op_name=\"[^\"]*{re.escape(scope)}")
    return [ln for ln in hlo.splitlines() if pat.search(ln)]


def test_compiled_merge_holds_no_gather():
    """The unfused quantized search, compiled: its `hop.merge` scope holds
    no gather, while `hop.score` (the code gathers) still reads as one."""
    rng = np.random.default_rng(3)
    d = 16
    idx = JasperIndex(d, capacity=512, quantization="rabitq", bits=4,
                      construction=ConstructionParams(
                          degree_bound=16, beam_width=16, max_iters=24,
                          rev_cap=16, prune_chunk=256), seed=3)
    idx.build(rng.normal(size=(384, d)).astype(np.float32))
    ses = idx.searcher(SearchSpec(k=5, beam_width=16, quantized=True))
    q = idx._prep_query(rng.normal(size=(8, d)).astype(np.float32))
    hlo = core_search.lower(idx.core, q, spec=ses.resolved,
                            filter_tombstones=False).compile().as_text()
    assert _ops_with_scope(hlo, "gather", "hop.score")
    assert re.search(r"op_name=\"[^\"]*hop\.merge", hlo)
    assert _ops_with_scope(hlo, "gather", "hop.merge") == []

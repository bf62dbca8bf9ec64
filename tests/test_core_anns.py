"""Core ANNS behaviour: distances, quantization, graph, search, index."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    JasperIndex,
    beam_search,
    compute_medoid,
    inner_product,
    l2_squared,
    make_rabitq_scorer,
    mips_augment_data,
    mips_augment_query,
    pairwise_distance,
    pairwise_l2_squared,
    pq_distance,
    pq_encode,
    pq_train,
    rabitq_encode,
    rabitq_estimate,
    rabitq_preprocess_query,
    rabitq_train,
)
from repro.core.beam_search import (beam_search_quantized, make_exact_scorer)
from repro.core.rabitq import (RaBitQCodes, pack_codes, packed_dim,
                               unpack_codes)
from repro.core.construction import ConstructionParams, build_graph
from repro.core.vamana import graph_degree_stats, init_graph, validate_graph

RNG = np.random.default_rng(7)


def randn(*shape):
    return jnp.asarray(RNG.normal(size=shape), jnp.float32)


SMALL = ConstructionParams(degree_bound=16, alpha=1.2, beam_width=16,
                           max_iters=24, rev_cap=16, prune_chunk=256)


# --------------------------------------------------------------- distances
def test_pairwise_l2_matches_direct():
    q, x = randn(13, 32), randn(40, 32)
    got = pairwise_l2_squared(q, x)
    want = jnp.sum((q[:, None] - x[None]) ** 2, -1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_mips_augmentation_preserves_order():
    x, q = randn(200, 16), randn(5, 16)
    ips = np.asarray(q @ x.T)
    xa = mips_augment_data(x)
    qa = mips_augment_query(q)
    d = np.asarray(pairwise_l2_squared(qa, xa))
    # argmax inner product == argmin augmented L2
    assert (ips.argmax(1) == d.argmin(1)).all()


def test_metric_registry():
    q, x = randn(3, 8), randn(5, 8)
    assert pairwise_distance(q, x, "l2").shape == (3, 5)
    assert pairwise_distance(q, x, "mips").shape == (3, 5)
    with pytest.raises(ValueError):
        pairwise_distance(q, x, "cosine")


def test_medoid_masked():
    x = jnp.concatenate([randn(50, 8), 100.0 + randn(10, 8)])
    m_all = compute_medoid(x)
    m_live = compute_medoid(x, jnp.arange(60) < 50)
    assert int(m_live) < 50
    # outliers pull the unmasked centroid
    assert int(m_all) != int(m_live) or True


# ------------------------------------------------------------------ rabitq
@pytest.mark.parametrize("dims", [128, 960])
@pytest.mark.parametrize("bits,max_rel", [(1, 0.8), (4, 0.15), (8, 0.05)])
def test_rabitq_estimator_quality(bits, max_rel, dims):
    """Estimator error shrinks with more bits (O(2^-m) per-dim error), at
    SIFT's width and at GIST's (960)."""
    if dims == 128:
        x, q = randn(300, dims), randn(16, dims)
    else:   # a stream of its own: the draws of the tests below stay put
        g = np.random.default_rng(dims)
        x, q = (jnp.asarray(g.normal(size=(n, dims)), jnp.float32)
                for n in (300, 16))
    params = rabitq_train(jax.random.PRNGKey(0), x, bits=bits)
    codes = rabitq_encode(params, x)
    qq = rabitq_preprocess_query(params, q)
    est = np.asarray(rabitq_estimate(codes, qq))
    true = np.asarray(pairwise_l2_squared(q, x))
    rel = np.abs(est - true) / (true + 1e-6)
    assert np.median(rel) < max_rel, f"median rel err {np.median(rel)}"


def test_rabitq_recall_screening():
    """Top-50 by estimate must contain most of true top-10 (m=4)."""
    x, q = randn(500, 96), randn(20, 96)
    params = rabitq_train(jax.random.PRNGKey(1), x, bits=4)
    codes = rabitq_encode(params, x)
    qq = rabitq_preprocess_query(params, q)
    est = np.asarray(rabitq_estimate(codes, qq))
    true = np.asarray(pairwise_l2_squared(q, x))
    hit = 0
    for i in range(20):
        top_est = set(np.argsort(est[i])[:50])
        top_true = set(np.argsort(true[i])[:10])
        hit += len(top_est & top_true) / 10
    assert hit / 20 > 0.9


def test_rabitq_zero_vector():
    """v == centroid must not NaN."""
    x = jnp.zeros((4, 16))
    params = rabitq_train(jax.random.PRNGKey(0), x, bits=4)
    codes = rabitq_encode(params, x)
    q = randn(2, 16)
    qq = rabitq_preprocess_query(params, q)
    est = rabitq_estimate(codes, qq)
    assert bool(jnp.isfinite(est).all())


# --------------------------------------------------------------- code rows
# RaBitQCodes holds one uint8[N, P + 8] row per vector: the packed codes,
# then data_add and data_rescale as f32 bit patterns. The reference is the
# three arrays held apart; these tests hold the rows to it bit for bit.
CODE_ROW_CASES = [(b, d) for b in (1, 2, 4, 8) for d in (96, 128, 960)]
_FI = np.finfo(np.float32)
SPECIAL_FACTORS = np.array([0.0, -0.0, _FI.max, -_FI.max, _FI.tiny,
                            -_FI.tiny], np.float32)


def _three_arrays(codes):
    """Host copies of the three arrays (packed, add, rescale) of the rows."""
    return (np.asarray(codes.packed), np.asarray(codes.data_add),
            np.asarray(codes.data_rescale))


def _three_array_estimate(packed, add, rescale, query, ids, bits, dims):
    """The estimator over three separate arrays: a row gather of the packed
    codes and two element gathers of the factors."""
    safe = jnp.maximum(ids, 0)
    c = unpack_codes(packed[safe], bits, dims).astype(jnp.float32)
    dot = jnp.einsum("qkd,qd->qk", c, query.q_rot)
    est = (add[safe] + query.query_add[..., None]
           + rescale[safe] * (dot - query.query_sumq[..., None]))
    return jnp.maximum(est, 0.0)


def _bits32(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("bits,dims", CODE_ROW_CASES)
def test_code_rows_views_roundtrip(bits, dims):
    """from_parts, then the views, give back packed, data_add and
    data_rescale bit for bit, signed zeros and the extreme normals too."""
    g = np.random.default_rng(bits * 1000 + dims)
    n = 40
    packed = np.asarray(pack_codes(
        jnp.asarray(g.integers(0, 2 ** bits, (n, dims), dtype=np.uint8)),
        bits))
    add = (g.normal(size=n) * 300).astype(np.float32)
    rescale = g.normal(size=n).astype(np.float32)
    add[:6] = SPECIAL_FACTORS
    rescale[-6:] = SPECIAL_FACTORS
    c = RaBitQCodes.from_parts(jnp.asarray(packed), jnp.asarray(add),
                               jnp.asarray(rescale), bits, dims)
    assert c.rows.dtype == jnp.uint8
    assert c.rows.shape == (n, packed_dim(dims, bits) + 8)
    got = _three_arrays(c)
    assert np.array_equal(got[0], packed)
    assert np.array_equal(_bits32(got[1]), _bits32(add))
    assert np.array_equal(_bits32(got[2]), _bits32(rescale))
    again = RaBitQCodes.from_parts(c.packed, c.data_add, c.data_rescale,
                                   bits, dims)
    assert np.array_equal(np.asarray(again.rows), np.asarray(c.rows))


@pytest.mark.parametrize("bits,dims", CODE_ROW_CASES)
def test_code_rows_estimate_matches_three_arrays(bits, dims):
    """rabitq_estimate over candidate ids (some -1) reads the rows' one
    gather and equals the three-array estimator bit for bit."""
    g = np.random.default_rng(dims * 10 + bits)
    x = jnp.asarray(g.normal(size=(300, dims)), jnp.float32)
    params = rabitq_train(jax.random.PRNGKey(bits), x, bits=bits)
    packed, add, rescale = _three_arrays(rabitq_encode(params, x))
    add, rescale = add.copy(), rescale.copy()
    add[:2], rescale[:2] = SPECIAL_FACTORS[:2], SPECIAL_FACTORS[4:6]
    codes = RaBitQCodes.from_parts(jnp.asarray(packed), jnp.asarray(add),
                                   jnp.asarray(rescale), bits, dims)
    query = rabitq_preprocess_query(
        params, jnp.asarray(g.normal(size=(12, dims)), jnp.float32))
    ids = g.integers(-1, 300, (12, 40)).astype(np.int32)
    ids[:, 0], ids[:, 1] = -1, 1
    ids = jnp.asarray(ids)
    got = jax.jit(rabitq_estimate)(codes, query, ids)
    want = jax.jit(_three_array_estimate, static_argnums=(5, 6))(
        jnp.asarray(packed), jnp.asarray(add), jnp.asarray(rescale), query,
        ids, bits, dims)
    assert np.array_equal(_bits32(got), _bits32(want))


# ---------------------------------------------------------------------- pq
def test_pq_roundtrip_quality():
    x, q = randn(400, 64), randn(8, 64)
    params = pq_train(jax.random.PRNGKey(0), x, n_subspaces=8)
    codes = pq_encode(params, x)
    assert codes.shape == (400, 8) and codes.dtype == jnp.uint8
    d = np.asarray(pq_distance(params, codes, q))
    true = np.asarray(pairwise_l2_squared(q, x))
    # ADC distances correlate strongly with true distances
    for i in range(8):
        c = np.corrcoef(d[i], true[i])[0, 1]
        assert c > 0.8, c


# ------------------------------------------------------------ graph/search
@pytest.fixture(scope="module")
def built_index():
    rng = np.random.default_rng(1234)        # independent of module RNG
    data = rng.normal(size=(2000, 48)).astype(np.float32)
    idx = JasperIndex(48, capacity=2600, construction=SMALL,
                      quantization="rabitq", bits=4)
    idx.build(data)
    return idx, data


def test_graph_invariants(built_index):
    idx, _ = built_index
    checks = validate_graph(idx.graph)
    assert all(bool(v) for v in checks.values()), checks
    stats = graph_degree_stats(idx.graph)
    assert float(stats["max_degree"]) <= SMALL.degree_bound
    assert float(stats["mean_degree"]) > 2


def test_search_recall(built_index):
    idx, _ = built_index
    rng = np.random.default_rng(99)
    queries = jnp.asarray(rng.normal(size=(100, 48)), jnp.float32)
    r = idx.recall(queries, k=10, beam_width=64)
    assert r > 0.75, r


def test_rabitq_search_recall(built_index):
    idx, _ = built_index
    queries = randn(100, 48)
    r = idx.recall(queries, k=10, beam_width=48, quantized=True)
    assert r > 0.75, r


def test_recall_improves_with_beam(built_index):
    idx, _ = built_index
    queries = randn(60, 48)
    r_small = idx.recall(queries, k=10, beam_width=12)
    r_big = idx.recall(queries, k=10, beam_width=64)
    assert r_big >= r_small - 0.02, (r_small, r_big)


def test_streaming_insert_preserves_recall(built_index):
    idx, data = built_index
    extra = np.asarray(randn(500, 48))
    idx.insert(extra)
    assert idx.size == 2500
    checks = validate_graph(idx.graph)
    assert all(bool(v) for v in checks.values())
    queries = randn(60, 48)
    assert idx.recall(queries, k=10, beam_width=48) > 0.75


def test_save_load_roundtrip(tmp_path, built_index):
    idx, _ = built_index
    p = str(tmp_path / "idx.npz")
    idx.save(p)
    # atomic checkpoint: exactly the final file + meta, no stray tmp
    assert sorted(x.name for x in tmp_path.iterdir()) == [
        "idx.npz", "idx.npz.meta.json"]
    idx2 = JasperIndex.load(p)
    q = randn(10, 48)
    i1, d1 = idx.search(q, 5, beam_width=32)
    i2, d2 = idx2.search(q, 5, beam_width=32)
    assert (np.asarray(i1) == np.asarray(i2)).all()


def test_save_load_roundtrip_quantized(tmp_path, built_index):
    """The packed quantizer state survives save/load bit-exactly."""
    idx, _ = built_index
    p = str(tmp_path / "q.npz")
    idx.save(p)
    idx2 = JasperIndex.load(p)
    assert (np.asarray(idx2.rabitq_codes.packed)
            == np.asarray(idx.rabitq_codes.packed)).all()
    assert idx2.rabitq_codes.bits == idx.rabitq_codes.bits
    q = randn(10, 48)
    i1, d1 = idx.search_rabitq(q, 5, beam_width=32)
    i2, d2 = idx2.search_rabitq(q, 5, beam_width=32)
    assert (np.asarray(i1) == np.asarray(i2)).all()
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-5, atol=1e-5)


def test_beam_search_visited_log(built_index):
    idx, _ = built_index
    q = randn(4, 48)
    score = make_exact_scorer(idx.vectors, q, idx.graph.n_valid,
                              idx.vec_sqnorm)
    res = beam_search(idx.graph, score, 4, beam_width=16, max_iters=24)
    hops = np.asarray(res.n_hops)
    assert (hops > 0).all() and (hops <= 24).all()
    # visited ids are valid or -1 padding
    v = np.asarray(res.visited_ids)
    assert ((v >= -1) & (v < idx.size)).all()
    # frontier sorted ascending
    fd = np.asarray(res.frontier_dists)
    assert (np.diff(fd, axis=1) >= -1e-5).all()


def test_mips_index():
    data = np.asarray(randn(800, 24))
    idx = JasperIndex(24, capacity=800, metric="mips", construction=SMALL)
    idx.build(data)
    q = np.asarray(randn(30, 24))
    ids, _ = idx.search(q, 10, beam_width=48)
    gt, _ = idx.brute_force(q, 10)
    rec = np.mean([len(set(np.asarray(ids)[i]) & set(np.asarray(gt)[i])) / 10
                   for i in range(30)])
    assert rec > 0.5, rec  # MIPS is the hard case (paper §6.3)


def test_fixed_trip_matches_while_loop(built_index):
    idx, _ = built_index
    q = randn(6, 48)
    score = make_exact_scorer(idx.vectors, q, idx.graph.n_valid,
                              idx.vec_sqnorm)
    r1 = beam_search(idx.graph, score, 6, beam_width=16, max_iters=40)
    r2 = beam_search(idx.graph, score, 6, beam_width=16, max_iters=40,
                     fixed_trip=True)
    assert (np.asarray(r1.frontier_ids) == np.asarray(r2.frontier_ids)).all()
    # hop ACCOUNTING parity too: the fori lowering's body is guarded by
    # the same has_work predicate the while cond uses, so a converged
    # query stops accruing hops — n_hops counts expansions performed,
    # never loop trips (ISSUE 6 satellite)
    assert (np.asarray(r1.n_hops) == np.asarray(r2.n_hops)).all()
    assert (np.asarray(r1.frontier_dists)
            == np.asarray(r2.frontier_dists)).all()


def test_fixed_trip_hop_parity_multi_expand(built_index):
    """Same fori/while n_hops parity under expand_per_iter > 1 — the
    guard must compose with multi-expansion."""
    idx, _ = built_index
    q = randn(6, 48)
    score = make_exact_scorer(idx.vectors, q, idx.graph.n_valid,
                              idx.vec_sqnorm)
    for e in (2, 4):
        r1 = beam_search(idx.graph, score, 6, beam_width=16, max_iters=40,
                         expand_per_iter=e)
        r2 = beam_search(idx.graph, score, 6, beam_width=16, max_iters=40,
                         expand_per_iter=e, fixed_trip=True)
        assert (np.asarray(r1.n_hops) == np.asarray(r2.n_hops)).all()
        assert (np.asarray(r1.frontier_ids)
                == np.asarray(r2.frontier_ids)).all()


def test_kernel_backed_search_matches_jnp(built_index):
    """use_kernels=True (Pallas gather kernel) returns identical results."""
    idx, _ = built_index
    q = randn(6, 48)
    i1, d1 = idx.search(q, 5, beam_width=16)
    i2, d2 = idx.search(q, 5, beam_width=16, use_kernels=True)
    assert (np.asarray(i1) == np.asarray(i2)).all()
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-4, atol=1e-3)


def test_rabitq_codes_packed_resident(built_index):
    """Packed codes are the ONLY full-width code array after build/insert."""
    from repro.core.rabitq import packed_dim
    idx, _ = built_index
    c = idx.rabitq_codes
    assert c.packed.shape == (idx.capacity, packed_dim(idx.store_dims, 4))
    assert c.packed.dtype == jnp.uint8
    # the dataclass holds one row table and no unpacked uint8[N, D] buffer
    assert set(type(c).__dataclass_fields__) == {"rows", "bits", "dims"}
    assert c.rows.shape == (idx.capacity, c.packed.shape[1] + 8)
    assert c.rows.dtype == jnp.uint8
    stats = idx.memory_stats()
    expected = (c.packed.shape[0] * c.packed.shape[1]   # packed codes
                + 2 * 4 * idx.capacity)                 # two f32 metadata
    assert stats["rabitq_resident_bytes"] == expected


@pytest.mark.parametrize("beam_width,expand", [(16, 1), (32, 1), (32, 4)])
def test_code_rows_search_bit_identical(built_index, beam_width, expand):
    """The unfused quantized search over the rows returns the ids, the
    distance bits and the hop counts of the search over three arrays."""
    idx, _ = built_index
    codes = idx.rabitq_codes
    packed, add, rescale = (jnp.asarray(a) for a in _three_arrays(codes))
    # a stream of its own: the module RNG's draws of later tests stay put
    q = np.random.default_rng(beam_width * 10 + expand).normal(size=(24, 48))
    query = rabitq_preprocess_query(idx.rabitq_params,
                                    jnp.asarray(q, jnp.float32))

    def three_array_score(ids):
        return _three_array_estimate(packed, add, rescale, query, ids,
                                codes.bits, codes.dims)

    kw = dict(beam_width=beam_width, max_iters=2 * beam_width + 12,
              expand_per_iter=expand)
    new = beam_search_quantized(idx.graph, codes, query, **kw)
    old = beam_search(idx.graph, three_array_score, 24, **kw)
    assert np.array_equal(np.asarray(new.frontier_ids),
                          np.asarray(old.frontier_ids))
    assert np.array_equal(_bits32(new.frontier_dists),
                          _bits32(old.frontier_dists))
    assert np.array_equal(np.asarray(new.n_hops), np.asarray(old.n_hops))


def test_checkpoint_three_arrays_load_into_code_rows(tmp_path, built_index):
    """A checkpoint keeps the keys rq_packed, rq_add and rq_rescale: three
    arrays written apart (as a checkpoint of the three-array form holds
    them) load into the rows bit for bit, and saving again writes the
    same arrays under the same keys."""
    idx, _ = built_index
    first = str(tmp_path / "first.npz")
    idx.save(first)
    with np.load(first) as z:
        arrays = {k: z[k] for k in z.files}
    assert {"rq_packed", "rq_add", "rq_rescale"} <= set(arrays)
    assert not any("row" in k for k in arrays if k.startswith("rq_"))
    arrays["rq_add"][:6] = SPECIAL_FACTORS
    arrays["rq_rescale"][-6:] = SPECIAL_FACTORS
    np.savez(first, **arrays)
    idx2 = JasperIndex.load(first)
    packed, add, rescale = _three_arrays(idx2.rabitq_codes)
    assert np.array_equal(packed, arrays["rq_packed"])
    assert np.array_equal(_bits32(add), _bits32(arrays["rq_add"]))
    assert np.array_equal(_bits32(rescale), _bits32(arrays["rq_rescale"]))
    second = str(tmp_path / "second.npz")
    idx2.save(second)
    with np.load(second) as z:
        assert sorted(z.files) == sorted(arrays)
        for k in z.files:
            assert z[k].dtype == arrays[k].dtype, k
            assert z[k].tobytes() == arrays[k].tobytes(), k


def test_rabitq_kernel_search_matches_jnp(built_index):
    """search_rabitq(use_kernels=True) parity with the jnp estimator path."""
    idx, _ = built_index
    rng = np.random.default_rng(55)
    q = jnp.asarray(rng.normal(size=(50, 48)), jnp.float32)
    i1, d1 = idx.search_rabitq(q, 10, beam_width=48)
    i2, d2 = idx.search_rabitq(q, 10, beam_width=48, use_kernels=True)
    gt, _ = idx.brute_force(q, 10)

    def rec(ids):
        ids, g = np.asarray(ids), np.asarray(gt)
        return np.mean([len(set(ids[i]) & set(g[i])) / 10
                        for i in range(ids.shape[0])])
    # same recall within the acceptance tolerance, near-identical frontiers
    assert abs(rec(i1) - rec(i2)) <= 0.01, (rec(i1), rec(i2))
    assert np.mean(np.asarray(i1) == np.asarray(i2)) > 0.95
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-3, atol=1e-2)


def test_rabitq_kernel_search_no_rerank(built_index):
    """Kernel parity holds on the raw estimator frontier too (rerank off)."""
    idx, _ = built_index
    q = randn(12, 48)
    i1, d1 = idx.search_rabitq(q, 10, beam_width=32, rerank=False)
    i2, d2 = idx.search_rabitq(q, 10, beam_width=32, rerank=False,
                               use_kernels=True)
    assert np.mean(np.asarray(i1) == np.asarray(i2)) > 0.95
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-3, atol=1e-2)


def test_merge_strategies_equivalent(built_index):
    """sort / topk / kernel merges select identical frontiers."""
    idx, _ = built_index
    q = randn(9, 48)
    ids_ref, d_ref = idx.search(q, 10, beam_width=32, merge="sort")
    for merge in ("topk", "kernel"):
        ids, d = idx.search(q, 10, beam_width=32, merge=merge)
        assert (np.asarray(ids) == np.asarray(ids_ref)).all(), merge
        np.testing.assert_allclose(np.asarray(d), np.asarray(d_ref),
                                   rtol=1e-6, err_msg=merge)
    with pytest.raises(ValueError):
        idx.search(q, 10, beam_width=32, merge="bogus")


def test_rabitq_multi_expand(built_index):
    """Quantized multi-expansion keeps recall (parity with exact expand)."""
    idx, _ = built_index
    rng = np.random.default_rng(66)
    q = jnp.asarray(rng.normal(size=(40, 48)), jnp.float32)
    gt, _ = idx.brute_force(q, 10)
    i1, _ = idx.search_rabitq(q, 10, beam_width=48, expand=1)
    i4, _ = idx.search_rabitq(q, 10, beam_width=48, expand=4)

    def rec(ids):
        ids, g = np.asarray(ids), np.asarray(gt)
        return np.mean([len(set(ids[i]) & set(g[i])) / 10 for i in range(40)])
    assert rec(i4) > rec(i1) - 0.05, (rec(i1), rec(i4))


def test_multi_expand_search_api(built_index):
    idx, _ = built_index
    rng = np.random.default_rng(77)
    q = jnp.asarray(rng.normal(size=(40, 48)), jnp.float32)
    gt, _ = idx.brute_force(q, 10)
    i1, _ = idx.search(q, 10, beam_width=48, expand=1)
    i4, _ = idx.search(q, 10, beam_width=48, expand=4)

    def rec(ids):
        ids, g = np.asarray(ids), np.asarray(gt)
        return np.mean([len(set(ids[i]) & set(g[i])) / 10 for i in range(40)])
    assert rec(i4) > rec(i1) - 0.05, (rec(i1), rec(i4))


# ------------------------------------------------------- mutation lifecycle
@pytest.fixture()
def churn_index():
    """Small quantized index + its data (function-scoped: tests mutate it)."""
    rng = np.random.default_rng(4242)
    data = rng.normal(size=(700, 32)).astype(np.float32)
    idx = JasperIndex(32, capacity=900, construction=SMALL,
                      quantization="rabitq", bits=4)
    idx.build(data)
    queries = rng.normal(size=(60, 32)).astype(np.float32)
    return idx, data, queries, rng


def test_delete_excludes_ids_all_paths(churn_index):
    """Tombstoned ids never surface — exact/kernel/rabitq/brute, both
    traversal modes (the PR's returnability contract)."""
    idx, _, queries, rng = churn_index
    dead = rng.choice(700, 140, replace=False)
    assert idx.delete(dead) == 140
    assert idx.size == 700 - 140
    assert idx.n_deleted == 140
    searches = [
        lambda: idx.search(queries, 10, beam_width=48),
        lambda: idx.search(queries, 10, beam_width=48, use_kernels=True),
        lambda: idx.search(queries, 10, beam_width=48,
                           traverse_deleted=False),
        lambda: idx.search_rabitq(queries, 10, beam_width=48),
        lambda: idx.search_rabitq(queries, 10, beam_width=48,
                                  use_kernels=True),
        lambda: idx.search_rabitq(queries, 10, beam_width=48,
                                  use_kernels=True, traverse_deleted=False),
        lambda: idx.brute_force(queries, 10),
    ]
    for fn in searches:
        ids, _ = fn()
        assert not np.isin(np.asarray(ids), dead).any()
    # tombstoned search still finds the survivors well
    assert idx.recall(queries, k=10, beam_width=48) > 0.75


def test_delete_validates_ids(churn_index):
    idx, _, _, _ = churn_index
    with pytest.raises(ValueError, match="out of range"):
        idx.delete([700])
    with pytest.raises(ValueError, match="out of range"):
        idx.delete([-1])
    idx.delete([3, 5])
    with pytest.raises(ValueError, match="already deleted"):
        idx.delete([5])
    assert idx.delete(np.empty((0,), np.int64)) == 0


def test_consolidate_restores_recall(churn_index):
    """Acceptance: post-consolidate recall within 1pt of a fresh build of
    the surviving rows; repaired graph has no edges into deleted rows."""
    from repro.core.vamana import validate_graph

    idx, data, queries, rng = churn_index
    dead = rng.choice(700, 140, replace=False)       # 20% churn
    idx.delete(dead)
    stats = idx.consolidate()
    assert stats["n_freed"] == 140 and stats["n_repaired"] > 0
    assert idx.n_deleted == 0 and int(idx.mut.n_free) == 140
    live = jnp.asarray(idx.live_mask())
    checks = validate_graph(idx.graph, live)
    assert all(bool(v) for v in checks.values()), checks

    r_cons = idx.recall(queries, k=10, beam_width=48)
    fresh = JasperIndex(32, capacity=900, construction=SMALL)
    fresh.build(data[np.setdiff1d(np.arange(700), dead)])
    r_fresh = fresh.recall(queries, k=10, beam_width=48)
    assert r_cons >= r_fresh - 0.01, (r_cons, r_fresh)
    # quantized path holds too
    assert idx.recall(queries, k=10, beam_width=48, quantized=True) > 0.75


def test_insert_after_delete_reuses_slots(churn_index):
    idx, _, _, rng = churn_index
    dead = np.sort(rng.choice(700, 60, replace=False))
    idx.delete(dead)
    idx.consolidate()
    new = rng.normal(size=(60, 32)).astype(np.float32)
    got = idx.insert(new)
    # freed slots reused ascending; the high-water mark did not move
    assert (got == dead).all()
    assert int(idx.graph.n_valid) == 700 and idx.size == 700
    # reused rows are live again and findable under their new vectors
    ids, dists = idx.search(new[:20], 1, beam_width=48)
    hit = np.asarray(ids)[:, 0] == got[:20]
    assert hit.mean() > 0.8, hit.mean()


def test_grow_preserves_packed_codes(churn_index):
    """Grow copies every code row whole (codes and factor bytes) and zero-
    fills the new rows."""
    idx, _, queries, _ = churn_index
    rows = np.asarray(idx.rabitq_codes.rows)
    adj = np.asarray(idx.graph.adjacency)
    i1, d1 = idx.search_rabitq(queries, 10, beam_width=32)
    idx.grow()
    assert idx.capacity == 1800
    assert (np.asarray(idx.rabitq_codes.rows)[:900] == rows).all()
    assert (np.asarray(idx.rabitq_codes.rows)[900:] == 0).all()
    assert (np.asarray(idx.graph.adjacency)[:900] == adj).all()
    assert (np.asarray(idx.graph.adjacency)[900:] == -1).all()
    i2, d2 = idx.search_rabitq(queries, 10, beam_width=32)
    assert (np.asarray(i1) == np.asarray(i2)).all()


def test_insert_writes_whole_code_rows(churn_index):
    """An insert writes each new row's codes and factor bytes as the encoder
    gives them and leaves every other row's bytes as they were."""
    idx, _, _, rng = churn_index
    before = np.asarray(idx.rabitq_codes.rows)
    new = rng.normal(size=(50, 32)).astype(np.float32)
    got = np.asarray(idx.insert(new))
    after = np.asarray(idx.rabitq_codes.rows)
    enc = rabitq_encode(idx.rabitq_params, jnp.asarray(new))
    assert np.array_equal(after[got], np.asarray(enc.rows))
    rest = np.setdiff1d(np.arange(idx.capacity), got)
    assert np.array_equal(after[rest], before[rest])


def test_insert_auto_grows(churn_index):
    idx, _, _, rng = churn_index
    extra = rng.normal(size=(400, 32)).astype(np.float32)  # 700+400 > 900
    ids = idx.insert(extra)
    assert idx.capacity == 1800 and idx.size == 1100
    assert (ids == np.arange(700, 1100)).all()


def test_save_load_roundtrips_tombstones(tmp_path, churn_index):
    idx, _, queries, rng = churn_index
    dead = np.sort(rng.choice(700, 50, replace=False))
    idx.delete(dead)
    p = str(tmp_path / "m.npz")
    idx.save(p)
    idx2 = JasperIndex.load(p)
    assert (np.asarray(idx2.mut.tombstone_bits)
            == np.asarray(idx.mut.tombstone_bits)).all()
    assert idx2.size == idx.size and idx2.generation == idx.generation
    ids, _ = idx2.search(queries, 10, beam_width=48)
    assert not np.isin(np.asarray(ids), dead).any()
    # free pool survives the roundtrip: post-consolidate insert reuses slots
    idx.consolidate()
    idx.save(p)
    idx3 = JasperIndex.load(p)
    assert int(idx3.mut.n_free) == 50
    got = idx3.insert(rng.normal(size=(50, 32)).astype(np.float32))
    assert (got == dead).all()


def test_delete_all_then_insert_rebuilds(churn_index):
    idx, _, _, rng = churn_index
    idx.delete(np.arange(700))
    assert idx.size == 0
    ids = idx.insert(rng.normal(size=(64, 32)).astype(np.float32))
    assert idx.size == 64 and (ids == np.arange(64)).all()
    q = rng.normal(size=(10, 32)).astype(np.float32)
    assert idx.recall(q, k=5, beam_width=32) > 0.9


def test_mips_streaming_reaugment():
    """Satellite fix: a later batch raising the global max-norm re-augments
    earlier rows, so the MIPS->L2 reduction stays exact under streaming."""
    rng = np.random.default_rng(11)
    d1 = rng.normal(size=(300, 24)).astype(np.float32)
    d2 = (10.0 * rng.normal(size=(150, 24))).astype(np.float32)  # norm jump
    idx = JasperIndex(24, capacity=500, metric="mips", construction=SMALL)
    idx.build(d1)
    idx.insert(d2)
    q = rng.normal(size=(40, 24)).astype(np.float32)
    ip = q @ np.concatenate([d1, d2]).T
    got, _ = idx.brute_force(q, 1)
    # brute force over consistently augmented rows == exact MIPS argmax
    assert (np.asarray(got)[:, 0] == ip.argmax(1)).all()


def test_pq_requires_explicit_opt_in():
    """Satellite: the LUT-based PQ path is gated + deprecated (the paper's
    negative result); RaBitQ is the only kernel-backed quantized path."""
    rng = np.random.default_rng(12)
    data = rng.normal(size=(400, 32)).astype(np.float32)
    with pytest.warns(DeprecationWarning, match="NEGATIVE result"):
        idx = JasperIndex(32, capacity=500, quantization="pq",
                          construction=SMALL)
    idx.build(data)
    q = rng.normal(size=(20, 32)).astype(np.float32)
    ids, _ = idx.search_pq(q, 10, beam_width=48)
    gt, _ = idx.brute_force(q, 10)
    rec = np.mean([len(set(np.asarray(ids)[i]) & set(np.asarray(gt)[i])) / 10
                   for i in range(20)])
    assert rec > 0.7, rec
    # non-opted-in indexes expose no PQ path
    plain = JasperIndex(32, capacity=100, construction=SMALL)
    with pytest.raises(RuntimeError, match="quantization='pq'"):
        plain.search_pq(q, 5)
    with pytest.raises(ValueError, match="quantization"):
        JasperIndex(32, capacity=100, quantization="opq")


def test_anns_service_churn_loop():
    """Online update/serve loop: interleaved insert/delete/search with
    generation-stamped results and the no-tombstoned-ids contract."""
    from repro.serving.anns_service import AnnsService

    rng = np.random.default_rng(13)
    idx = JasperIndex(32, capacity=1200, construction=SMALL,
                      quantization="rabitq")
    idx.build(rng.normal(size=(600, 32)).astype(np.float32))
    svc = AnnsService(idx, k=10, beam_width=32, consolidate_threshold=0.2,
                      verify=True)
    live = list(range(600))
    gens = []
    for _ in range(4):
        dead = rng.choice(live, 60, replace=False)
        live = sorted(set(live) - set(dead.tolist()))
        res = svc.step(deletes=dead,
                       inserts=rng.normal(size=(40, 32)).astype(np.float32),
                       queries=rng.normal(size=(20, 32)).astype(np.float32))
        live += res.inserted_ids.tolist()
        # verify=True already asserts no tombstoned ids; check the stamp
        gens.append(res.search.generation)
        returned = res.search.ids[res.search.ids >= 0]
        assert np.isin(returned, live).all()
    assert gens == sorted(gens) and len(set(gens)) == len(gens)
    assert svc.stats.n_consolidations >= 1        # threshold crossed
    assert svc.stats.as_dict()["n_delete_rows"] == 240

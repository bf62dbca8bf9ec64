"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.rabitq import (
    pack_codes,
    rabitq_encode,
    rabitq_estimate,
    rabitq_preprocess_query,
    rabitq_train,
    unpack_codes,
)
from repro.kernels.distance import ops as dops
from repro.kernels.distance.ref import gather_l2_ref, pairwise_l2_ref
from repro.kernels.rabitq_dot import ops as rops
from repro.kernels.rabitq_dot.ref import rabitq_distance_ref
from repro.kernels.topk import ops as tops
from repro.kernels.topk.ref import topk_ref

RNG = np.random.default_rng(42)


def randn(*shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape), dtype)


# ------------------------------------------------------------- pairwise L2
@pytest.mark.parametrize("q,c,d", [
    (8, 128, 128),          # exact tile multiples
    (37, 211, 96),          # ragged everything
    (1, 1, 1),              # degenerate
    (130, 4, 960),          # Gist-dim, tiny C
    (16, 300, 1536),        # OpenAI-dim
])
def test_pairwise_l2_shapes(q, c, d):
    qv, xv = randn(q, d), randn(c, d)
    out = dops.pairwise_l2(qv, xv)
    ref = pairwise_l2_ref(qv, xv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_l2_dtypes(dtype):
    qv = randn(16, 128).astype(dtype)
    xv = randn(64, 128).astype(dtype)
    out = dops.pairwise_l2(qv, xv)
    ref = pairwise_l2_ref(qv, xv)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol * 100)


def test_pairwise_l2_block_sweep():
    qv, xv = randn(64, 256), randn(256, 256)
    ref = pairwise_l2_ref(qv, xv)
    for bq, bc, bd in [(8, 128, 128), (32, 256, 256), (64, 128, 128)]:
        out = dops.pairwise_l2(qv, xv, block_q=bq, block_c=bc, block_d=bd)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-3)


# ------------------------------------------------------------- gather forms
@pytest.mark.parametrize("q,k,d,n", [
    (8, 16, 128, 200),
    (33, 7, 96, 100),
    (4, 64, 960, 64),
])
def test_gather_l2(q, k, d, n):
    qv, db = randn(q, d), randn(n, d)
    db_sq = jnp.sum(db * db, axis=-1)
    ids = jnp.asarray(RNG.integers(-1, n, (q, k)), jnp.int32)
    out = dops.gather_l2_chunked(qv, db, db_sq, ids)
    ref = gather_l2_ref(qv, db, ids)
    finite = np.isfinite(np.asarray(ref))
    assert (np.isfinite(np.asarray(out)) == finite).all()
    np.testing.assert_allclose(np.asarray(out)[finite], np.asarray(ref)[finite],
                               rtol=1e-4, atol=1e-3)


def test_kernel_scorer_matches_exact_scorer():
    from repro.core.beam_search import make_exact_scorer
    db, qv = randn(128, 64), randn(9, 64)
    n_valid = jnp.int32(100)
    ids = jnp.asarray(RNG.integers(-1, 128, (9, 11)), jnp.int32)
    exact = make_exact_scorer(db, qv, n_valid)(ids)
    kern = dops.make_kernel_scorer(db, qv, n_valid)(ids)
    exact = np.where(np.asarray(ids) >= 0, np.asarray(exact), np.inf)
    # exact scorer returns garbage (not inf) for out-of-range; align masks
    mask = (np.asarray(ids) >= 0) & (np.asarray(ids) < 100)
    np.testing.assert_allclose(np.asarray(kern)[mask], exact[mask],
                               rtol=1e-4, atol=1e-3)
    assert np.all(np.isinf(np.asarray(kern)[~mask]))


# ------------------------------------------------------------------ rabitq
@pytest.mark.parametrize("bits", [1, 2, 4, 8])
@pytest.mark.parametrize("q,n,d", [(8, 64, 128), (19, 100, 96), (4, 32, 960)])
def test_rabitq_kernel_vs_ref(bits, q, n, d):
    db, qv = randn(n, d), randn(q, d)
    params = rabitq_train(jax.random.PRNGKey(0), db, bits=bits)
    codes = rabitq_encode(params, db)
    qq = rabitq_preprocess_query(params, qv)
    packed = codes.packed                    # canonical — already packed
    ref = rabitq_distance_ref(packed, codes.data_add, codes.data_rescale,
                              qq.q_rot, qq.query_add, qq.query_sumq,
                              bits=bits, dims=d)
    out = rops.rabitq_distance(packed, codes.data_add, codes.data_rescale,
                               qq.q_rot, qq.query_add, qq.query_sumq,
                               bits=bits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-2)
    # ref itself must agree with the core jnp estimator
    est = rabitq_estimate(codes, qq)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(est),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("bits", [1, 4, 8])
def test_rabitq_gather_kernel(bits):
    n, d, q, k = 90, 128, 12, 9
    db, qv = randn(n, d), randn(q, d)
    params = rabitq_train(jax.random.PRNGKey(1), db, bits=bits)
    codes = rabitq_encode(params, db)
    qq = rabitq_preprocess_query(params, qv)
    packed = codes.packed
    ids = jnp.asarray(RNG.integers(0, n, (q, k)), jnp.int32)
    out = rops.rabitq_gather_distance(
        packed[ids], codes.data_add[ids], codes.data_rescale[ids],
        qq.q_rot, qq.query_add, qq.query_sumq, bits=bits)
    full = rabitq_distance_ref(packed, codes.data_add, codes.data_rescale,
                               qq.q_rot, qq.query_add, qq.query_sumq,
                               bits=bits, dims=d)
    ref = np.take_along_axis(np.asarray(full), np.asarray(ids), axis=1)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-3, atol=1e-2)


@pytest.mark.parametrize("dims", [1, 3, 7, 33, 100, 129])
@pytest.mark.parametrize("bits", [1, 2, 4, 8])
def test_pack_unpack_roundtrip(bits, dims):
    """Round-trips across all SUPPORTED_BITS x odd/non-multiple dims."""
    codes = jnp.asarray(
        RNG.integers(0, 2**bits, (13, dims)), jnp.uint8)
    packed = pack_codes(codes, bits)
    assert packed.shape[1] == int(np.ceil(dims * bits / 8))
    un = unpack_codes(packed, bits, dims)
    assert (np.asarray(un) == np.asarray(codes)).all()


@pytest.mark.parametrize("bits", [1, 4])
def test_pack_unpack_leading_dims(bits):
    """(Q, K, D) batches pack/unpack row-independently."""
    codes = jnp.asarray(RNG.integers(0, 2**bits, (5, 7, 50)), jnp.uint8)
    packed = pack_codes(codes, bits)
    assert packed.shape == (5, 7, int(np.ceil(50 * bits / 8)))
    un = unpack_codes(packed, bits, 50)
    assert (np.asarray(un) == np.asarray(codes)).all()


@pytest.mark.parametrize("bits", [1, 4, 8])
def test_rabitq_search_step_kernel_masks_invalid(bits):
    """Fused search-step kernel: estimator + in-kernel invalid-id masking."""
    from repro.kernels.rabitq_dot.ref import rabitq_search_step_ref

    n, d, q, k = 80, 96, 11, 13
    n_valid = 60
    db, qv = randn(n, d), randn(q, d)
    params = rabitq_train(jax.random.PRNGKey(2), db, bits=bits)
    codes = rabitq_encode(params, db)
    qq = rabitq_preprocess_query(params, qv)
    # ids include -1 (padding) and >= n_valid (stale graph edges)
    ids = jnp.asarray(RNG.integers(-1, n, (q, k)), jnp.int32)
    safe = jnp.maximum(ids, 0)
    cand = codes.packed[safe]
    out = rops.rabitq_search_step(
        cand, codes.data_add[safe], codes.data_rescale[safe], ids,
        jnp.int32(n_valid), qq.q_rot, qq.query_add, qq.query_sumq,
        bits=bits)
    ref = rabitq_search_step_ref(
        cand, codes.data_add[safe], codes.data_rescale[safe], ids,
        n_valid, qq.q_rot, qq.query_add, qq.query_sumq, bits=bits, dims=d)
    mask = np.asarray((ids >= 0) & (ids < n_valid))
    assert (np.isinf(np.asarray(out)) == ~mask).all()
    np.testing.assert_allclose(np.asarray(out)[mask], np.asarray(ref)[mask],
                               rtol=1e-3, atol=1e-2)


@pytest.mark.parametrize("bits", [1, 4])
def test_rabitq_search_step_kernel_tombstone_mask(bits):
    """The per-row tombstone bitmap extends the fused epilogue mask: dead
    candidates come back +inf, live ones match the no-tombstone run."""
    from repro.core.mutations import bitmap_gather, pack_bitmap
    from repro.kernels.rabitq_dot.ref import rabitq_search_step_ref

    n, d, q, k = 80, 96, 11, 13
    n_valid = 70
    db, qv = randn(n, d), randn(q, d)
    params = rabitq_train(jax.random.PRNGKey(3), db, bits=bits)
    codes = rabitq_encode(params, db)
    qq = rabitq_preprocess_query(params, qv)
    ids = jnp.asarray(RNG.integers(-1, n, (q, k)), jnp.int32)
    safe = jnp.maximum(ids, 0)
    cand = codes.packed[safe]
    dense = jnp.asarray(RNG.integers(0, 2, n).astype(bool))
    bits_map = pack_bitmap(dense)
    live = (~bitmap_gather(bits_map, safe)).astype(jnp.int32)
    out = rops.rabitq_search_step(
        cand, codes.data_add[safe], codes.data_rescale[safe], ids,
        jnp.int32(n_valid), qq.q_rot, qq.query_add, qq.query_sumq,
        bits=bits, live=live)
    ref = rabitq_search_step_ref(
        cand, codes.data_add[safe], codes.data_rescale[safe], ids,
        n_valid, qq.q_rot, qq.query_add, qq.query_sumq, bits=bits, dims=d,
        live=live)
    mask = np.asarray((ids >= 0) & (ids < n_valid) & (live != 0))
    assert (np.isinf(np.asarray(out)) == ~mask).all()
    np.testing.assert_allclose(np.asarray(out)[mask], np.asarray(ref)[mask],
                               rtol=1e-3, atol=1e-2)


# -------------------------------------------------------------------- topk
@pytest.mark.parametrize("q,c,k", [(8, 128, 10), (5, 300, 32), (64, 64, 64)])
def test_topk_kernel(q, c, k):
    d = randn(q, c)
    i = jnp.arange(q * c, dtype=jnp.int32).reshape(q, c)
    od, oi = tops.topk(d, i, k)
    rd, ri = topk_ref(d, i, k)
    np.testing.assert_allclose(np.asarray(od), np.asarray(rd), rtol=1e-6)
    assert (np.asarray(oi) == np.asarray(ri)).all()


def test_topk_with_ties_and_inf():
    d = jnp.asarray([[1.0, 1.0, np.inf, 0.5], [np.inf, np.inf, np.inf, np.inf]],
                    jnp.float32)
    i = jnp.asarray([[10, 11, 12, 13], [20, 21, 22, 23]], jnp.int32)
    od, oi = tops.topk(d, i, 3)
    assert oi[0, 0] == 13 and od[0, 0] == 0.5
    assert oi[0, 1] == 10  # first occurrence wins the tie
    assert np.isinf(np.asarray(od)[1]).all()


# ---------------------------------------------------------- flash attention
@pytest.mark.parametrize("b,s,h,hk,dh,causal,window", [
    (2, 128, 4, 4, 32, True, 0),
    (1, 128, 8, 2, 64, True, 0),     # GQA
    (2, 128, 4, 4, 32, False, 0),    # bidirectional (encoder)
    (1, 256, 4, 2, 32, True, 64),    # sliding window
])
def test_flash_attention_vs_ref(b, s, h, hk, dh, causal, window):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    q = randn(b, s, h, dh)
    k = randn(b, s, hk, dh)
    v = randn(b, s, hk, dh)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_kv=64)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_flash_attention_block_sweep():
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    q, k, v = randn(1, 256, 4, 32), randn(1, 256, 2, 32), randn(1, 256, 2, 32)
    ref = flash_attention_ref(q, k, v, causal=True)
    for bq, bkv in [(64, 64), (128, 64), (64, 128), (256, 256)]:
        out = flash_attention(q, k, v, causal=True, block_q=bq, block_kv=bkv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


def test_flash_traffic_model():
    from repro.kernels.flash_attention.ops import flash_traffic_bytes
    t = flash_traffic_bytes(1, 4, 4, 1024, 1024, 64, block_q=256)
    # q + o once (2 * 1*4*1024*64), kv re-read nq=4 times (2*4*4*1024*64)
    assert t == (2 * 4 * 1024 * 64 + 2 * 4 * 4 * 1024 * 64) * 2


@pytest.mark.parametrize("hk,causal,window", [(4, True, 0), (2, True, 0),
                                              (4, False, 0), (2, True, 64)])
def test_flash_attention_grads_vs_autodiff(hk, causal, window):
    """custom_vjp backward kernels match autodiff of the reference."""
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    b, s, h, dh = 1, 128, 4, 32
    q, k, v = randn(b, s, h, dh), randn(b, s, hk, dh), randn(b, s, hk, dh)
    ct = randn(b, s, h, dh)

    def f_kernel(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=causal,
                                       window=window, block_q=64,
                                       block_kv=64) * ct)

    def f_ref(q_, k_, v_):
        return jnp.sum(flash_attention_ref(q_, k_, v_, causal=causal,
                                           window=window) * ct)

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------- fused search (ISSUE 6)
@pytest.fixture(scope="module")
def fused_index():
    """Small built index shared by the fused-search kernel tests."""
    from repro.core.construction import ConstructionParams
    from repro.core.index import JasperIndex

    rng = np.random.default_rng(321)
    n, d, q = 384, 16, 16
    data = rng.normal(size=(n, d)).astype(np.float32)
    queries = jnp.asarray(rng.normal(size=(q, d)), jnp.float32)
    params = ConstructionParams(degree_bound=16, alpha=1.2, beam_width=16,
                                max_iters=24, rev_cap=16, prune_chunk=256)
    idx = JasperIndex(d, capacity=n, construction=params,
                      quantization="rabitq", bits=4, seed=321)
    idx.build(data)
    return idx, queries


@pytest.mark.parametrize("schedule", [None, (16, 12, 10)])
def test_fused_search_ref_bitwise_vs_beam_search(fused_index, schedule):
    """The oracle contract: fused_search_ref IS beam_search(merge="topk",
    expand=1) — bit-exact ids, dists, AND hop counts, with or without a
    beam schedule."""
    from repro.core.beam_search import beam_search, make_exact_scorer
    from repro.kernels.search_step.ref import fused_search_ref

    idx, queries = fused_index
    nq = queries.shape[0]
    score = make_exact_scorer(idx.vectors, queries, idx.graph.n_valid,
                              idx.vec_sqnorm)
    res = beam_search(idx.graph, score, nq, beam_width=16, max_iters=40,
                      merge_strategy="topk", beam_schedule=schedule)
    ri, rd, rh = fused_search_ref(
        idx.graph.adjacency, idx.graph.n_valid, idx.graph.medoid, score,
        nq, beam_width=16, max_iters=40, beam_schedule=schedule)
    assert (np.asarray(res.frontier_ids) == np.asarray(ri)).all()
    assert (np.asarray(res.frontier_dists) == np.asarray(rd)).all()
    assert (np.asarray(res.n_hops) == np.asarray(rh)).all()


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["exact", "rabitq"])
@pytest.mark.parametrize("mode", ["hop", "megakernel"])
def test_fused_kernel_vs_ref_oracle(fused_index, quantized, mode):
    """Both Pallas kernels vs the jnp oracle, whole-search: near-total id
    agreement, dists allclose (MXU reduction order differs), hop counts
    exactly equal."""
    from repro.core.beam_search import make_exact_scorer, make_rabitq_scorer
    from repro.core.rabitq import rabitq_preprocess_query
    from repro.kernels.search_step.ops import fused_beam_search
    from repro.kernels.search_step.ref import fused_search_ref

    idx, queries = fused_index
    nq = queries.shape[0]
    if quantized:
        rq = rabitq_preprocess_query(idx.rabitq_params, queries)
        score = make_rabitq_scorer(idx.rabitq_codes, rq)
        res = fused_beam_search(idx.graph, mode=mode, beam_width=16,
                                max_iters=40, codes=idx.rabitq_codes,
                                rq_query=rq)
    else:
        score = make_exact_scorer(idx.vectors, queries, idx.graph.n_valid,
                                  idx.vec_sqnorm)
        res = fused_beam_search(idx.graph, mode=mode, beam_width=16,
                                max_iters=40, queries=queries,
                                vectors=idx.vectors,
                                vec_sqnorm=idx.vec_sqnorm)
    ri, rd, rh = fused_search_ref(
        idx.graph.adjacency, idx.graph.n_valid, idx.graph.medoid, score,
        nq, beam_width=16, max_iters=40)
    agree = float(np.mean(np.asarray(res.frontier_ids) == np.asarray(ri)))
    assert agree >= 0.95, agree
    fin = np.isfinite(np.asarray(rd))
    np.testing.assert_allclose(np.asarray(res.frontier_dists)[fin],
                               np.asarray(rd)[fin], rtol=1e-4, atol=1e-3)
    assert (np.asarray(res.n_hops) == np.asarray(rh)).all()


@pytest.mark.parametrize("mode", ["hop", "megakernel"])
def test_fused_kernel_beam_schedule_vs_ref(fused_index, mode):
    """One narrowing-schedule case straight at the kernel layer."""
    from repro.core.beam_search import make_exact_scorer
    from repro.kernels.search_step.ops import fused_beam_search
    from repro.kernels.search_step.ref import fused_search_ref

    idx, queries = fused_index
    nq = queries.shape[0]
    sched = (16, 12, 10)
    score = make_exact_scorer(idx.vectors, queries, idx.graph.n_valid,
                              idx.vec_sqnorm)
    res = fused_beam_search(idx.graph, mode=mode, beam_width=16,
                            max_iters=40, beam_schedule=sched,
                            queries=queries, vectors=idx.vectors,
                            vec_sqnorm=idx.vec_sqnorm)
    ri, rd, rh = fused_search_ref(
        idx.graph.adjacency, idx.graph.n_valid, idx.graph.medoid, score,
        nq, beam_width=16, max_iters=40, beam_schedule=sched)
    agree = float(np.mean(np.asarray(res.frontier_ids) == np.asarray(ri)))
    assert agree >= 0.95, agree
    assert (np.asarray(res.n_hops) == np.asarray(rh)).all()


@pytest.mark.parametrize("traverse", [False, True],
                         ids=["exclude", "traverse"])
def test_fused_kernel_tombstones_vs_ref(fused_index, traverse):
    """Tombstones through the kernels: exclude mode gathers liveness bytes
    in-kernel, traverse mode filters only the final frontier — both must
    match the oracle and never return a deleted id."""
    from repro.core.beam_search import make_exact_scorer
    from repro.core.mutations import pack_bitmap
    from repro.kernels.search_step.ops import fused_beam_search
    from repro.kernels.search_step.ref import fused_search_ref

    idx, queries = fused_index
    nq = queries.shape[0]
    cap = idx.vectors.shape[0]
    rng = np.random.default_rng(7)
    dead = np.sort(rng.choice(384, 40, replace=False)).astype(np.int32)
    dense = np.zeros((cap,), bool)
    dense[dead] = True
    tomb = pack_bitmap(jnp.asarray(dense))
    score = make_exact_scorer(idx.vectors, queries, idx.graph.n_valid,
                              idx.vec_sqnorm)
    for mode in ("hop", "megakernel"):
        res = fused_beam_search(idx.graph, mode=mode, beam_width=16,
                                max_iters=40, queries=queries,
                                vectors=idx.vectors,
                                vec_sqnorm=idx.vec_sqnorm,
                                tombstone_bits=tomb,
                                traverse_deleted=traverse)
        ids = np.asarray(res.frontier_ids)
        assert not np.isin(ids, dead).any()
        ri, _, rh = fused_search_ref(
            idx.graph.adjacency, idx.graph.n_valid, idx.graph.medoid,
            score, nq, beam_width=16, max_iters=40, tombstone_bits=tomb,
            traverse_deleted=traverse)
        agree = float(np.mean(ids == np.asarray(ri)))
        assert agree >= 0.95, (mode, agree)
        assert (np.asarray(res.n_hops) == np.asarray(rh)).all()

"""Telemetry plane (ISSUE 7): zero-overhead off mode, exact kernel
counters, span tracing, and the unified metrics snapshot.

The contracts under test:

  * `telemetry="off"` (the default) is a TRUE zero — results bitwise
    identical to "on" across the whole search grid, `.telemetry is
    None`, and the plan-cache key of a spec that never mentions
    telemetry equals the explicit-"off" key (no retrace, no new entry).
  * `telemetry="on"` counters are EXACTLY equal (integers, no
    tolerance) across every execution path of the same search config:
    the unfused jnp loop, the self-masking kernel scorer, the fused
    per-hop kernel, and the megakernel — with `fused_search_ref` as the
    bit-exact oracle the Pallas kernels are diffed against directly.
  * spans nest, order, and aggregate correctly, are thread-safe, and
    export valid Chrome trace-event JSON; without an installed tracer
    `obs.span` is a no-op.
  * `ServiceStats` / `CacheStats` / `MetricsRegistry` snapshots are
    plain JSON (round-trip through `json.dumps`), with guarded derived
    rates (no ZeroDivisionError on empty stats).
  * program spans land in a `jax.profiler` trace beside the search
    program `jit_jasper_search`, starting where the `SpanTracer` says;
    the `session.*` counters count what the landed batches hold; the
    named scopes, spans and counters leave every search bit-identical
    and every plan-cache key unchanged.
"""

import json
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.construction import ConstructionParams
from repro.core.index import JasperIndex
from repro.core.search_spec import SearchSpec

SEED = 5
N, D, Q, K, BEAM = 384, 16, 8, 5, 16
SMALL = ConstructionParams(degree_bound=16, alpha=1.2, beam_width=16,
                           max_iters=24, rev_cap=16, prune_chunk=256)

# the full search grid from the issue: {exact, rabitq} x {jnp scorer,
# kernel scorer} x {unfused, fused-hop, megakernel}
GRID = [
    pytest.param(quantized, kernels, fusion,
                 id=f"{'rabitq' if quantized else 'exact'}-"
                    f"{'kernel' if kernels else 'jnp'}-{fusion}")
    for quantized in (False, True)
    for kernels in (False, True)
    for fusion in ("none", "hop", "megakernel")
]


@pytest.fixture(scope="module")
def built():
    rng = np.random.default_rng(SEED)
    data = rng.normal(size=(N, D)).astype(np.float32)
    queries = rng.normal(size=(Q, D)).astype(np.float32)
    idx = JasperIndex(D, capacity=512, construction=SMALL,
                      quantization="rabitq", bits=4, seed=SEED)
    idx.build(data)
    return idx, queries


def _spec(quantized, kernels, fusion, **kw):
    return SearchSpec(k=K, beam_width=BEAM, quantized=quantized,
                      use_kernels=kernels, fusion=fusion, **kw)


def _tel_np(tel):
    return tuple(np.asarray(t) for t in tel)


# ------------------------------------------------------- off is a true zero
@pytest.mark.parametrize("quantized,kernels,fusion", GRID)
def test_telemetry_off_bitwise_identity(built, quantized, kernels, fusion):
    """Off-mode results are bit-identical to on-mode across the grid, and
    off tickets carry no telemetry object at all."""
    idx, queries = built
    off = idx.searcher(_spec(quantized, kernels, fusion)).search(queries)
    on = idx.searcher(
        _spec(quantized, kernels, fusion, telemetry="on")).search(queries)
    assert off.telemetry is None
    assert on.telemetry is not None
    assert np.array_equal(np.asarray(off.ids), np.asarray(on.ids))
    assert np.array_equal(np.asarray(off.dists), np.asarray(on.dists))
    assert np.array_equal(np.asarray(off.n_hops), np.asarray(on.n_hops))
    # counters are present and sane
    scored, masked, dups, occ = _tel_np(on.telemetry)
    assert scored.dtype == np.int32 and scored.shape == (Q,)
    assert (scored > 0).all()
    assert (masked == 0).all()        # no tombstones in this fixture
    assert occ.shape[0] == Q
    # every row's occupancy log has exactly n_hops non-leading-zero...
    # occupancy is recorded only for hops the row actually expanded
    hops = np.asarray(on.n_hops)
    for r in range(Q):
        assert (occ[r, hops[r]:] == 0).all()
        assert (occ[r, :hops[r]] > 0).all()


def test_plan_cache_key_off_identity(built):
    """A spec that never mentions telemetry and an explicit
    telemetry="off" spec resolve to the SAME plan-cache key: equal, same
    hash, and the second search is a pure cache hit (zero new traces)."""
    idx, queries = built
    a = SearchSpec(k=K, beam_width=BEAM, quantized=True)
    b = SearchSpec(k=K, beam_width=BEAM, quantized=True, telemetry="off")
    assert a.resolve() == b.resolve()
    assert hash(a.resolve()) == hash(b.resolve())
    idx.searcher(a).search(queries)
    before = idx.searcher(a).cache_stats.snapshot()
    idx.searcher(b).search(queries)
    after = idx.searcher(b).cache_stats
    assert after.traces == before.traces, "telemetry='off' retraced"
    assert after.hits > before.hits
    # "on" is a DIFFERENT key (extra kernel outputs) — must not collide
    assert a.resolve() != a.with_(telemetry="on").resolve()


@pytest.mark.parametrize("quantized", [False, True], ids=["exact", "rabitq"])
def test_counters_exactly_equal_across_paths(built, quantized):
    """The headline contract: all execution paths of one search config
    emit IDENTICAL counters — integer equality, no tolerance."""
    idx, queries = built
    ref = None
    for kernels in (False, True):
        for fusion in ("none", "hop", "megakernel"):
            res = idx.searcher(
                _spec(quantized, kernels, fusion,
                      telemetry="on")).search(queries)
            tel = _tel_np(res.telemetry)
            if ref is None:
                ref = tel
                continue
            for name, a, b in zip(("scored", "masked", "dups", "occ"),
                                  ref, tel):
                assert np.array_equal(a, b), (
                    f"{name} differs on kernels={kernels} fusion={fusion}")


# --------------------------------------------- kernels vs the jnp ref oracle
@pytest.mark.parametrize("quantized", [False, True], ids=["exact", "rabitq"])
@pytest.mark.parametrize("mode", ["hop", "megakernel"])
def test_fused_kernel_counters_vs_ref_oracle(built, quantized, mode):
    """Straight at the kernel layer: both Pallas kernels' telemetry
    outputs vs `fused_search_ref(telemetry=True)` — exact equality of
    scored / masked / duplicates / per-hop occupancy."""
    from repro.core.beam_search import make_exact_scorer, make_rabitq_scorer
    from repro.core.rabitq import rabitq_preprocess_query
    from repro.kernels.search_step.ops import fused_beam_search
    from repro.kernels.search_step.ref import fused_search_ref

    idx, queries = built
    qj = jnp.asarray(queries)
    if quantized:
        rq = rabitq_preprocess_query(idx.rabitq_params, qj)
        score = make_rabitq_scorer(idx.rabitq_codes, rq)
        res = fused_beam_search(idx.graph, mode=mode, beam_width=BEAM,
                                max_iters=40, codes=idx.rabitq_codes,
                                rq_query=rq, telemetry=True)
    else:
        score = make_exact_scorer(idx.vectors, qj, idx.graph.n_valid,
                                  idx.vec_sqnorm)
        res = fused_beam_search(idx.graph, mode=mode, beam_width=BEAM,
                                max_iters=40, queries=qj,
                                vectors=idx.vectors,
                                vec_sqnorm=idx.vec_sqnorm, telemetry=True)
    _, _, rh, rtel = fused_search_ref(
        idx.graph.adjacency, idx.graph.n_valid, idx.graph.medoid, score,
        Q, beam_width=BEAM, max_iters=40, telemetry=True)
    assert (np.asarray(res.n_hops) == np.asarray(rh)).all()
    for name, a, b in zip(("scored", "masked", "dups", "occ"),
                          _tel_np(res.telemetry), _tel_np(rtel)):
        assert np.array_equal(a, b), f"{mode}: {name} != ref oracle"


@pytest.mark.parametrize("traverse", [False, True],
                         ids=["exclude", "traverse"])
def test_kernel_counters_tombstones_vs_ref(built, traverse):
    """Tombstone counters through both kernels vs the oracle: exclude
    mode counts masked candidates in-kernel (and they must be > 0 here);
    traverse mode scores through tombstones so masked stays 0."""
    from repro.core.beam_search import make_exact_scorer
    from repro.core.mutations import pack_bitmap
    from repro.kernels.search_step.ops import fused_beam_search
    from repro.kernels.search_step.ref import fused_search_ref

    idx, queries = built
    qj = jnp.asarray(queries)
    cap = idx.vectors.shape[0]
    rng = np.random.default_rng(7)
    dead = np.sort(rng.choice(N, 60, replace=False)).astype(np.int32)
    dense = np.zeros((cap,), bool)
    dense[dead] = True
    tomb = pack_bitmap(jnp.asarray(dense))
    score = make_exact_scorer(idx.vectors, qj, idx.graph.n_valid,
                              idx.vec_sqnorm)
    _, _, rh, rtel = fused_search_ref(
        idx.graph.adjacency, idx.graph.n_valid, idx.graph.medoid, score,
        Q, beam_width=BEAM, max_iters=40, tombstone_bits=tomb,
        traverse_deleted=traverse, telemetry=True)
    rtel = _tel_np(rtel)
    if traverse:
        assert rtel[1].sum() == 0
    else:
        assert rtel[1].sum() > 0, "exclude mode must mask candidates here"
    for mode in ("hop", "megakernel"):
        res = fused_beam_search(idx.graph, mode=mode, beam_width=BEAM,
                                max_iters=40, queries=qj,
                                vectors=idx.vectors,
                                vec_sqnorm=idx.vec_sqnorm,
                                tombstone_bits=tomb,
                                traverse_deleted=traverse, telemetry=True)
        assert (np.asarray(res.n_hops) == np.asarray(rh)).all()
        for name, a, b in zip(("scored", "masked", "dups", "occ"),
                              _tel_np(res.telemetry), rtel):
            assert np.array_equal(a, b), f"{mode}: {name} != ref oracle"


def test_exclude_mode_counters_equal_across_scorers(built):
    """Exclude-mode masked counts through the SERVING surface: the jnp
    scorer, self-masking kernel scorer, and both fused kernels all report
    the same masked/scored/dup counts on a tombstoned index. Per-hop
    occupancy is compared within each fusion family only — under
    tombstones the unfused and fused searches may legitimately hold
    different -1 paddings in the frontier (conformance holds their ids
    to 0.95 agreement, not bit-equality), while the candidate counters
    still agree exactly because both walks expand the same nodes."""
    idx, queries = built
    rng = np.random.default_rng(11)
    dead = rng.choice(np.arange(N), 50, replace=False)
    idx.delete(dead)
    try:
        ref, occ_ref = None, {}
        for kernels in (False, True):
            for fusion in ("none", "hop", "megakernel"):
                res = idx.searcher(
                    _spec(True, kernels, fusion, telemetry="on",
                          traverse_deleted=False)).search(queries)
                assert not np.isin(np.asarray(res.ids), dead).any()
                tel = _tel_np(res.telemetry)
                assert tel[1].sum() > 0
                if ref is None:
                    ref = tel[:3]
                else:
                    for name, a, b in zip(("scored", "masked", "dups"),
                                          ref, tel[:3]):
                        assert np.array_equal(a, b), (
                            f"{name} differs on kernels={kernels} "
                            f"fusion={fusion}")
                family = "unfused" if fusion == "none" else "fused"
                if family in occ_ref:
                    assert np.array_equal(occ_ref[family], tel[3]), (
                        f"occupancy differs within {family} family on "
                        f"kernels={kernels} fusion={fusion}")
                else:
                    occ_ref[family] = tel[3]
    finally:
        idx.consolidate()             # leave the module fixture clean


# ------------------------------------------------------------- span tracing
def test_span_nesting_and_ordering():
    from repro.obs.tracing import SpanTracer, use_tracer

    tr = SpanTracer()
    with use_tracer(tr):
        from repro.obs.tracing import span
        with span("outer", tick=1):
            with span("inner_a"):
                pass
            with span("inner_b"):
                pass
    events = tr.events()
    assert [e["name"] for e in events] == ["inner_a", "inner_b", "outer"]
    by = {e["name"]: e for e in events}
    # children are contained in the parent interval
    for child in ("inner_a", "inner_b"):
        assert by["outer"]["ts"] <= by[child]["ts"]
        assert (by[child]["ts"] + by[child]["dur"]
                <= by["outer"]["ts"] + by["outer"]["dur"] + 1)
    assert by["inner_a"]["ts"] + by["inner_a"]["dur"] <= by["inner_b"]["ts"]
    assert by["outer"]["args"] == {"tick": 1}
    # chrome export is valid JSON with the required fields
    doc = tr.to_chrome_trace()
    json.dumps(doc)
    assert doc["displayTimeUnit"] == "ms"
    for e in doc["traceEvents"]:
        assert e["ph"] == "X"
        for field in ("name", "ts", "dur", "pid", "tid"):
            assert field in e
    s = tr.summary()
    assert s["outer"]["count"] == 1
    assert s["outer"]["total_us"] >= s["inner_a"]["total_us"]


def test_span_noop_without_tracer():
    from repro.obs.tracing import get_tracer, span

    assert get_tracer() is None
    with span("never_recorded"):      # must not raise, must not record
        pass
    assert get_tracer() is None


def test_span_thread_safety():
    from repro.obs.tracing import SpanTracer, use_tracer

    tr = SpanTracer()
    n_threads, n_spans = 8, 50
    gate = threading.Barrier(n_threads)   # hold all threads alive at once

    def worker(i):
        from repro.obs.tracing import span
        gate.wait()
        for j in range(n_spans):
            with span(f"t{i}"):
                pass

    with use_tracer(tr):
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert len(tr) == n_threads * n_spans
    s = tr.summary()
    assert all(s[f"t{i}"]["count"] == n_spans for i in range(n_threads))
    # distinct threads get distinct tids in the export
    tids = {e["tid"] for e in tr.events()}
    assert len(tids) == n_threads


# ------------------------------------------------- stats + metrics snapshots
def test_cache_stats_guarded_and_json():
    from repro.core.search_spec import CacheStats

    empty = CacheStats()
    assert empty.hit_rate == 0.0      # no ZeroDivisionError
    d = empty.as_dict()
    json.dumps(d)
    assert d["hit_rate"] == 0.0
    full = CacheStats(hits=3, misses=1, traces=1)
    assert full.hit_rate == pytest.approx(0.75)
    assert full.as_dict()["hit_rate"] == pytest.approx(0.75)


def test_service_stats_roundtrip():
    from repro.serving.anns_service import ServiceStats

    st = ServiceStats()
    assert st.mean_hops == 0.0        # guarded on zero queries
    d = st.to_dict()
    rt = json.loads(json.dumps(d))
    assert rt == d
    st.n_searches = 2
    st.n_search_queries = 10
    st.hops_sum = 55.0
    d2 = st.to_dict()
    assert d2["mean_hops"] == pytest.approx(5.5)
    json.dumps(d2)


def test_metrics_registry():
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    c = reg.counter("requests")
    c.inc()
    c.inc(np.int64(4))                # numpy scalars coerce
    with pytest.raises(ValueError):
        c.inc(-1)                     # counters are monotonic
    reg.gauge("depth").set(3)
    reg.gauge("live", fn=lambda: np.int32(7))
    h = reg.histogram("lat", buckets=(10, 100, 1000))
    h.observe_many([5, 50, 500, 5000])
    reg.register_collector("svc", lambda: {"x": np.float32(1.5)})
    snap = reg.snapshot()
    json.dumps(snap)                  # plain JSON end to end
    assert snap["requests"] == 5
    assert snap["depth"] == 3
    assert snap["live"] == 7
    assert snap["svc.x"] == pytest.approx(1.5)
    assert snap["lat"]["count"] == 4
    assert sum(snap["lat"]["counts"]) == 4
    assert snap["lat"]["counts"] == [1, 1, 1, 1]
    # re-requesting a name returns the same instrument; a type clash raises
    assert reg.counter("requests") is c
    with pytest.raises(TypeError):
        reg.gauge("requests")


def test_service_unified_snapshot_and_spans():
    """One churn tick through the service with the tracer installed:
    every phase span shows up, the snapshot carries all four namespaces,
    and the whole thing survives json.dumps."""
    from repro.obs.tracing import SpanTracer, use_tracer
    from repro.serving.anns_service import AnnsService

    rng = np.random.default_rng(3)
    data = rng.normal(size=(300, D)).astype(np.float32)
    idx = JasperIndex(D, capacity=512, construction=SMALL,
                      quantization="rabitq", bits=4, seed=3)
    tr = SpanTracer()
    with use_tracer(tr):
        idx.build(data[:256])
        svc = AnnsService(idx, spec=SearchSpec(k=K, beam_width=BEAM,
                                               quantized=True,
                                               telemetry="on"),
                          consolidate_threshold=0.05)
        svc.metrics()
        res = svc.step(queries=rng.normal(size=(4, D)).astype(np.float32),
                       inserts=data[256:],
                       deletes=np.arange(30, dtype=np.int64))
    assert res.search.telemetry is not None
    names = {e["name"] for e in tr.events()}
    assert {"index.build", "service.step", "service.delete",
            "service.insert", "service.search",
            "service.consolidate"} <= names
    snap = svc.metrics_snapshot()
    json.dumps(snap)
    for key in ("service.n_searches", "plan_cache.hit_rate",
                "shards.live", "search.latency_us", "search.hops",
                "search.beam_occupancy"):
        assert key in snap, key
    assert snap["search.latency_us"]["count"] == 1
    assert snap["search.hops"]["count"] == 4
    assert snap["service.n_deletes"] == 1


# ------------------------------------- profiler spans and session counters
def _session_delta(before: dict) -> dict:
    from repro import obs
    now = obs.registry().snapshot()
    return {k[len("session."):]: v - before.get(k, 0)
            for k, v in now.items() if k.startswith("session.")}


def _warm_service(idx, queries, **spec):
    from repro.serving.anns_service import AnnsService
    svc = AnnsService(idx, spec=SearchSpec(k=K, beam_width=BEAM,
                                           quantized=True, **spec))
    svc.search_many([queries, queries])      # trace + compile here
    return svc


def _xplane_spans(path: str, names: set) -> tuple[dict, set]:
    """({span name: sorted absolute start ns}, hlo_module names) of a
    profiler trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    base, starts, modules = None, {}, set()
    for plane in pd.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            base = stats["profile_start_time"]
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    starts.setdefault(ev.name, []).append(ev.start_ns)
                for k, v in ev.stats:
                    if k == "hlo_module":
                        modules.add(v)
    assert base is not None, "trace holds no profile_start_time"
    return ({n: sorted(base + t for t in ts) for n, ts in starts.items()},
            modules)


def test_profiler_trace_holds_program_spans(built, tmp_path):
    """A CPU profiler trace of a small search_many holds the program's
    spans and the `jit_jasper_search` program, and each span starts
    within 1 ms of the same span's SpanTracer record."""
    import glob

    import jax

    from repro.obs.tracing import SpanTracer, use_tracer

    idx, queries = built
    svc = _warm_service(idx, queries)
    names = {"service.search_many", "searcher.submit", "searcher.wait",
             "searcher.land"}
    tr = SpanTracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with use_tracer(tr):
            svc.search_many([queries] * 3)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    starts, modules = _xplane_spans(path, names)
    assert "jit_jasper_search" in modules, sorted(modules)
    mine = {}
    for e in tr.events():
        mine.setdefault(e["name"], []).append(e["ts"] * 1e3)
    for name in names:
        assert len(starts.get(name, [])) == len(mine[name]), name
        for a, b in zip(starts[name], sorted(mine[name])):
            assert abs(a - b) < 1e6, (name, a - b)
    assert len(mine["searcher.submit"]) == 3
    assert len(mine["service.search_many"]) == 1


def test_span_sinks_off_and_on(tmp_path):
    """No tracer and no profiler: the shared no-op. Under a profiler
    session alone, a TraceAnnotation. SpanTracer stamps wall-clock ns."""
    import time

    import jax

    from repro.obs import tracing

    assert tracing.span("x", a=1) is tracing._NOOP
    tr = tracing.SpanTracer()
    t0 = time.time_ns()
    with tracing.use_tracer(tr):
        with tracing.span("y"):
            pass
    t1 = time.time_ns()
    (evt,) = tr.events()
    assert t0 / 1e3 <= evt["ts"] <= evt["ts"] + evt["dur"] <= t1 / 1e3
    with jax.profiler.trace(str(tmp_path)):
        assert isinstance(tracing.span("z"), jax.profiler.TraceAnnotation)


def test_session_counters_count_landed_batches(built):
    """After N batches: session.batches == N, hops == sum of n_hops,
    trips == sum of each batch's max n_hops; every dispatch that did not
    trace is timed, and one that traced is left out of the dispatch
    counters (the plan cache counts its trace)."""
    from repro import obs

    idx, queries = built
    svc = _warm_service(idx, queries)
    before = obs.registry().snapshot()
    tickets = svc.search_many([queries, queries[::-1], queries * 2])
    d = _session_delta(before)
    assert d["batches"] == 3 and d["rows"] == 3 * Q
    assert d["hops"] == sum(int(t.n_hops.sum()) for t in tickets)
    assert d["trips"] == sum(int(t.n_hops.max()) for t in tickets)
    assert d["dispatches"] == 3 and d["dispatch_s"] > 0
    assert d["wait_s"] >= 0 and d["land_s"] > 0
    # a new query shape traces: the plan cache counts it, and the
    # dispatch counters (host time without compiles) leave it out
    before = obs.registry().snapshot()
    traces = idx.plans.stats.traces
    svc.search_many([queries[:3]])
    d = _session_delta(before)
    assert idx.plans.stats.traces == traces + 1
    assert d["batches"] == 1
    assert d.get("dispatches", 0) == 0 and d.get("dispatch_s", 0) == 0
    # the service's snapshot folds the process-wide registry in, labelled
    # as process-wide
    snap = svc.metrics_snapshot()
    assert "session.batches" not in snap
    assert snap["process.session.batches"] == obs.registry().snapshot()[
        "session.batches"]


def test_trips_is_the_while_loop_trip_count(built):
    """On the unfused while_loop at expand=1 the batch's max n_hops is
    the loop's trip count: the visited log is filled exactly up to it."""
    idx, queries = built
    from repro.core.beam_search import beam_search_quantized
    from repro.core.rabitq import rabitq_preprocess_query

    core = idx.core
    rq = rabitq_preprocess_query(core.rq_params, jnp.asarray(queries))
    res = beam_search_quantized(idx.graph, core.codes, rq,
                                beam_width=BEAM, max_iters=64)
    vlog = np.asarray(res.visited_ids)
    trips = int(np.asarray(res.n_hops).max())
    assert (vlog[:, :trips] >= 0).any(axis=0).all()
    assert (vlog[:, trips:] == -1).all()


@pytest.mark.parametrize("quantized,kernels,fusion", GRID)
def test_scopes_and_counters_bitwise_identity(built, monkeypatch,
                                              quantized, kernels, fusion):
    """The search program with named scopes, spans and counters in place
    gives bit-identical ids, dists and n_hops to the same program traced
    with every named scope taken out; a second search with a tracer
    installed adds no plan-cache entry and no trace."""
    import contextlib

    import jax

    from repro.core.index_core import core_search
    from repro.obs.tracing import SpanTracer, use_tracer

    idx, queries = built
    ses = idx.searcher(_spec(quantized, kernels, fusion))
    res = ses.search(queries)
    entries, traces = len(idx.plans), idx.plans.stats.traces
    with use_tracer(SpanTracer()):
        res2 = ses.search(queries)
    assert len(idx.plans) == entries
    assert idx.plans.stats.traces == traces
    assert np.array_equal(np.asarray(res.ids), np.asarray(res2.ids))

    def program():
        # a fresh function each time: jit caches traces per function
        def bare(core, q):
            return core_search.__wrapped__(core, q, spec=ses.resolved,
                                           filter_tombstones=False)
        return jax.jit(bare)
    q = idx._prep_query(queries)
    scoped_text = program().lower(idx.core, q).as_text(debug_info=True)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = program()
    plain_text = plain.lower(idx.core, q).as_text(debug_info=True)
    assert "hop.select" in scoped_text or fusion != "none"
    assert "search.finalize" in scoped_text
    assert "search.finalize" not in plain_text and "hop." not in plain_text
    ids, dists, n_hops = plain(idx.core, q)[:3]
    assert np.array_equal(np.asarray(res.ids), np.asarray(ids))
    assert np.array_equal(np.asarray(res.dists).view(np.int32),
                          np.asarray(dists).view(np.int32))
    assert np.array_equal(np.asarray(res.n_hops), np.asarray(n_hops))


def test_scheduler_landing_carries_telemetry(built):
    """The scheduler's harvest lands through the one landing helper, so
    a telemetry-on batch keeps its counters (they were dropped before)."""
    from repro.serving.scheduler import _AsyncBatch

    idx, queries = built
    ses = idx.searcher(_spec(True, False, "none", telemetry="on"))
    got = _AsyncBatch(ses.search(queries)).take()
    assert got.telemetry is not None
    assert all(isinstance(t, np.ndarray) for t in got.telemetry)
    assert isinstance(got.ids, np.ndarray)
    want = ses.search(queries).telemetry
    for a, b in zip(got.telemetry, want):
        assert np.array_equal(a, np.asarray(b))


"""Standing-query scheduler: flush policy under a fake clock (no
wall-clock sleeps anywhere in this module), padding hygiene (coalesced
padded dispatch is bit-identical to per-query dispatch and padding rows
never leak into tickets), priority lanes, backpressure shedding, the
LRU-bounded plan cache, and the zero-steady-state-retrace contract under
mixed-spec open-loop traffic."""

import numpy as np
import pytest

from repro.core.construction import ConstructionParams
from repro.core.index import JasperIndex
from repro.core.search_spec import (
    BUCKET_LADDER,
    PlanCache,
    SearchResult,
    SearchSpec,
    bucket_for,
    pad_to_bucket,
)
from repro.serving.anns_service import AnnsService
from repro.serving.loadgen import bursty_trace, poisson_trace
from repro.serving.scheduler import (
    SchedulerConfig,
    StandingQueryScheduler,
    summarize_handles,
)

SMALL = ConstructionParams(degree_bound=16, alpha=1.2, beam_width=16,
                           max_iters=24, rev_cap=16, prune_chunk=256)
DIMS = 24


# ---------------------------------------------------------------------------
# Deterministic harness: fake clock + fake dispatch (manual readiness)
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class FakeBatch:
    """ready()/take() protocol with manual readiness."""

    def __init__(self, n: int, k: int = 3):
        self.ready_flag = False
        self._n, self._k = n, k

    def ready(self) -> bool:
        return self.ready_flag

    def take(self) -> SearchResult:
        n, k = self._n, self._k
        ids = np.arange(n * k, dtype=np.int32).reshape(n, k)
        return SearchResult(ids=ids, dists=ids.astype(np.float32),
                            n_hops=np.zeros(n, np.int32), generation=0)


class FakeLaneDispatch:
    """Records every dispatched batch shape; batches complete only when
    the test flips them ready."""

    def __init__(self):
        self.batches: list[FakeBatch] = []
        self.shapes: list[tuple] = []

    def __call__(self, queries) -> FakeBatch:
        self.shapes.append(tuple(queries.shape))
        b = FakeBatch(queries.shape[0])
        self.batches.append(b)
        return b

    def finish_all(self) -> None:
        for b in self.batches:
            b.ready_flag = True


def make_sched(clock, *, lanes=("default",), priorities=None, **cfg):
    cfg.setdefault("buckets", (1, 8, 32))
    cfg.setdefault("slo_budget_s", 1.0)
    sched = StandingQueryScheduler(clock=clock, **cfg)
    dispatches = {}
    for i, name in enumerate(lanes):
        d = FakeLaneDispatch()
        prio = priorities[i] if priorities else 0
        sched.add_lane(name, dispatch=d, priority=prio)
        dispatches[name] = d
    return sched, dispatches


Q = np.zeros(DIMS, np.float32)


# ---------------------------------------------------------------------------
# Bucket / padding helpers
# ---------------------------------------------------------------------------

def test_bucket_for_ladder():
    assert [bucket_for(n) for n in (1, 2, 8, 9, 32, 33, 128, 500)] == \
        [1, 8, 8, 32, 32, 128, 128, 128]
    assert bucket_for(3, (4, 16)) == 4
    with pytest.raises(ValueError):
        bucket_for(0)


def test_pad_to_bucket_repeats_last_row_and_reports_valid_count():
    q = np.arange(3 * DIMS, dtype=np.float32).reshape(3, DIMS)
    padded, n = pad_to_bucket(q, (1, 8))
    assert n == 3 and padded.shape == (8, DIMS)
    assert np.array_equal(padded[:3], q)
    assert np.array_equal(padded[3:], np.repeat(q[-1:], 5, axis=0))
    exact, n2 = pad_to_bucket(q[:1], (1, 8))
    assert n2 == 1 and exact.shape == (1, DIMS)   # exact rung: no copy


# ---------------------------------------------------------------------------
# Flush policy (fake clock — zero wall-clock dependence)
# ---------------------------------------------------------------------------

def test_idle_flush_serves_partial_batch_immediately():
    """Device idle -> a partial batch dispatches at once (latency when
    idle); batching only happens while the device is busy."""
    clk = FakeClock()
    sched, d = make_sched(clk)
    sched.submit(Q)
    sched.submit(Q)
    sched.poll()
    assert d["default"].shapes == [(8, DIMS)]     # 2 padded up to rung 8
    assert sched.stats.flush_idle == 1
    assert sched.stats.padded_rows == 6
    assert sched.stats.dispatched == 2


def test_bucket_full_flush_while_busy():
    """With work in flight, a queue reaching the top bucket flushes for
    reason 'full' (throughput when loaded)."""
    clk = FakeClock()
    sched, d = make_sched(clk, max_inflight=2)
    sched.submit(Q)
    sched.poll()                                  # idle flush, now busy
    for _ in range(32):
        sched.submit(Q)
    sched.poll()
    assert d["default"].shapes == [(1, DIMS), (32, DIMS)]
    assert sched.stats.flush_full == 1
    assert sched.stats.mean_batch_occupancy == 1.0


def test_deadline_flush_at_budget_half_spent():
    """While the device is busy a partial batch waits — until the oldest
    query's SLO budget is flush_fraction spent, then it goes."""
    clk = FakeClock()
    sched, d = make_sched(clk, max_inflight=2, slo_budget_s=1.0,
                          flush_fraction=0.5)
    sched.submit(Q)
    sched.poll()                                  # occupy the device
    assert d["default"].shapes == [(1, DIMS)]
    sched.submit(Q, slo_budget_s=1.0)
    clk.advance(0.49)
    sched.poll()
    assert len(d["default"].shapes) == 1          # 49% spent: still waiting
    clk.advance(0.02)
    sched.poll()                                  # 51% spent: flush
    assert d["default"].shapes[-1] == (1, DIMS)
    assert sched.stats.flush_deadline == 1


def test_per_query_slo_override_drives_deadline():
    clk = FakeClock()
    sched, d = make_sched(clk, max_inflight=2, slo_budget_s=10.0)
    sched.submit(Q)
    sched.poll()                                  # occupy the device
    sched.submit(Q, slo_budget_s=0.010)           # tight per-query budget
    clk.advance(0.006)
    sched.poll()
    assert sched.stats.flush_deadline == 1        # 60% of 10ms spent


def test_deadline_is_min_over_queue_not_head():
    """Regression: a tight-budget query queued BEHIND a lax one must pull
    the flush forward. The old policy only looked at the queue head's
    budget, so the tight query's deadline was invisible until the lax
    head's (much later) deadline fired."""
    clk = FakeClock()
    sched, d = make_sched(clk, max_inflight=2, slo_budget_s=10.0)
    sched.submit(Q)
    sched.poll()                                  # occupy the device
    sched.submit(Q, slo_budget_s=10.0)            # lax head: deadline @ 5s
    clk.advance(0.001)
    sched.submit(Q, slo_budget_s=0.010)           # tight: deadline @ 6ms
    clk.advance(0.004)
    sched.poll()
    assert sched.stats.flush_deadline == 0        # tight at 40%: waiting
    clk.advance(0.003)                            # tight now 70% spent
    sched.poll()                                  # head-only policy would
    assert sched.stats.flush_deadline == 1        # have slept until ~5s
    # both queries left in the SAME flush (FIFO: head goes with it)
    assert d["default"].shapes[-1] == (8, DIMS)
    assert sched.stats.dispatched == 3


def test_priority_lane_dispatch_order():
    """Both lanes overdue, one dispatch slot: the lower priority value
    wins even though the other lane's query is older."""
    clk = FakeClock()
    sched, d = make_sched(clk, lanes=("lo", "hi"), priorities=(1, 0),
                          max_inflight=2, slo_budget_s=1.0)
    sched.submit(Q, lane="lo")
    sched.poll()                                  # idle flush goes to lo
    assert sched.flush_log[-1][0] == "lo"
    sched.submit(Q, lane="lo")
    clk.advance(0.01)
    sched.submit(Q, lane="hi")                    # younger than lo's
    clk.advance(0.6)                              # both overdue now
    sched.poll()                                  # ONE free slot
    assert sched.flush_log[-1][0] == "hi"         # priority beats age
    assert sched.inflight_depth == 2
    d["hi"].finish_all()
    d["lo"].finish_all()
    sched.poll()
    sched.poll()                                  # freed slots: lo drains
    assert [e[0] for e in sched.flush_log] == ["lo", "hi", "lo"]


def test_backpressure_sheds_to_rejected_ticket():
    clk = FakeClock()
    sched, d = make_sched(clk, max_queue=4, max_inflight=1)
    sched.submit(Q)
    sched.poll()                                  # in flight, never ready
    admitted = [sched.submit(Q) for _ in range(4)]
    shed = sched.submit(Q)
    assert all(h.status == "queued" for h in admitted)
    assert shed.status == "rejected" and shed.result is None
    assert sched.stats.rejected == 1
    assert sched.queue_depth == 4                 # bounded: no growth
    rep = summarize_handles([*admitted, shed], wall_s=1.0)
    assert rep["rejected"] == 1 and rep["completed"] == 0


def test_overlap_bounded_inflight_and_inorder_harvest():
    clk = FakeClock()
    sched, d = make_sched(clk, max_inflight=2, slo_budget_s=0.1)
    hs = [sched.submit(Q)]
    sched.poll()                                  # idle flush: batch 1
    hs.append(sched.submit(Q))
    clk.advance(1.0)
    sched.poll()                                  # deadline flush: batch 2
    assert sched.inflight_depth == 2              # double buffer is full
    hs.append(sched.submit(Q))
    clk.advance(1.0)
    sched.poll()
    assert sched.inflight_depth == 2              # bounded: no 3rd dispatch
    d["default"].batches[0].ready_flag = True
    done = sched.poll()                           # harvest head, dispatch 3
    assert [h.status for h in hs] == ["done", "inflight", "inflight"]
    assert done and done[0] is hs[0]
    assert len(d["default"].shapes) == 3
    d["default"].finish_all()
    done = sched.poll()
    assert all(h.status == "done" for h in hs)
    assert sched.stats.completed == 3
    # fake-clock latency accounting: all three spent fake time queueing
    assert all(h.latency_s is not None and h.latency_s >= 0 for h in hs)


def test_drain_flushes_everything_and_blocks():
    clk = FakeClock()
    sched, d = make_sched(clk, max_inflight=1)

    # auto-completing dispatch (ready immediately) so drain can finish
    class AutoBatch(FakeBatch):
        def ready(self):
            return True

    auto = []
    sched.add_lane("auto", dispatch=lambda q: (
        auto.append(tuple(q.shape)), AutoBatch(q.shape[0]))[1])
    hs = [sched.submit(Q, lane="auto") for _ in range(70)]
    done = sched.drain()
    assert all(h.status == "done" for h in hs)
    assert len(done) == 70
    assert sched.queue_depth == 0 and sched.inflight_depth == 0
    # 70 queries through ladder (1,8,32): two full 32s then a padded 8
    assert sched.stats.flush_drain >= 1
    assert sum(n for _, _, n, _ in sched.flush_log) == 70


def test_slo_miss_accounting():
    clk = FakeClock()
    sched, d = make_sched(clk, max_inflight=1, slo_budget_s=0.05)
    h = sched.submit(Q)
    sched.poll()
    clk.advance(1.0)                              # way past budget
    d["default"].finish_all()
    sched.poll()
    assert h.status == "done" and h.slo_met is False
    assert sched.stats.slo_misses == 1


def test_scheduler_config_validation():
    with pytest.raises(ValueError, match="flush_fraction"):
        SchedulerConfig(flush_fraction=0.0)
    with pytest.raises(ValueError, match="buckets"):
        SchedulerConfig(buckets=())
    with pytest.raises(ValueError, match=">= 1"):
        SchedulerConfig(max_inflight=0)
    assert SchedulerConfig(buckets=(32, 1, 8)).buckets == (1, 8, 32)
    with pytest.raises(KeyError):
        sched = StandingQueryScheduler(clock=FakeClock())
        sched.submit(Q, lane="nope")
    with pytest.raises(ValueError, match="need an index"):
        StandingQueryScheduler(clock=FakeClock()).add_lane("x")


# ---------------------------------------------------------------------------
# Real-index integration: padding hygiene + plan-cache behavior
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def built():
    rng = np.random.default_rng(11)
    idx = JasperIndex(DIMS, capacity=640, construction=SMALL,
                      quantization="rabitq", bits=4)
    idx.build(rng.normal(size=(500, DIMS)).astype(np.float32))
    queries = rng.normal(size=(5, DIMS)).astype(np.float32)
    return idx, queries


GRID = [
    ("exact/jnp", SearchSpec(k=5, beam_width=16)),
    ("exact/kernel", SearchSpec(k=5, beam_width=16, use_kernels=True)),
    ("rabitq/jnp", SearchSpec(k=5, beam_width=16, quantized=True)),
    ("rabitq/kernel", SearchSpec(k=5, beam_width=16, quantized=True,
                                 use_kernels=True)),
]


@pytest.mark.parametrize("label,spec", GRID, ids=[g[0] for g in GRID])
def test_coalesced_padded_equals_per_query_dispatch(built, label, spec):
    """THE padding-hygiene regression: a coalesced padded dispatch (5
    queries padded to the 8-bucket) is bit-identical, per query, to
    one-query-at-a-time dispatch through the same scheduler, on every
    backend cell — the batch a query lands in (and the padding rows
    that ride along) must never change its answer. Padding content
    differs between the two runs (repeat-last of 5 mixed rows vs a
    single row repeated 8x), so this also proves padding rows don't
    bleed into valid rows."""
    idx, queries = built
    sched = StandingQueryScheduler(idx, spec, buckets=(8,),
                                   slo_budget_s=10.0)
    handles = [sched.submit(q) for q in queries]
    sched.drain()
    assert sched.stats.batches == 1               # ONE coalesced dispatch
    assert sched.stats.padded_rows == 3
    solo_sched = StandingQueryScheduler(idx, spec, buckets=(8,),
                                        slo_budget_s=10.0)
    ses = idx.searcher(spec)
    for i, h in enumerate(handles):
        assert h.status == "done"
        solo_sched.submit(queries[i])
        (solo,) = solo_sched.drain()
        assert np.array_equal(h.ids, solo.ids), label
        assert np.array_equal(h.dists, solo.dists), label
        assert h.n_hops == solo.n_hops, label
        assert h.generation == solo.generation
        # the ticket is exactly k wide — no padding-row spill-over
        assert h.ids.shape == (5,) and h.dists.shape == (5,)
        # against the raw batch-1 executable: same neighbours always;
        # dists may drift by an ULP on the jnp path (XLA compiles a
        # different reduction for a different batch shape)
        raw = ses.search(queries[i:i + 1])
        assert np.array_equal(h.ids, np.asarray(raw.ids)[0]), label
        np.testing.assert_allclose(h.dists, np.asarray(raw.dists)[0],
                                   rtol=1e-6)


def test_mixed_spec_traffic_zero_steady_state_retraces(built):
    """Open-loop mixed-spec traffic (two lanes, every bucket shape):
    after one warmup pass the plan cache serves EVERYTHING — zero
    retraces, zero misses, across a fresh scheduler too (plans belong
    to the index, not the scheduler)."""
    idx, _ = built
    rng = np.random.default_rng(7)
    pool = rng.normal(size=(64, DIMS)).astype(np.float32)
    lanes = {"exact": (SearchSpec(k=5, beam_width=16), 1)}
    svc = AnnsService(idx, spec=SearchSpec(k=5, beam_width=16,
                                           quantized=True))
    trace = poisson_trace(5000.0, 150, n_queries=64, seed=3,
                          lanes=("default", "exact"),
                          lane_weights=(0.7, 0.3))
    # warmup: every (lane, rung) shape explicitly — which shapes a serve
    # pass coalesces depends on harvest timing (device readiness), so
    # traffic alone cannot deterministically cover the ladder
    for spec in (svc.spec, lanes["exact"][0]):
        ses = idx.searcher(spec)
        for b in (1, 8, 32):
            ses.search(pool[:b])
    svc.serve(trace, pool, lanes=lanes, buckets=(1, 8, 32),
              realtime=False)                     # warmup: scheduler path
    before = idx.plans.stats.snapshot()
    rep, handles = svc.serve(trace, pool, lanes=lanes, buckets=(1, 8, 32),
                             realtime=False)
    delta = idx.plans.stats.delta(before)
    assert delta["traces"] == 0, delta            # zero steady-state
    assert delta["misses"] == 0, delta
    assert rep["completed"] == 150 and rep["rejected"] == 0
    assert rep["flush_reasons"]["full"] + rep["flush_reasons"]["idle"] \
        + rep["flush_reasons"]["deadline"] + rep["flush_reasons"]["drain"] \
        == rep["batches"]


def test_serve_folds_service_stats_and_metrics(built):
    idx, _ = built
    rng = np.random.default_rng(8)
    pool = rng.normal(size=(16, DIMS)).astype(np.float32)
    svc = AnnsService(idx, spec=SearchSpec(k=5, beam_width=16,
                                           quantized=True))
    svc.metrics()                                 # histograms live
    trace = poisson_trace(3000.0, 40, n_queries=16, seed=5)
    rep, handles = svc.serve(trace, pool, buckets=(1, 8), realtime=False)
    assert svc.stats.n_search_queries == 40
    assert svc.stats.hops_sum > 0
    snap = svc.metrics_snapshot()
    assert snap["scheduler.completed"] == 40
    assert snap["scheduler.queue_depth"] == 0
    assert snap["scheduler.batch_occupancy"]["count"] == \
        snap["scheduler.batches"]
    assert snap["search.latency_us"]["count"] >= 40
    # the snapshot is the schema obs_report validates
    import importlib.util
    import json
    import pathlib
    json.dumps(snap)
    loc = importlib.util.spec_from_file_location(
        "obs_report",
        pathlib.Path(__file__).resolve().parents[1] / "scripts"
        / "obs_report.py")
    obs_report = importlib.util.module_from_spec(loc)
    loc.loader.exec_module(obs_report)
    obs_report.check_snapshot(snap)
    sched_series = obs_report.check_scheduler(snap)
    assert sched_series is not None
    assert sched_series["batches"] == sum(
        sched_series[f"flush_{r}"]
        for r in ("full", "deadline", "idle", "drain"))


def test_summarize_handles_times_from_due():
    """Latency and SLO count from the due time where one is stamped (on
    the handle itself, so the summary agrees with it), and the
    generator's lateness is reported; handles without a due time keep
    their submit-based values."""
    from repro.serving.scheduler import QueryHandle

    def handle(t_due, t_submit, t_done):
        h = QueryHandle(None, "default", 0.010, t_submit, status="done")
        h.t_due, h.t_done = t_due, t_done
        return h

    plain = summarize_handles([handle(None, 1.0, 1.005)], wall_s=1.0)
    assert plain["p50_ms"] == pytest.approx(5.0)
    assert plain["slo_hit_rate"] == 1.0
    assert plain["lateness_p99_ms"] is None
    late = handle(0.990, 1.0, 1.005)
    # one definition: the handle's own latency and SLO count from t_due
    assert late.latency_s == pytest.approx(0.015)
    assert late.slo_met is False
    due = summarize_handles([late, handle(1.000, 1.0, 1.002)], wall_s=1.0)
    assert due["max_ms"] == pytest.approx(15.0)
    assert due["slo_hit_rate"] == 0.5          # 15 ms > the 10 ms budget
    assert due["lateness_max_ms"] == pytest.approx(10.0)
    assert due["lateness_p99_ms"] == pytest.approx(9.9)


def test_serve_in_real_time_stamps_due_times(built):
    """A real-time replay stamps each handle with the arrival's due time
    on the loop's clock; the loop submits at or after it, and the report
    gives the lateness."""
    idx, _ = built
    rng = np.random.default_rng(9)
    pool = rng.normal(size=(16, DIMS)).astype(np.float32)
    svc = AnnsService(idx, spec=SearchSpec(k=5, beam_width=16,
                                           quantized=True))

    class StepClock(FakeClock):
        def __call__(self) -> float:
            self.t += 1e-4                        # every read moves on
            return self.t

    trace = poisson_trace(2000.0, 20, n_queries=16, seed=4)
    clk = StepClock()
    rep, handles = svc.serve(trace, pool, buckets=(1, 8), clock=clk)
    t0 = handles[0].t_due - trace[0].at
    assert [h.t_due for h in handles] == pytest.approx(
        [t0 + a.at for a in trace])
    assert all(h.t_submit >= h.t_due for h in handles)
    assert rep["lateness_max_ms"] == pytest.approx(
        max(h.t_submit - h.t_due for h in handles) * 1e3)
    assert rep["lateness_p99_ms"] <= rep["lateness_max_ms"]
    # a saturation replay has no due times
    rep, handles = svc.serve(trace, pool, buckets=(1, 8), realtime=False)
    assert all(h.t_due is None for h in handles)
    assert rep["lateness_max_ms"] is None


def test_rejected_handles_carry_no_query_payload(built):
    idx, queries = built
    sched = StandingQueryScheduler(
        idx, SearchSpec(k=5, beam_width=16), buckets=(1,),
        max_queue=1, max_inflight=1, slo_budget_s=10.0)
    a = sched.submit(queries[0])
    b = sched.submit(queries[1])                  # queue full -> shed
    assert b.status == "rejected" and b.query is None
    done = sched.drain()
    assert a.status == "done" and len(done) == 1


# ---------------------------------------------------------------------------
# LRU-bounded plan cache
# ---------------------------------------------------------------------------

def test_plan_cache_lru_eviction_and_counter():
    cache = PlanCache(capacity=2)
    built = []

    def builder(tag):
        def build():
            built.append(tag)
            return tag
        return build

    assert cache.get("a", builder("a")) == "a"
    assert cache.get("b", builder("b")) == "b"
    assert cache.get("a", builder("a2")) == "a"   # hit refreshes a's recency
    assert cache.get("c", builder("c")) == "c"    # evicts b (LRU), not a
    assert cache.stats.evictions == 1
    assert cache.get("a", builder("a3")) == "a"   # a survived
    assert cache.get("b", builder("b2")) == "b2"  # b is gone: rebuilt
    assert cache.stats.evictions == 2
    assert len(cache) == 2
    assert built == ["a", "b", "c", "b2"]
    assert cache.stats.as_dict()["evictions"] == 2


def test_plan_cache_capacity_validation_and_shrink():
    with pytest.raises(ValueError):
        PlanCache(capacity=0)
    cache = PlanCache()                            # unbounded default
    for i in range(5):
        cache.get(i, lambda i=i: (lambda: i))
    assert len(cache) == 5 and cache.stats.evictions == 0
    cache.capacity = 2                             # shrinking evicts now
    assert len(cache) == 2 and cache.stats.evictions == 3


def test_index_plan_cache_capacity_kwarg_and_snapshot():
    rng = np.random.default_rng(3)
    idx = JasperIndex(DIMS, capacity=320, construction=SMALL,
                      plan_cache_capacity=2)
    idx.build(rng.normal(size=(200, DIMS)).astype(np.float32))
    q = rng.normal(size=(4, DIMS)).astype(np.float32)
    base = len(idx.plans)                          # build-time plans, if any
    for k in (3, 4, 5):                            # 3 distinct search plans
        idx.searcher(SearchSpec(k=k, beam_width=16)).search(q)
    assert len(idx.plans) <= 2
    assert idx.plans.stats.evictions >= 1 + max(0, base - 2)
    svc = AnnsService(idx, spec=SearchSpec(k=5, beam_width=16))
    snap = svc.metrics_snapshot()
    assert snap["plan_cache.capacity"] == 2
    assert snap["plan_cache.evictions"] == idx.plans.stats.evictions


def test_bursty_trace_mean_rate_and_determinism():
    t1 = bursty_trace(500.0, 400, n_queries=8, seed=9)
    t2 = bursty_trace(500.0, 400, n_queries=8, seed=9)
    assert t1 == t2                                # seeded: byte-identical
    # long-run mean offered rate stays within 2x of nominal (it's a
    # random modulated process; exactness is not the contract)
    dur = t1[-1].at
    assert 0.5 * 500 <= len(t1) / dur <= 2.0 * 500
    # arrival times strictly increase and queries hit the pool
    ats = [a.at for a in t1]
    assert all(b > a for a, b in zip(ats, ats[1:]))
    assert all(0 <= a.query_id < 8 for a in t1)

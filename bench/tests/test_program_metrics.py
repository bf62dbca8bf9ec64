"""The per-layer readers of the program's own names and counters
(`search_program_ms.batch`, `loop_trips.batch`, `trip_device_us.batch`,
`host_us.batch`): each on a constructed run, each silent on a run whose
program keeps no such name or counter.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench/tests
"""

from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import registry  # noqa: E402

NEW = ("search_program_ms.batch", "loop_trips.batch", "trip_device_us.batch",
       "host_us.batch")


def read(name, run):
    return registry.layer_metric(name).read(run)


@pytest.fixture
def session(monkeypatch):
    """A fresh process-wide registry in the program, so that counts of
    other tests stay out."""
    from repro.obs import metrics
    reg = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "_PROCESS", reg)
    return reg


def count(reg, **values):
    for name, v in values.items():
        reg.counter(f"session.{name}").inc(v)


def traced_run(program_s=36.4, batches=100):
    return SimpleNamespace(
        trace=SimpleNamespace(modules_s={"jit_jasper_search": program_s,
                                         "jit_core_insert_at": 9.0}),
        counters={"batches": batches})


def test_each_reader_on_a_constructed_run(session):
    count(session, batches=104, trips=104 * 90, rows=104 * 1000,
          dispatches=103, dispatch_s=103 * 150e-6, land_s=104 * 250e-6)
    run = traced_run()
    assert read("search_program_ms.batch", run) == pytest.approx(364.0)
    assert read("loop_trips.batch", run) == pytest.approx(90.0)
    assert read("trip_device_us.batch", run) == pytest.approx(
        364.0 * 1e3 / 90.0)
    assert read("host_us.batch", run) == pytest.approx(400.0)


def test_readers_are_silent_without_the_program_name_or_counters(session):
    run = SimpleNamespace(
        trace=SimpleNamespace(modules_s={"jit_run": 36.4}),
        counters={"batches": 100})
    for name in NEW:
        assert read(name, run) is None, name
    for name in NEW:
        assert read(name, SimpleNamespace(trace=None, counters={})) is None


def test_readers_are_silent_on_a_program_without_a_registry(monkeypatch):
    from repro import obs
    monkeypatch.delattr(obs, "registry")
    run = traced_run()
    assert read("search_program_ms.batch", run) == pytest.approx(364.0)
    for name in ("loop_trips.batch", "trip_device_us.batch",
                 "host_us.batch"):
        assert read(name, run) is None, name


def test_counters_of_a_real_search_feed_the_readers(session):
    """Batches landed through the service: loop trips are the mean of
    each batch's largest hop count, and host time is positive."""
    from repro.core.construction import ConstructionParams
    from repro.core.index import JasperIndex
    from repro.core.search_spec import SearchSpec
    from repro.serving.anns_service import AnnsService

    rng = np.random.default_rng(11)
    data = rng.normal(size=(300, 16)).astype(np.float32)
    queries = rng.normal(size=(3, 8, 16)).astype(np.float32)
    idx = JasperIndex(16, capacity=512, quantization="rabitq", bits=4,
                      seed=11, construction=ConstructionParams(
                          degree_bound=16, beam_width=16, max_iters=24,
                          rev_cap=16, prune_chunk=256))
    idx.build(data)
    svc = AnnsService(idx, spec=SearchSpec(k=5, beam_width=16,
                                           quantized=True))
    tickets = svc.search_many(list(queries))
    run = SimpleNamespace(trace=None, counters={"batches": len(tickets)})
    want = np.mean([t.n_hops.max() for t in tickets])
    assert read("loop_trips.batch", run) == pytest.approx(want)
    assert read("host_us.batch", run) > 0
    assert read("trip_device_us.batch", run) is None   # no trace

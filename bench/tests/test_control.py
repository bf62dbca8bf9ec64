"""The comparison that decides `correct` fails what it has to fail.

At a size a CPU test can hold, each cell is driven through the whole of
a run except the look for a chip: once as the program is, which must come
out correct, and once with the timed path broken underneath in each way
the cell can break, which must come out not correct. The control (the
reference computed in bfloat16, in the program's place) must fail too.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench/tests
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import check  # noqa: E402
import registry  # noqa: E402

BENCHMARK = registry.benchmark()
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
SEED = 2**31 + 99

# sizes a CPU run holds: a build of this many rows is one all-pairs
# bootstrap, no batch rungs
SMALL_CONFIG = {"rows": 1024, "query_pool": 200}
SMALL_TRAFFIC = {"closed_batch": {"batch": 100}}


def small(cell: str):
    w = registry.cell(cell, BENCHMARK)
    cfg = dict(registry.config(w["config"]), **SMALL_CONFIG)
    traffic = registry.traffic(w["traffic"])
    traffic = dict(traffic, **SMALL_TRAFFIC[traffic["loop"]])
    return cfg, traffic


def run_small(cell: str) -> dict:
    import run
    cfg, traffic = small(cell)
    return run.run_cell(cell, cfg, traffic, seed=SEED, seconds=1.0,
                        trace=False, device={"platform": "cpu", "count": 1,
                                             "kind": "cpu"},
                        bench=BENCHMARK, limits=check.limits(cell))


# ------------------------------------------------------------------ faults
def patch_search(monkeypatch, fault):
    """`Searcher._dispatch`, where every search's answer is produced (the
    synchronous and the pipelined path), answers as
    `fault(searcher, queries, result)` says."""
    from repro.core.search_spec import Searcher
    orig = Searcher._dispatch

    def dispatch(self, queries):
        return fault(self, queries, orig(self, queries))
    monkeypatch.setattr(Searcher, "_dispatch", dispatch)


def answer_altered(monkeypatch):
    """Each answer's first id replaced by the next row's."""
    import jax.numpy as jnp

    def fault(searcher, queries, res):
        ids = np.asarray(res.ids).copy()
        ids[:, 0] = (ids[:, 0] + 1) % int(searcher.index.core.n_valid)
        return res._replace(ids=jnp.asarray(ids))
    patch_search(monkeypatch, fault)


def half_batch_left_out(monkeypatch):
    """The second half of a batch gets the first half's answers."""
    import jax.numpy as jnp

    def fault(searcher, queries, res):
        n = np.asarray(res.ids).shape[0]
        half = n // 2
        fill = lambda a: jnp.concatenate(  # noqa: E731
            [a[:n - half], a[:half]])
        return res._replace(ids=fill(res.ids), dists=fill(res.dists))
    patch_search(monkeypatch, fault)


def walk_never_advances(monkeypatch):
    """A hop that returns the walk's state unchanged: the answer is the
    entry point's frontier (the medoid and its out-edges), ranked and
    returned at their exact distances, as a reranked search would."""
    import jax.numpy as jnp

    def fault(searcher, queries, res):
        core = searcher.index.core
        m = int(core.medoid)
        front = np.concatenate([[m], np.asarray(core.adjacency[m])])
        front = np.unique(front[front >= 0])
        rows = np.asarray(core.vectors)[front]
        q = np.asarray(queries, np.float32)
        d = ((q[:, None, :] - rows[None]) ** 2).sum(-1)
        k = np.asarray(res.ids).shape[1]
        order = np.argsort(d, axis=1)[:, :k]
        ids = np.full((q.shape[0], k), -1, np.int32)
        dists = np.full((q.shape[0], k), np.inf, np.float32)
        ids[:, :order.shape[1]] = front[order]
        dists[:, :order.shape[1]] = np.take_along_axis(d, order, 1)
        return res._replace(ids=jnp.asarray(ids), dists=jnp.asarray(dists))
    patch_search(monkeypatch, fault)


def no_answer(monkeypatch):
    """Every answer is empty: all ids -1."""
    import jax.numpy as jnp

    def fault(searcher, queries, res):
        return res._replace(ids=jnp.full_like(res.ids, -1))
    patch_search(monkeypatch, fault)


FAULTS = {"answer_altered": answer_altered,
          "half_batch_left_out": half_batch_left_out,
          "walk_never_advances": walk_never_advances,
          "no_answer": no_answer}


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(cell):
    res = run_small(cell)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = run_small(cell)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(cell, monkeypatch):
    import control
    cfg, _ = small(cell)
    monkeypatch.setattr(registry, "config", lambda name: cfg)
    numbers = control.readings(registry.cell(cell, BENCHMARK), SEED)
    correct, checks = check.verdict(numbers, check.limits(cell))
    assert not correct, checks

"""Checks of the benchmark harness that need no chip: trace reduction, the
reference, the work function, the comparison, discovery by name, the
peaks table, and the command's refusal to run without a TPU.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench/tests
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import registry  # noqa: E402
import trace_reduce as tr  # noqa: E402
import work  # noqa: E402

BENCHMARK = registry.benchmark()
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


# --------------------------------------------------------- trace reduction
def small_trace() -> tr.Trace:
    """Window [0, 100) ns; device ops at [10, 30), [20, 40) (overlapping)
    and [60, 70); one op outside the window; host annotations around the
    first two calls."""
    ops = [("fusion.1", 10.0, 20.0), ("fusion.2", 20.0, 20.0),
           ("gather.3", 60.0, 10.0), ("late", 150.0, 5.0)]
    mods = [("jit_run", 10.0, 30.0), ("jit_core_insert_at", 60.0, 10.0)]
    return tr.Trace(
        devices={"/device:TPU:0": {tr.OPS_LINE: ops,
                                   tr.MODULES_LINE: mods}},
        host=[("bench.window", 0.0, 100.0), ("bench.search", 5.0, 40.0),
              ("bench.insert", 55.0, 20.0)])


def test_names_are_shortened_to_the_instruction_and_program():
    assert tr.short("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), "
                    "kind=kLoop") == "fusion.12"
    assert tr.short("jit_core_insert_at(2178148576420754758)") == \
        "jit_core_insert_at"


def test_control_flow_is_left_out_of_the_top_ops():
    t = small_trace()
    t.devices["/device:TPU:0"][tr.OPS_LINE].append(("while.28", 5.0, 80.0))
    assert "while.28" not in [n for n, _ in tr.top_ops(t, 0.0, 100.0)]
    assert tr.busy_ns(t, 0.0, 100.0) == 80.0


def test_busy_is_the_union_of_device_ops():
    t = small_trace()
    assert tr.union([(10, 30), (20, 40), (60, 70)]) == [(10, 40), (60, 70)]
    assert tr.busy_ns(t, 0.0, 100.0) == 40.0


def test_summary_idle_share_programs_and_annotations():
    s = tr.summarize(small_trace())
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(40e-9)
    assert s.idle_share == pytest.approx(0.6)
    assert s.modules_s == pytest.approx({"jit_run": 30e-9,
                                         "jit_core_insert_at": 10e-9})
    assert s.annotated_s == pytest.approx({"bench.search": 30e-9,
                                           "bench.insert": 10e-9})
    assert [n for n, _ in s.device_ops] == ["fusion.1", "fusion.2",
                                            "gather.3"]


def test_idle_gaps_are_named_by_the_host_annotation_over_them():
    s = tr.summarize(small_trace())
    # gaps [70, 100), [40, 60) and [0, 10): only the last is half covered
    assert s.gaps == [["host", pytest.approx(30e-9)],
                      ["host", pytest.approx(20e-9)],
                      ["bench.search", pytest.approx(10e-9)]]


def test_a_trace_without_a_window_is_refused():
    with pytest.raises(ValueError):
        tr.summarize(tr.Trace(devices={}, host=[]))


def test_readers_return_nothing_without_a_trace():
    run = SimpleNamespace(trace=None, counters={"batches": 3})
    for name in ("device_idle.batch", "search_device_ms.batch",
                 "search_roofline.batch"):
        assert registry.layer_metric(name).read(run) is None


# --------------------------------------------------------------- reference
def test_reference_topk_matches_numpy():
    import references.l2 as l2
    rng = np.random.default_rng(0)
    rows = rng.normal(size=(300, 16)).astype(np.float32)
    q = rng.normal(size=(7, 16)).astype(np.float32)
    d = ((q[:, None, :].astype(np.float64) - rows[None]) ** 2).sum(-1)
    want = np.argsort(d, axis=1)[:, :5]
    ids, dists = l2.topk(rows, q, 5, block=4)
    np.testing.assert_array_equal(ids, want)
    np.testing.assert_allclose(dists, np.take_along_axis(d, want, 1),
                               rtol=1e-4)
    np.testing.assert_allclose(l2.exact_d2(rows, q, ids),
                               np.take_along_axis(d, want, 1), rtol=1e-12)


def test_control_is_the_reference_in_bfloat16():
    import references.l2 as l2
    rng = np.random.default_rng(1)
    rows = rng.normal(size=(2000, 64)).astype(np.float32)
    q = rng.normal(size=(20, 64)).astype(np.float32)
    ids, d = l2.control_topk(rows, q, 10)
    ref_ids, _ = l2.topk(rows, q, 10)
    # bfloat16 keeps 8 significant bits: reported distances are rounded
    exact = l2.exact_d2(rows, q, ids)
    assert np.max(np.abs(d - exact) / exact) > 1e-3
    assert ids.shape == ref_ids.shape


# ------------------------------------------------------------------ work
def test_search_bytes_at_known_shapes():
    # bigann-128-l2: 4-bit codes of 128 dims = 64 B + 8 B of factors
    assert work.code_bytes(128, 4) == 72
    assert work.code_bytes(960, 4) == 488
    per_hop = 64 * 4 + 64 * 72
    assert work.search_bytes(10, dims=128, bits=4, degree=64, beam=64) == \
        10 * per_hop + 64 * 128 * 4
    assert work.search_flops(10, dims=128, degree=64, beam=64) == \
        10 * 64 * 256 + 64 * 3 * 128


def test_least_time_names_its_roof():
    peak = work.peaks("TPU v5 lite")
    t, roof = work.least_time(819e9, 1.0, peak)
    assert (t, roof) == (pytest.approx(1.0), "hbm")
    t, roof = work.least_time(1.0, 197e12 * 2, peak)
    assert (t, roof) == (pytest.approx(2.0), "bf16_flops")


def test_peaks_refuse_an_unknown_device_kind():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peaks("source")


# -------------------------------------------------------------- comparison
def answers(n_rows=400, n_q=30, k=5, seed=2):
    import references.l2 as l2
    rng = np.random.default_rng(seed)
    rows = rng.normal(size=(n_rows, 8)).astype(np.float32)
    q = rng.normal(size=(n_q, 8)).astype(np.float32)
    ids, _ = l2.topk(rows, q, k)
    return l2, rows, q, ids, l2.exact_d2(rows, q, ids).astype(np.float32)


def test_exact_answers_compare_clean():
    l2, rows, q, ids, d = answers()
    row_ids = np.arange(rows.shape[0]) + 1000         # system ids differ
    got = check.compare(l2, rows, row_ids, q, np.arange(q.shape[0]),
                        ids + 1000, d, 5)
    assert got["recall_miss"] == 0.0
    assert got["dist_gap"] < 1e-6
    assert got["bad_ids"] == 0


def test_wrong_duplicate_and_unknown_ids_are_caught():
    l2, rows, q, ids, d = answers()
    qidx = np.arange(q.shape[0])
    rid = np.arange(rows.shape[0])
    dup = ids.copy()
    dup[:, 1] = dup[:, 0]
    got = check.compare(l2, rows, rid, q, qidx, dup, d, 5)
    assert got["recall_miss"] == pytest.approx(0.2)
    assert got["recall_miss_p50"] == pytest.approx(0.2)
    bad = ids.copy()
    bad[0, 0] = rows.shape[0] + 5                     # never inserted
    got = check.compare(l2, rows, rid, q, qidx, bad, d, 5)
    assert got["bad_ids"] == 1
    shifted = np.roll(ids, 1, axis=0)                 # another query's answer
    got = check.compare(l2, rows, rid, q, qidx, shifted, d, 5)
    assert got["recall_miss"] > 0.5 and got["dist_gap"] > 0.1


def test_ties_at_the_kth_distance_are_hits():
    import references.l2 as l2
    rows = np.array([[0.0], [1.0], [-1.0], [5.0]], np.float32)
    q = np.zeros((1, 1), np.float32)
    # rows 1 and 2 tie for second: either is a correct answer at k = 2
    got = check.compare(l2, rows, np.arange(4), q, np.array([0]),
                        np.array([[0, 2]]), np.array([[0.0, 1.0]]), 2)
    assert got["recall_miss"] == 0.0


def test_verdict_holds_each_number_to_its_limit():
    ok, checks = check.verdict({"a": 0.1, "b": 0}, {"a": 0.2, "b": 0})
    assert ok and checks["a"] == {"value": 0.1, "limit": 0.2}
    ok, _ = check.verdict({"a": 0.3, "b": 0}, {"a": 0.2, "b": 0})
    assert not ok


# --------------------------------------------------------------- discovery
@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_finds_its_files_by_name(cell):
    w = registry.cell(cell, BENCHMARK)
    cfg = registry.config(w["config"])
    traffic = registry.traffic(w["traffic"])
    import loops
    assert traffic["loop"] in loops.LOOPS
    assert cfg["name"] == w["config"]
    assert registry.reference(cfg["metric"]).topk
    assert {"dist_gap", "bad_ids"} <= set(check.limits(cell)) <= {
        "recall_miss", "recall_miss_p50", "dist_gap", "bad_ids"}
    e2e = {m["name"] for m in registry.metrics_of(cell, BENCHMARK,
                                                  "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    per = registry.metrics_of(cell, BENCHMARK, "per_layer")
    assert per
    for m in per:
        assert registry.layer_metric(m["name"]).read


@pytest.mark.parametrize("kind,find", [
    ("config", registry.config), ("traffic", registry.traffic),
    ("layer metric", registry.layer_metric),
    ("reference", registry.reference), ("limits", check.limits)])
def test_unknown_names_are_refused(kind, find):
    for name in ("no-such-name", "../BENCHMARK", "a b"):
        with pytest.raises(KeyError):
            find(name)


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        registry.cell("no-such-cell", BENCHMARK)


def test_benchmark_names_a_file_for_every_entry():
    for c in BENCHMARK["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    for m in BENCHMARK["per_layer"]:
        assert (BENCH / "layer_metrics" / f"{m['name']}.py").is_file()


# ----------------------------------------------------------------- command
def run_command(cwd: Path, env: dict) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bigann-batch",
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = run_command(ROOT, env)
    assert p.returncode != 0
    assert p.stdout.strip() == "" or not p.stdout.strip().splitlines()[
        -1].startswith("{")
    assert "TPU" in p.stderr


def test_command_refuses_a_checkout_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = run_command(tmp_path, env)
    assert p.returncode != 0
    assert "{" not in p.stdout

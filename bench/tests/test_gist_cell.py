"""The gist-batch cell: GIST-960 (float32 rows, L2) under the batch mix.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench/tests
"""

from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import registry  # noqa: E402
import work  # noqa: E402

BENCHMARK = registry.benchmark()
CELL = "gist-batch"


def test_the_configuration_keeps_the_source_shapes():
    cfg = registry.config("gist-960-l2")
    assert (cfg["dataset"], cfg["dims"], cfg["metric"], cfg["dtype"]) == (
        "gist", 960, "l2", "float32")
    assert cfg["query_pool"] == 1000
    assert cfg["reduced"] == {"rows": [1_000_000, cfg["rows"]]}
    assert cfg["index"] == registry.config("bigann-128-l2")["index"]
    assert cfg["search"] == {"k": 10, "beam_width": 128, "quantized": True}
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == list(cfg["reduced"])


def test_the_cell_resolves_to_its_config_traffic_and_limits():
    w = registry.cell(CELL, BENCHMARK)
    assert (w["config"], w["traffic"], w["chips"]) == (
        "gist-960-l2", "batch", 1)
    traffic = registry.traffic(w["traffic"])
    assert registry.config(w["config"])["query_pool"] % traffic["batch"] == 0
    lim = check.limits(CELL)
    assert set(lim) == {"recall_miss_p50", "dist_gap", "bad_ids"}
    # float rows: dist_gap holds distances to float32 rounding, not to 0;
    # the bfloat16 control's median answer misses 1 of 10 (PERF.md)
    assert 0 < lim["dist_gap"] <= 1e-3 and lim["recall_miss_p50"] < 0.1
    assert lim["bad_ids"] == 0


def test_the_cell_reports_the_batch_metrics():
    e2e = {m["name"] for m in registry.metrics_of(CELL, BENCHMARK,
                                                  "end_to_end")}
    per = {m["name"] for m in registry.metrics_of(CELL, BENCHMARK,
                                                  "per_layer")}
    assert e2e == {"qps", "recall_at_10", "setup_s"}
    assert per == {"device_idle.batch", "search_device_ms.batch",
                   "hops_per_query.batch", "search_roofline.batch",
                   "build_rows_per_s", "search_program_ms.batch",
                   "loop_trips.batch", "trip_device_us.batch",
                   "host_us.batch"}
    assert per == {m["name"] for m in registry.metrics_of(
        "bigann-batch", BENCHMARK, "per_layer")}


def test_a_candidate_moves_488_bytes_of_code_at_960_dims():
    assert work.code_bytes(960, 4) == 488

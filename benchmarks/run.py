"""Benchmark driver: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only SECTION]

Prints ``name,us_per_call,derived`` CSV (benchmarks/common.Csv).
"""

from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import (
    construction,
    distributed,
    incremental,
    quantization,
    queries,
    roofline_anns,
    serving,
    tiering,
    tiles,
    updates,
)
from benchmarks.common import Csv
from repro.launch.compile_cache import enable_compile_cache

SECTIONS = {
    # paper Table 4
    "construction": lambda csv, fast: construction.run(
        csv, n=4000 if fast else None),
    # paper Figs 6-7
    "incremental": lambda csv, fast: incremental.run(
        csv, n=4000 if fast else None),
    # mutation engine: deletes/s, consolidation, recall vs churn
    "updates": lambda csv, fast: updates.run(
        csv, n=2000 if fast else None),
    # paper Fig 8
    "queries": lambda csv, fast: queries.run(
        csv, datasets=("bigann", "deep") if fast else
        ("bigann", "deep", "gist", "openai", "text2image"),
        n=4000 if fast else None),
    # paper Fig 12
    "quantization": lambda csv, fast: quantization.run(
        csv, n=3000 if fast else None),
    # paper Figs 10-11
    "tiles": lambda csv, fast: tiles.run(csv),
    # per-hop frontier merge A/B (partial top-L vs full sort vs kernel)
    "merge": lambda csv, fast: tiles.run_merge_ab(csv),
    # paper Fig 9 / §6.5
    "roofline_anns": lambda csv, fast: roofline_anns.run(
        csv, n=3000 if fast else None),
    # standing-query scheduler: coalescing A/B at saturation + open-loop
    # Poisson/bursty latency sweeps (emits BENCH_serving.json)
    "serving": lambda csv, fast: serving.run(
        csv, n=2000 if fast else None,
        n_arrivals=400 if fast else 2000),
    # tiered storage: device vs host rerank source at equal budget +
    # code-only floor (emits BENCH_tiering.json)
    "tiering": lambda csv, fast: tiering.run(
        csv, n=2000 if fast else None),
    # sharded search over this process's devices: QPS vs shard count +
    # merge-collective bytes (in-process: one process holds the chips)
    "distributed": lambda csv, fast: distributed.run(
        csv, n=2048 if fast else None),
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="reduced dataset sizes (CI-scale)")
    ap.add_argument("--only", action="append", default=None,
                    choices=sorted(SECTIONS))
    args = ap.parse_args()
    enable_compile_cache()

    csv = Csv()
    csv.header()
    failed = []
    for name in (args.only or list(SECTIONS)):
        print(f"# === {name} ===", flush=True)
        try:
            SECTIONS[name](csv, args.fast)
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            print(f"# SECTION FAILED {name}: {e!r}", flush=True)
            traceback.print_exc()
    if failed:
        print(f"# failed sections: {failed}", flush=True)
        sys.exit(1)
    print(f"# all sections complete ({len(csv.rows)} rows)", flush=True)


if __name__ == "__main__":
    main()

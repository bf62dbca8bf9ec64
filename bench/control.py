#!/usr/bin/env python3
"""Readings of the control: the plain reference computed in bfloat16, put
in the program's place, at a cell's own size.

    python bench/control.py --workload <cell> --seeds 11 12 13

For each seed it generates the cell's data as a run does (the
configuration's rows and query pool, alike for every seed), answers every
query of the pool with `control_topk` of the configuration's reference, and
prints the numbers that decide `correct` beside the cell's limits. The
control must come out as not correct; its smallest reading of each
number is the upper end a limit is set under (PERF.md), read on the
chip. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import datagen  # noqa: E402
import registry  # noqa: E402
from check import compare, limits, verdict  # noqa: E402

def readings(cell: dict, seed: int) -> dict:
    cfg = registry.config(cell["config"])
    k = cfg["search"]["k"]
    rows = datagen.rows(cfg, cfg["rows"], datagen.BASE)
    queries = datagen.rows(cfg, cfg["query_pool"], datagen.QUERIES)
    ref = registry.reference(cfg["metric"])
    pos, d = ref.control_topk(rows, queries, k)
    return compare(ref, rows, np.arange(rows.shape[0]), queries,
                   np.arange(queries.shape[0]), pos, d, k)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = registry.cell(args.workload, registry.benchmark())
    lim = limits(cell["name"])
    for seed in args.seeds:
        numbers = readings(cell, seed)
        correct, checks = verdict(numbers, lim)
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "control": "bfloat16 reference",
                          "correct": correct, "checks": checks}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

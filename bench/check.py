"""The comparison that decides `correct`: served answers against the plain
reference, each number beside its limit.

Numbers, for answers (query, returned ids, returned distances):

* `recall_miss`: 1 - recall@k, where a returned id is a hit when it is a
  live row whose exact distance is at most the reference's k-th exact
  distance (ann-benchmarks' rule, so that exact ties on the uint8 grid
  do not count as misses); duplicates and -1 count as misses.
* `recall_miss_p50`: the median over answers of each answer's share of
  misses: steady where a few queries of a graph are hard to reach, and
  moved by anything that degrades most answers.
* `dist_gap`: the largest gap between a returned distance and the exact
  distance of the row it names, over the query's k-th exact distance.
* `bad_ids`: returned ids that name no live row (never inserted, or
  deleted). The configuration's guarantee; its limit is 0.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

LIMITS = Path(__file__).resolve().parent / "limits"


def limits(cell: str) -> dict:
    path = LIMITS / f"{cell}.json"
    if not path.is_file():
        raise KeyError(f"no limits for workload {cell!r} ({path.name})")
    return json.loads(path.read_text())["limits"]


def compare(ref, rows: np.ndarray, row_ids: np.ndarray,
            queries: np.ndarray, qidx: np.ndarray, ids: np.ndarray,
            dists: np.ndarray, k: int) -> dict:
    """The numbers for answers `ids`/`dists` (n, k) to queries
    `queries[qidx]`, over live rows `rows` whose system ids are `row_ids`.
    `ref` is the reference module of the configuration's metric."""
    pos_of = np.full(int(max(row_ids.max(), ids.max(initial=0))) + 1, -1,
                     np.int64)
    pos_of[row_ids] = np.arange(row_ids.size)
    valid = ids >= 0
    pos = np.where(valid, pos_of[np.where(valid, ids, 0)], -1)
    bad = int(np.sum(valid & (pos < 0)))

    used = np.unique(qidx)
    ref_pos, _ = ref.topk(rows, queries[used], k)
    kth = ref.exact_d2(rows, queries[used], ref_pos).max(axis=1)
    kth_of = np.zeros(queries.shape[0])
    kth_of[used] = kth
    scale = kth_of[qidx]

    # answers repeat (a pool is served many times over): compute each
    # distinct (query, row) pair's exact distance once
    pairs, inv = np.unique(np.stack([np.repeat(qidx, k), pos.ravel()]),
                           axis=1, return_inverse=True)
    true = ref.exact_d2(rows, queries[pairs[0]], pairs[1][:, None])[
        inv.ravel(), 0].reshape(pos.shape)
    ok = pos >= 0
    order = np.argsort(pos, axis=1, kind="stable")
    sp = np.take_along_axis(pos, order, axis=1)
    dup_sorted = np.zeros_like(ok)
    dup_sorted[:, 1:] = sp[:, 1:] == sp[:, :-1]
    dup = np.zeros_like(ok)
    np.put_along_axis(dup, order, dup_sorted, axis=1)
    hit = ok & ~dup & (np.nan_to_num(true, nan=np.inf)
                       <= scale[:, None] * (1 + 1e-6))
    gap = np.where(ok, np.abs(dists - np.nan_to_num(true)), 0.0)
    return {
        "recall_miss": float(1.0 - hit.sum() / hit.size),
        "recall_miss_p50": float(np.median(1.0 - hit.mean(axis=1))),
        "dist_gap": float(np.max(gap / np.maximum(scale, 1e-30)[:, None])),
        "bad_ids": bad,
    }


def verdict(numbers: dict, lim: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}); a number is within its limit
    when it is at most the limit."""
    out = {n: {"value": numbers[n], "limit": lim[n]} for n in lim}
    return all(v["value"] <= v["limit"] for v in out.values()), out


def report(checks: dict) -> None:
    """Each number beside its limit, as the last lines on stderr."""
    for n, v in checks.items():
        print(f"check {n} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)

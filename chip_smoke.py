#!/usr/bin/env python3
"""Run the Jasper index end to end on a TPU and check what comes out.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # ShardedJasperIndex over four chips

Deployment: the BigANN-shaped dataset (`ANNS_DATASETS["bigann"]`: 128-d,
L2, values on the uint8 grid), generated from `--seed` and indexed by
`JasperIndex(128, quantization="rabitq", bits=4)` with the default
construction parameters (R=64, L=64). The scale of ann-benchmarks'
sift-128-euclidean is 1,000,000 rows; a run builds DEFAULT_ROWS of them
(FOUR_CHIP_ROWS with `--chips 4`) unless `--rows` says otherwise, and
prints the cut.

One chip: build; search 1,000 queries through `AnnsService` sessions on
five lanes (exact, quantized jnp, quantized kernel scorer, per-hop fused
kernel, megakernel); one `AnnsService.step` tick inserts 10,000 rows and
deletes 10,000, every lane searches again, a second tick consolidates,
and every lane searches once more. Each search is checked for recall@10
against a blocked brute-force scan of the live rows, for tombstoned ids,
and (fused lanes) for id agreement with their unfused jnp twin; each
kernel lane must show a Mosaic kernel (`tpu_custom_call`) in its compiled
program.

Four chips: the same deployment built over `make_mesh((4,), ("data",))`,
searched on the quantized jnp and megakernel lanes, checked against the
brute-force scan and against a single-chip index of the same rows at equal
total beam budget.

Every line but the last is progress; the last is one JSON object
`{"ok": true, "device": {...}}`. Any failed check exits non-zero, and so
does a run where JAX finds no TPU, before anything is built.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
sys.path.insert(0, str(SRC))

FULL_ROWS = 1_000_000   # sift-128-euclidean scale
# rows built by default: a 250,000-row run on one v5e inserted about 1.2
# rows per ms (build and the 10,000-row insert alike), so 1,000,000 rows
# plus a consolidation that re-links about half of them would take about
# 1,500 s cold, past the 1,200 s a smoke run may take
DEFAULT_ROWS = 300_000
# rows built by `--chips 4`: that phase builds twice (sharded, and the
# single-chip index it is compared with) and compiles every build rung of
# both cold; 8,192 rows (2,048 per shard) keep it to six build
# executables, a few minutes of compile on a v5e host
FOUR_CHIP_ROWS = 8192
N_QUERIES = 1000
K = 10
BEAM = 64
N_MUTATE = 10_000       # rows inserted, and rows deleted, in the mutate tick
BF_BLOCK = 100          # brute-force queries per block: (100, 1M) f32 = 400 MB

# recall@10 floors (checked against the blocked brute-force scan)
RECALL_FLOOR = {
    # Vamana with R=64 searched at L=64 over exact distances: DiskANN-class
    # graphs reach >= 0.95 at this beam on SIFT-scale data; 0.90 leaves
    # seed-to-seed room while any broken build, search or merge lands far
    # below it
    "exact": 0.90,
    # 4-bit RaBitQ traversal + exact rerank of the final frontier: the
    # estimator's error moves a few frontier members, the rerank restores
    # exact order, so a few points under the exact lane at most
    "quantized": 0.85,
    "quantized_kernel": 0.85,
    "hop": 0.85,
    "megakernel": 0.85,
}
# fused / kernel lanes vs their unfused jnp twin: the conformance suite's
# id-agreement bar (tests/test_conformance.py FUSED_CELLS)
TWIN = {"quantized_kernel": "quantized", "hop": "quantized",
        "megakernel": "quantized"}
TWIN_AGREEMENT = 0.95
KERNEL_LANES = ("quantized_kernel", "hop", "megakernel")


def say(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds XLA spends compiling (or fetching from the persistent cache),
    from JAX's own monitoring events, plus persistent-cache hits/misses."""

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


class Checks:
    def __init__(self):
        self.failed: list[str] = []

    def expect(self, ok: bool, what: str) -> None:
        say(f"check {'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            self.failed.append(what)


def recall_at_k(ids, gt) -> float:
    import numpy as np
    ids, gt = np.asarray(ids), np.asarray(gt)
    hits = (ids[:, :, None] == gt[:, None, :]) & (ids >= 0)[:, :, None]
    return float(np.mean(hits.any(axis=2).sum(axis=1) / gt.shape[1]))


def brute_force(index, queries):
    """Exact top-k (ids, dists) over the live rows, in query blocks on the
    device."""
    import numpy as np
    out = [index.brute_force(queries[i:i + BF_BLOCK], K)
           for i in range(0, queries.shape[0], BF_BLOCK)]
    return (np.concatenate([np.asarray(o[0]) for o in out]),
            np.concatenate([np.asarray(o[1]) for o in out]))


def kernel_calls(index, resolved, queries) -> int:
    """`tpu_custom_call`s in the compiled program of a lane's search."""
    import functools

    import jax
    from repro.core.index_core import core_search
    run = jax.jit(functools.partial(core_search, spec=resolved,
                                    filter_tombstones=True))
    q = index._prep_query(queries)
    return run.lower(index.core, q).compile().as_text().count(
        "tpu_custom_call")


def build_progress(t0: float):
    """A `build(progress_fn=...)` hook: one line per inserted batch."""
    def report(done: int, total: int) -> None:
        say(f"[build] {done}/{total} rows at "
            f"{time.perf_counter() - t0:.1f} s")
    return report


def lane_specs():
    from repro.core.search_spec import SearchSpec
    base = SearchSpec(k=K, beam_width=BEAM)
    return {
        "exact": base,
        "quantized": base.with_(quantized=True),
        "quantized_kernel": base.with_(quantized=True, use_kernels=True),
        "hop": base.with_(quantized=True, fusion="hop"),
        "megakernel": base.with_(quantized=True, fusion="megakernel"),
    }


def search_phase(phase, services, index, queries, checks, clock,
                 dead=None):
    """Every lane searches once; recall, tombstones and twins checked."""
    import numpy as np
    t = time.perf_counter()
    gt = brute_force(index, queries)[0]
    say(f"[{phase}] brute force: {time.perf_counter() - t:.2f} s")
    ids = {}
    for lane, svc in services.items():
        c0, t = clock.seconds, time.perf_counter()
        ticket = svc.search(queries)       # verifies: no tombstoned ids
        cold = time.perf_counter() - t
        t = time.perf_counter()
        svc.search(queries)
        warm = time.perf_counter() - t
        ids[lane] = ticket.ids
        rec = recall_at_k(ticket.ids, gt)
        say(f"[{phase}] {lane}: recall@{K}={rec:.4f} "
            f"mean_hops={float(np.mean(ticket.n_hops)):.2f} "
            f"first_call_s={cold:.3f} (compile {clock.seconds - c0:.2f} s) "
            f"repeat_call_s={warm:.3f}")
        checks.expect(rec >= RECALL_FLOOR[lane],
                      f"[{phase}] {lane} recall {rec:.4f} >= "
                      f"{RECALL_FLOOR[lane]}")
        if dead is not None:
            leaked = int(np.isin(ticket.ids, dead).sum())
            checks.expect(leaked == 0,
                          f"[{phase}] {lane} returns no deleted id "
                          f"({leaked} returned)")
    for lane, twin in TWIN.items():
        agree = float(np.mean(ids[lane] == ids[twin]))
        checks.expect(agree >= TWIN_AGREEMENT,
                      f"[{phase}] {lane} agrees with {twin} on "
                      f"{agree:.4f} of ids (>= {TWIN_AGREEMENT})")


def run_one_chip(args, checks, clock, data, queries, ds) -> None:
    import jax
    import numpy as np
    from repro.core.index import JasperIndex
    from repro.data.synthetic import make_anns_dataset
    from repro.serving.anns_service import AnnsService

    n = data.shape[0]
    cap = n + 2 * N_MUTATE
    index = JasperIndex(ds.dims, cap, quantization="rabitq", bits=4,
                        seed=args.seed)
    c0, t = clock.seconds, time.perf_counter()
    index.build(data, progress_fn=build_progress(t))
    jax.block_until_ready(index.core.graph.adjacency)
    build_s = time.perf_counter() - t
    say(f"[build] rows={n} capacity={cap} seconds={build_s:.2f} "
        f"rows_per_s={n / build_s:.1f} compile_s={clock.seconds - c0:.2f}")
    mem = index.memory_stats()
    say(f"[build] memory_stats: {json.dumps(mem, sort_keys=True)}")

    specs = lane_specs()
    services = {lane: AnnsService(index, spec=spec)
                for lane, spec in specs.items()}
    for lane in KERNEL_LANES:
        calls = kernel_calls(index, services[lane].searcher().resolved,
                             queries)
        say(f"[kernels] {lane}: tpu_custom_call x{calls} in the compiled "
            f"search program")
        checks.expect(calls > 0, f"{lane} runs a compiled Mosaic kernel")

    search_phase("fresh", services, index, queries, checks, clock)

    # ---- mutate: one tick inserts + deletes, a second consolidates
    rng = np.random.default_rng(args.seed + 17)
    dead = np.sort(rng.choice(n, N_MUTATE, replace=False)).astype(np.int64)
    fresh_rows = make_anns_dataset(ds, n=N_MUTATE, seed=args.seed + 1)
    mut = AnnsService(index, spec=specs["exact"], consolidate_threshold=0.0)
    c0, t = clock.seconds, time.perf_counter()
    tick = mut.step(inserts=fresh_rows, deletes=dead)
    jax.block_until_ready(index.core.graph.adjacency)
    say(f"[mutate] tick 1: inserted={tick.inserted_ids.size} "
        f"deleted={tick.n_deleted} seconds={time.perf_counter() - t:.2f} "
        f"compile_s={clock.seconds - c0:.2f} size={index.size}")
    checks.expect(tick.n_deleted == N_MUTATE and
                  tick.inserted_ids.size == N_MUTATE,
                  "tick 1 inserted and deleted 10,000 rows each")
    checks.expect(bool(np.all(index.tombstoned(dead))),
                  "every deleted id is tombstoned")
    search_phase("after_deletes", services, index, queries, checks, clock,
                 dead=dead)

    mut.consolidate_threshold = 1e-9      # the next tick repairs the graph
    c0, t = clock.seconds, time.perf_counter()
    tick = mut.step()
    say(f"[mutate] tick 2: consolidated={tick.consolidated is not None} "
        f"seconds={time.perf_counter() - t:.2f} "
        f"compile_s={clock.seconds - c0:.2f} n_deleted={index.n_deleted}")
    checks.expect(tick.consolidated is not None,
                  "tick 2 consolidated the graph")
    search_phase("after_consolidate", services, index, queries, checks,
                 clock, dead=dead)


def run_four_chips(args, checks, clock, data, queries, ds) -> None:
    import jax
    import numpy as np
    from repro.core.distributed import ShardedJasperIndex
    from repro.core.index import JasperIndex
    from repro.core.search_spec import SearchSpec
    from repro.launch.mesh import make_mesh
    from repro.serving.anns_service import AnnsService

    n = data.shape[0]
    shards = 4
    mesh = make_mesh((shards,), ("data",))
    per = -(-(n + 2 * N_MUTATE) // shards)
    per += (-per) % 8
    sharded = ShardedJasperIndex(mesh, ds.dims, capacity_per_shard=per,
                                 quantization="rabitq", bits=4,
                                 seed=args.seed)
    c0, t = clock.seconds, time.perf_counter()
    sharded.build(data)
    build_s = time.perf_counter() - t
    say(f"[build x4] rows={n} capacity_per_shard={per} "
        f"seconds={build_s:.2f} rows_per_s={n / build_s:.1f} "
        f"compile_s={clock.seconds - c0:.2f}")
    for name, arr in (("vectors", sharded.core.vectors),
                      ("adjacency", sharded.core.adjacency),
                      ("codes", sharded.core.codes.packed)):
        devs = {s.device for s in arr.addressable_shards}
        say(f"[build x4] {name}: {arr.shape} over "
            f"{sorted(d.id for d in devs)}")
        checks.expect(len(devs) == shards,
                      f"{name} sits on {shards} distinct devices")

    gt, kth4 = brute_force(sharded, queries)
    base = SearchSpec(k=K, beam_width=BEAM, quantized=True)
    lanes = {"quantized": base, "megakernel": base.with_(fusion="megakernel")}
    got = {}
    for lane, spec in lanes.items():
        c0, t = clock.seconds, time.perf_counter()
        ticket = AnnsService(sharded, spec=spec).search(queries)
        got[lane] = ticket.ids
        rec = recall_at_k(ticket.ids, gt)
        say(f"[search x4] {lane}: recall@{K}={rec:.4f} "
            f"first_call_s={time.perf_counter() - t:.3f} "
            f"(compile {clock.seconds - c0:.2f} s)")
        checks.expect(rec >= RECALL_FLOOR[lane],
                      f"x4 {lane} recall {rec:.4f} >= {RECALL_FLOOR[lane]}")
    agree = float(np.mean(got["megakernel"] == got["quantized"]))
    checks.expect(agree >= TWIN_AGREEMENT,
                  f"x4 megakernel agrees with quantized on {agree:.4f} of "
                  f"ids (>= {TWIN_AGREEMENT})")

    # the single-chip comparison at equal total budget: one index over
    # the same rows, searched at the summed beam of the four shards
    single = JasperIndex(ds.dims, n + 2 * N_MUTATE, quantization="rabitq",
                         bits=4, seed=args.seed)
    t = time.perf_counter()
    single.build(data)
    jax.block_until_ready(single.core.graph.adjacency)
    say(f"[build x1] rows={n} seconds={time.perf_counter() - t:.2f} on "
        f"{single.core.vectors.devices()}")
    gt1, kth1 = brute_force(single, queries)
    # the two references hold the same rows under different ids (global
    # shard-strided vs row numbers): their top-k distances must agree
    gap = float(np.max(np.abs(kth4 - kth1)))
    checks.expect(gap <= 1e-3 * float(np.max(kth1)),
                  f"sharded and single-chip brute-force distances agree "
                  f"(max gap {gap:.4g})")
    ticket = AnnsService(single, spec=base.with_(
        beam_width=shards * BEAM)).search(queries)
    rec1 = recall_at_k(ticket.ids, gt1)
    rec4 = recall_at_k(got["quantized"], gt)
    say(f"[compare] quantized recall@{K}: 4 shards x beam {BEAM} = "
        f"{rec4:.4f}; 1 chip x beam {shards * BEAM} = {rec1:.4f}")
    checks.expect(rec4 >= rec1 - 0.05,
                  f"sharded recall {rec4:.4f} within 0.05 of single-chip "
                  f"recall {rec1:.4f} at equal total beam")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rows", type=int, default=None,
                    help="rows built (default DEFAULT_ROWS, FOUR_CHIP_ROWS "
                         "with --chips 4; a cut below 1,000,000 is printed)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.rows is None:
        args.rows = FOUR_CHIP_ROWS if args.chips == 4 else DEFAULT_ROWS
    if not (SRC / "repro").is_dir():
        say(f"chip_smoke: no repro package under {SRC}")
        return 2

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        say(f"chip_smoke: JAX finds no TPU (platform {platform!r})")
        return 2
    if len(devices) < args.chips:
        say(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
            f"JAX finds {len(devices)}")
        return 2

    from repro.configs.base import ANNS_DATASETS
    from repro.data.synthetic import make_anns_dataset, make_queries
    from repro.launch.compile_cache import enable_compile_cache

    say(f"device: {devices[0].device_kind} x{len(devices)}")
    say(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    checks = Checks()
    ds = ANNS_DATASETS["bigann"]
    if args.rows < FULL_ROWS:
        say(f"rows cut: {args.rows} of {FULL_ROWS}")
    t = time.perf_counter()
    data = make_anns_dataset(ds, n=args.rows, seed=args.seed)
    queries = make_queries(ds, N_QUERIES, seed=args.seed + 1)
    say(f"dataset: bigann-shaped {data.shape} queries {queries.shape} "
        f"generated in {time.perf_counter() - t:.2f} s")

    t = time.perf_counter()
    if args.chips == 4:
        run_four_chips(args, checks, clock, data, queries, ds)
    else:
        run_one_chip(args, checks, clock, data, queries, ds)
    say(f"total: {time.perf_counter() - t:.2f} s; compile "
        f"{clock.seconds:.2f} s; persistent cache hits={clock.hits} "
        f"misses={clock.misses}")
    if checks.failed:
        say(f"FAILED {len(checks.failed)} check(s): {checks.failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in BENCHMARK.json names its configuration
(`bench/configs/<config>.json`) and traffic mix
(`bench/traffic/<traffic>.json`); the mix names the loop that drives it
(`bench/loops.py`). A run: checks that JAX sees as many TPU chips as the
cell asks for (exit 2 otherwise, no result), turns on the persistent
compile cache at a fixed path in the checkout, generates its data from
`--seed`, builds the index through `JasperIndex.build`, warms the cell's
shapes, measures for `--seconds`, then frees the program's state and
compares what the window served with the plain reference
(`bench/references/<metric>.py`) under the cell's limits
(`bench/limits/<cell>.json`).

`--trace 0` reports the cell's end-to-end metrics; `--trace 1` records the
window with the profiler and reports the cell's per-layer metrics, each
read by `bench/layer_metrics/<metric>.py`. The last stdout line is one
JSON object; the numbers compared and their limits are the last stderr
lines and the result's last key.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
# libtpu writes its logs under /tmp unless told otherwise: a run writes
# only inside its checkout and the directories it is given (TMPDIR)
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))

import registry  # noqa: E402


class NoChip(RuntimeError):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def require_chips(n: int) -> dict:
    """The device the cell runs on; NoChip unless JAX finds >= n TPUs."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX finds no accelerator: {e}") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def enable_compile_cache() -> str:
    """JAX's persistent compile cache, at JAX_COMPILATION_CACHE_DIR where
    that is set and otherwise at the fixed `.jax_cache/` of the checkout."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = str(ROOT / ".jax_cache")
        os.makedirs(cache, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


def peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def traced(fn, seconds: float):
    """Run the window under the profiler; return its reduced trace."""
    import jax
    import trace_reduce

    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                fn(seconds)
        finally:
            jax.profiler.stop_trace()
        t = time.perf_counter()
        path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
        summary = trace_reduce.summarize(trace_reduce.load(path))
        say(f"trace: {os.path.getsize(path)} bytes reduced in "
            f"{time.perf_counter() - t:.3f} s; device programs (s): "
            f"{json.dumps(summary.modules_s, sort_keys=True)}; device time "
            f"inside host annotations (s): "
            f"{json.dumps(summary.annotated_s, sort_keys=True)}")
        return summary
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_cell(name: str, cfg: dict, traffic: dict, *, seed: int,
             seconds: float, trace: bool, device: dict, bench: dict,
             limits: dict) -> dict:
    """One run of cell `name`; returns the result object."""
    import loops
    import jax
    from check import compare, report, verdict
    from compile_clock import CompileClock

    clock = CompileClock()
    loop = loops.LOOPS[traffic["loop"]](cfg, traffic, seed, seconds, clock)
    loop.setup()
    plans = loop.index.plans.stats
    c0, n0, r0 = clock.seconds, clock.compiles, plans.traces
    setup_s = time.perf_counter() - T0
    say(f"setup: {setup_s:.3f} s, of which compile {c0:.3f} s "
        f"({n0} compiles; persistent cache hits {clock.hits}, misses "
        f"{clock.misses})")
    if trace:
        summary = traced(loop.window, seconds)
    else:
        summary = None
        with jax.profiler.TraceAnnotation("bench.window"):
            loop.window(seconds)
    say(f"window: {clock.compiles - n0} compiles "
        f"({clock.seconds - c0:.3f} s), {plans.traces - r0} plan retraces")
    device = dict(device, memory_peak_bytes=peak_bytes())
    say(f"memory: peak {device['memory_peak_bytes']} bytes on device 0")

    answers = loop.answers()
    loop.release()
    gc.collect()
    t = time.perf_counter()
    ref = registry.reference(cfg["metric"])
    numbers = compare(ref, answers.rows, answers.row_ids, answers.queries,
                      answers.qidx, answers.ids, answers.dists,
                      cfg["search"]["k"])
    say(f"reference: {answers.ids.shape[0]} answers compared in "
        f"{time.perf_counter() - t:.3f} s; numbers "
        f"{json.dumps(numbers, sort_keys=True)}")
    correct, checks = verdict(numbers, limits)

    run = SimpleNamespace(cell=name, cfg=cfg, traffic=traffic,
                          counters=loop.counters, trace=summary,
                          device=device)
    metrics = {}
    if trace:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        for m in registry.metrics_of(name, bench, "per_layer"):
            value = registry.layer_metric(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        measured = dict(loop.e2e, setup_s=setup_s,
                        recall_at_10=1.0 - numbers["recall_miss"])
        for m in registry.metrics_of(name, bench, "end_to_end"):
            metrics[m["name"]] = {"value": measured[m["name"]],
                                  "unit": m["unit"]}
    result = {"correct": correct, "attempted": loop.attempted,
              "failed": loop.failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.gaps}
    result["checks"] = checks
    report(checks)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = registry.benchmark()
        cell = registry.cell(args.workload, bench)
        cfg = registry.config(cell["config"])
        traffic = registry.traffic(cell["traffic"])
        from check import limits
        lim = limits(cell["name"])
    except (KeyError, OSError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no system under test at {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        device = require_chips(cell["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    say(f"device: {device}; compile cache: {enable_compile_cache()}")
    result = run_cell(cell["name"], cfg, traffic, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      device=device, bench=bench, limits=lim)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

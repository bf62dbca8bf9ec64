"""Program spans: one `obs.span` for the profiler trace and a JSON sink.

The paper's serving claims — latency hiding in the fused search kernel,
p99 flat through a consolidate + reshard cycle — are timing claims, and
this module is the ONE place the repo names host-side time. Each
`obs.span(name, **args)` goes to up to two sinks, with one start:

* the profiler: under any `jax.profiler` session the span opens a
  `jax.profiler.TraceAnnotation(name, **args)`, so it lands in the
  session's `.xplane.pb` on the clock the device ops are converted to.
  Perfetto or TensorBoard then shows the program's host spans above the
  device ops they launched (docs/observability.md);
* a `SpanTracer`, when one is installed: an in-memory recorder whose
  export is the Chrome trace-event format (`{"traceEvents": [...]}` of
  "ph": "X" complete events), for CPU-only use. Its timestamps are
  wall-clock (`time.time_ns()`), the clock `TraceMe` stamps, so a span
  starts at the same instant in both sinks.

Usage:

    from repro import obs
    tracer = obs.SpanTracer()
    with obs.use_tracer(tracer):
        with obs.span("consolidate", n_deleted=37):
            ...
    tracer.export("trace.json")

`obs.span(...)` is safe to leave in hot paths permanently: with no tracer
installed and no profiler session it returns a shared no-op context
manager after one global read and one `TraceAnnotation.is_enabled()`
check — no allocation, no clock read, no lock.

Span taxonomy (the names the serving/search stack emits — keep stable,
dashboards key on them):

    service.step            one update/serve tick (parent of the phases)
    service.delete / service.insert / service.search
    service.consolidate / service.rebalance
    service.search_many     a pipelined run of search batches
    service.tenant_search   one batch scoped to a tenant's label
    service.serve           an open-loop trace replay (scheduler front end)
    scheduler.flush         one coalesced batch dispatched
    scheduler.harvest       one coalesced batch landed on the host
    searcher.submit         prep + plan lookup + enqueue of one batch
    searcher.drain          landing of the oldest in-flight batches
    searcher.wait           blocked until one batch is ready on the device
    searcher.land           device-to-host copies of one ready batch
    index.build             bulk construction (either driver)
    reshard.cores           shard-count-changing restore

`searcher.wait` and `searcher.land` are emitted wherever a batch lands
(`core.search_spec.land`): inside `searcher.drain`, `service.search`
and `scheduler.harvest`.
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Iterator

from jax.profiler import TraceAnnotation

__all__ = ["SpanTracer", "span", "use_tracer", "set_tracer", "get_tracer"]


class SpanTracer:
    """Thread-safe, nestable span recorder.

    Spans are recorded as Chrome trace "complete" events (ph "X"): wall
    timestamp (`time.time_ns()`, the clock `TraceMe` stamps) + duration
    (monotonic `perf_counter_ns`) in microseconds, pid = this process,
    tid = the recording thread — nesting falls out of the format
    (Perfetto stacks events on the same tid by time containment), so the
    tracer itself keeps no explicit stack.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._events: list[dict] = []

    # ------------------------------------------------------------- recording
    @contextmanager
    def span(self, name: str, **args: Any) -> Iterator[None]:
        """Record one span around the body (and, under a profiler
        session, the same span in the profiler trace). Nestable and
        thread-safe; `args` land in the trace event's args dict
        (JSON-coerced)."""
        # the start on TraceMe's clock; the duration on the monotonic
        # perf counter, immune to wall-clock steps
        start = time.time_ns()
        t0 = time.perf_counter_ns()
        try:
            with _annotation(name, args):
                yield
        finally:
            dur = time.perf_counter_ns() - t0
            evt = {"name": name, "ph": "X", "ts": start / 1e3,
                   "dur": dur / 1e3, "pid": os.getpid(),
                   "tid": threading.get_ident()}
            if args:
                evt["args"] = {k: _jsonable(v) for k, v in args.items()}
            with self._lock:
                self._events.append(evt)

    # --------------------------------------------------------------- exports
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def to_chrome_trace(self) -> dict:
        """The Chrome trace-event JSON object (Perfetto-loadable)."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def export(self, path: str) -> None:
        """Write the Chrome trace JSON to `path`."""
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)

    def summary(self) -> dict[str, dict]:
        """Per-span-name aggregates: {name: {count, total_us, mean_us,
        max_us}} — the no-browser view scripts/obs_report.py prints."""
        out: dict[str, dict] = {}
        for e in self.events():
            s = out.setdefault(e["name"], {"count": 0, "total_us": 0.0,
                                           "max_us": 0.0})
            s["count"] += 1
            s["total_us"] += e["dur"]
            s["max_us"] = max(s["max_us"], e["dur"])
        for s in out.values():
            s["mean_us"] = s["total_us"] / s["count"]
        return out


def _jsonable(v: Any):
    """Coerce span args to plain JSON scalars (numpy scalars included)."""
    if isinstance(v, (str, bool, int, float)) or v is None:
        return v
    item = getattr(v, "item", None)
    if callable(item):
        try:
            return item()
        except (TypeError, ValueError):
            pass
    return str(v)


# ---------------------------------------------------------------------------
# Module-level active tracer — the `obs.span(...)` hot-path surface
# ---------------------------------------------------------------------------

_active: SpanTracer | None = None


class _NoopSpan:
    """Shared reusable no-op context manager: `obs.span()` with tracing
    disabled costs one global read and one `is_enabled()` check and
    returns this singleton — no allocation, no clock, no lock."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()

def _annotation(name: str, args: dict):
    """The span's profiler-trace half: a `TraceAnnotation` while a
    profiler session records host events, else the no-op."""
    return TraceAnnotation(name, **args) if TraceAnnotation.is_enabled() \
        else _NOOP


def set_tracer(tracer: SpanTracer | None) -> SpanTracer | None:
    """Install (or clear, with None) the process-wide active tracer.
    Returns the previous one."""
    global _active
    prev, _active = _active, tracer
    return prev


def get_tracer() -> SpanTracer | None:
    return _active


@contextmanager
def use_tracer(tracer: SpanTracer) -> Iterator[SpanTracer]:
    """Scoped activation: install `tracer` for the block, restore after."""
    prev = set_tracer(tracer)
    try:
        yield tracer
    finally:
        set_tracer(prev)


def span(name: str, **args: Any):
    """One program span: into the active tracer (if any) and, under a
    profiler session, into the profiler trace; a shared no-op when
    neither records."""
    t = _active
    if t is None:
        return _annotation(name, args)
    return t.span(name, **args)

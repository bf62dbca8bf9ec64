"""Reduction of a profiler trace to the benchmark's device numbers.

A run with `--trace 1` records its measured window with `jax.profiler`
and this module reduces the `.xplane.pb` that comes out:

* busy time: the union of the intervals in which an operation ran on a
  device (the "XLA Ops" line of each `/device:TPU:<n>` plane), averaged
  over the devices that ran anything;
* device time per program: the durations of the "XLA Modules" events,
  summed by program name (`short`: `jit_core_insert_at(123)` counts as
  `jit_core_insert_at`);
* device time inside host annotations: operation time whose start lies
  inside a host `TraceAnnotation` of a given name (the harness wraps each
  call into the system in one);
* the device operations that took most time, and the longest idle gaps,
  each gap named by the host annotation that covers at least half of it.

Everything after `load` works on plain tuples so that it can be checked
on a constructed trace.
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BENCH_PREFIX = "bench."
_ID_SUFFIX = re.compile(r"\(\d+\)$")
# control flow whose device interval holds the ops it runs: left out of
# the list of ops that took most time, so that the ops inside show
_CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def short(name: str) -> str:
    """An op's HLO instruction name (`%fusion.12 = f32[...] ...` ->
    `fusion.12`), a program's name without its id (`jit_run(123)` ->
    `jit_run`)."""
    return _ID_SUFFIX.sub("", name.split(" = ", 1)[0].lstrip("%"))


@dataclass
class Trace:
    """Events of one trace, in nanoseconds on the profiler's clock.

    devices: {plane name: {line name: [(event name, start, duration)]}}
    host:    [(annotation name, start, duration)] of host events whose
             name starts with `bench.`
    """

    devices: dict = field(default_factory=dict)
    host: list = field(default_factory=list)


def load(path: str) -> Trace:
    """Read an `.xplane.pb` with JAX's own reader."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = Trace()
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            out.devices[plane.name] = {
                line.name: [(short(ev.name), float(ev.start_ns),
                             float(ev.duration_ns)) for ev in line.events]
                for line in plane.lines
                if line.name in (OPS_LINE, MODULES_LINE)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(BENCH_PREFIX):
                        out.host.append((ev.name, float(ev.start_ns),
                                         float(ev.duration_ns)))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def window_of(trace: Trace, name: str = "bench.window"
              ) -> tuple[float, float]:
    """(start, end) of the host annotation that spans the measured window."""
    spans = [(s, s + d) for n, s, d in trace.host if n == name]
    if not spans:
        raise ValueError(f"trace holds no {name!r} annotation")
    return spans[0]


def ops(trace: Trace, plane: str) -> list:
    return trace.devices[plane].get(OPS_LINE, [])


def busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Busy time inside [lo, hi), averaged over devices that ran an op."""
    per = [sum(e - s for s, e in union(clip(
        [(s, s + d) for _, s, d in ops(trace, p)], lo, hi)))
        for p in trace.devices if ops(trace, p)]
    return sum(per) / len(per) if per else 0.0


def module_ns(trace: Trace, lo: float, hi: float) -> dict[str, float]:
    """Device time of each program (jitted module) inside [lo, hi),
    summed over devices."""
    out: dict[str, float] = {}
    for p, lines in trace.devices.items():
        for name, s, d in lines.get(MODULES_LINE, []):
            if lo <= s < hi:
                out[name] = out.get(name, 0.0) + d
    return out


def annotated_ns(trace: Trace, name: str) -> float:
    """Device op time that starts inside a host annotation `name`,
    summed over devices (overlapping ops are merged first, so nested ops
    count once)."""
    spans = union((s, s + d) for n, s, d in trace.host if n == name)
    starts = [a for a, _ in spans]
    total = 0.0
    for p in trace.devices:
        for s, e in union((s, s + d) for _, s, d in ops(trace, p)):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < spans[i][1]:
                total += e - s
    return total


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """[[op name, seconds]] of the n device ops that took most time,
    control-flow containers left out."""
    acc: dict[str, float] = {}
    for p in trace.devices:
        for name, s, d in ops(trace, p):
            if lo <= s < hi and not _CONTAINERS.match(name):
                acc[name] = acc.get(name, 0.0) + d
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """[[host annotation, seconds]] of the n longest gaps in which no
    device ran an op, each named by the `bench.*` host annotation that
    covers at least half of it (`host` where none does)."""
    busy = union(clip([(s, s + d) for p in trace.devices
                       for _, s, d in ops(trace, p)], lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        best, cover = "host", 0.5 * (e - s) - 1e-9
        for name, hs, hd in trace.host:
            if name == "bench.window":
                continue
            c = min(e, hs + hd) - max(s, hs)
            if c > cover:
                best, cover = name, c
        out.append([best, (e - s) * 1e-9])
    return out


@dataclass
class Summary:
    """What the per-layer readers and the result line take from a trace."""

    window_s: float
    busy_s: float
    modules_s: dict
    annotated_s: dict
    device_ops: list
    gaps: list

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def summarize(trace: Trace) -> Summary:
    lo, hi = window_of(trace)
    names = sorted({n for n, _, _ in trace.host if n != "bench.window"})
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_ns(trace, lo, hi) * 1e-9,
        modules_s={k: v * 1e-9 for k, v in module_ns(trace, lo, hi).items()},
        annotated_s={n: annotated_ns(trace, n) * 1e-9 for n in names},
        device_ops=top_ops(trace, lo, hi),
        gaps=idle_gaps(trace, lo, hi))


def idle_percent(run) -> float | None:
    """Per-layer reader: the share of the traced window in which no
    operation ran on the device, in %."""
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share


def annotated_ms(run, name: str, per: float) -> float | None:
    """Per-layer helper: device ms inside host annotation `name`, divided
    by `per`; nothing where the trace holds none."""
    if run.trace is None or per <= 0:
        return None
    s = run.trace.annotated_s.get(name, 0.0)
    return s * 1e3 / per if s > 0 else None

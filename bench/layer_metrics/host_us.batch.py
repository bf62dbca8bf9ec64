"""Session layer (`core/search_spec.py` `Searcher`, `land`), batch cells:
host time per batch, in us: the mean host time of a dispatch that did
not trace (prep, plan lookup and enqueue: `session.dispatch_s` /
`session.dispatches`) plus the mean time to land a ready batch on the
host (`session.land_s` / `session.batches`; the wait for the device left
out).

Per-layer metrics are read only in a `--trace 1` run, so this is host
time under the profiler, where each program span also records a
`TraceAnnotation`: it reads above the same counters of an untraced
window, and spreads more from run to run (PERF.md gives both). The
counters are process-wide totals, so they also hold the set-up's
warm-up batches (the seed's first two batches, twice), which the means
dilute but do not remove."""

import program_counters


def read(run):
    c = program_counters.counters()
    dispatch = program_counters.ratio(c, "dispatch_s", "dispatches")
    land = program_counters.ratio(c, "land_s", "batches")
    if dispatch is None or land is None:
        return None
    return (dispatch + land) * 1e6

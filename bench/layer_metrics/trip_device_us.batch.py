"""Kernels layer, batch cells: device time of one trip of the hop loop
over a whole batch, in us: `search_program_ms.batch` x 1e3 over
`loop_trips.batch`. Nothing where either is missing."""

import registry


def read(run):
    ms = registry.layer_metric("search_program_ms.batch").read(run)
    trips = registry.layer_metric("loop_trips.batch").read(run)
    return ms * 1e3 / trips if ms and trips else None

"""Core-ops layer (`core/index_core.py` `core_search`), batch cells: device
time of the search programs per served batch, in ms. The device
operations that start inside the harness's `bench.search` annotation,
which wraps each `AnnsService.search` call of the window."""

from trace_reduce import annotated_ms


def read(run):
    return annotated_ms(run, "bench.search", run.counters["batches"])

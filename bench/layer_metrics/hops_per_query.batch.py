"""Traversal layer (`core/beam_search.py`), batch cells: the mean of the
program's own per-query hop count (`SearchResult.n_hops`) over every
query the window served."""


def read(run):
    return run.counters["hops_mean"]

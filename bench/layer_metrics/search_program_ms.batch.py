"""Core-ops layer (`core/index_core.py` `core_search`), batch cells: device
time of the search program per served batch, in ms. The program's own
name, `jit_jasper_search` on the trace's "XLA Modules" line, clipped to
the traced window, over the window's batches. Nothing where the trace
holds no program of that name."""

PROGRAM = "jit_jasper_search"


def read(run):
    t, batches = run.trace, run.counters.get("batches", 0)
    s = t.modules_s.get(PROGRAM, 0.0) if t else 0.0
    return s * 1e3 / batches if s > 0 and batches > 0 else None

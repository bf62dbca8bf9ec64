"""Kernels layer, batch cells: the least time the chip could take for the
window's searches (bench/work.py: the bytes the algorithm needs at peak
HBM bandwidth, or its operations at peak bf16 rate, whichever is
longer), over the device time of the search programs, in %."""

import work


def read(run):
    t = run.trace
    dev = t.annotated_s.get("bench.search", 0.0) if t else 0.0
    if dev <= 0:
        return None
    cfg, ix = run.cfg, run.cfg["index"]
    hops, n = run.counters["hops_mean"], run.counters["queries"]
    beam = cfg["search"]["beam_width"]
    nbytes = n * work.search_bytes(hops, dims=cfg["dims"], bits=ix["bits"],
                                   degree=ix["degree_bound"], beam=beam)
    flops = n * work.search_flops(hops, dims=cfg["dims"],
                                  degree=ix["degree_bound"], beam=beam)
    least, _ = work.least_time(nbytes, flops, work.peaks(run.device["kind"]))
    return 100.0 * least / dev

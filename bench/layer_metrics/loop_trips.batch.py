"""Traversal layer (`core/beam_search.py`), batch cells: trips of the hop
loop per landed batch, from the program's counters (`session.trips` /
`session.batches`). A batch's trips are its largest per-query hop count:
on the unfused loop at expand 1, which the batch cells run, exactly the
`while_loop`'s trip count, which the batch's slowest query sets. The
counters are process-wide totals, so they also hold the set-up's
warm-up batches (the seed's first two batches, twice), which the mean
dilutes but does not remove."""

import program_counters


def read(run):
    return program_counters.ratio(program_counters.counters(),
                                  "trips", "batches")

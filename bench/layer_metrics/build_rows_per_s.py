"""Construction layer, every cell: rows built per second of host time in
the set-up build (`JasperIndex.build`, ended by `block_until_ready`),
compiles included."""


def read(run):
    return run.counters["build_rows"] / run.counters["build_s"]

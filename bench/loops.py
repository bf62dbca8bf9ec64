"""The one general traffic generator: it reads a traffic mix (a JSON file
under `traffic/`) and drives the system under test through its public
entry points. `loop` in the mix picks how the window drives the
service; every size and `SearchSpec` override (`spec`) is data.

* `closed_batch`: one client sends `batch` queries at a time, the pool's
  batches in the seed's order, through `AnnsService.search_many`, with
  `inflight` searches dispatched on the device ahead of the one it waits
  for, so that a stall of the host does not starve the chip.

Each loop builds its index in `setup` (through `JasperIndex.build`),
warms every shape its window uses, runs the window, and hands back the
answers the window produced.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

import datagen

def say(msg: str) -> None:
    print(msg, flush=True)


@dataclass
class Answers:
    """Served answers, for the comparison with the reference: answer i is
    `ids[i]`/`dists[i]` for query `queries[qidx[i]]`, over live rows
    `rows` whose system ids are `row_ids`."""

    rows: np.ndarray
    row_ids: np.ndarray
    queries: np.ndarray
    qidx: np.ndarray
    ids: np.ndarray
    dists: np.ndarray


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Loop:
    """Shared set-up: the configuration's data, the index built through
    `JasperIndex.build`, and the service with the cell's `SearchSpec`."""

    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 seconds: float, clock):
        self.cfg = cfg
        self.seconds = seconds
        self.traffic = traffic
        self.clock = clock
        self.counters: dict = {}
        self.attempted = 0
        self.failed = 0
        t = time.perf_counter()
        self.seed = seed
        # the rows are built in the configuration's own order, so every
        # seed builds the same graph
        self.base = datagen.rows(cfg, cfg["rows"], datagen.BASE)
        self.queries = datagen.rows(cfg, cfg["query_pool"], datagen.QUERIES)
        say(f"data: base {self.base.shape} queries {self.queries.shape} "
            f"in {time.perf_counter() - t:.3f} s")

    def spec(self):
        from repro.core.search_spec import SearchSpec
        fields = dict(self.cfg["search"])
        fields.update(self.traffic.get("spec", {}))
        return SearchSpec(**{k: tuple(v) if isinstance(v, list) else v
                             for k, v in fields.items()})

    def build(self):
        import jax
        from repro.core.construction import ConstructionParams
        from repro.core.index import JasperIndex
        from repro.serving.anns_service import AnnsService

        ix = self.cfg["index"]
        params = ConstructionParams(degree_bound=ix["degree_bound"],
                                    alpha=ix["alpha"],
                                    beam_width=ix["build_beam_width"])
        self.index = JasperIndex(
            self.cfg["dims"], self.cfg["rows"], metric=self.cfg["metric"],
            quantization=ix["quantization"], bits=ix["bits"],
            construction=params, seed=self.cfg["data_seed"])
        c0 = self.clock.seconds
        t = time.perf_counter()
        self.index.build(self.base)
        jax.block_until_ready(self.index.core.adjacency)
        build_s = time.perf_counter() - t
        compile_s = self.clock.seconds - c0
        n = self.base.shape[0]
        self.counters.update(build_rows=n, build_s=build_s)
        say(f"build: rows={n} seconds={build_s:.3f} "
            f"rows_per_s={n / build_s:.1f} compile_s={compile_s:.3f} "
            f"compute_s={build_s - compile_s:.3f} "
            f"({'cold' if compile_s > 0.5 * build_s else 'warm'} cache)")
        self.svc = AnnsService(self.index, spec=self.spec())

    def release(self) -> None:
        """Drop the program's state, so the reference runs on a free chip."""
        self.svc = self.index = None

    def answers(self) -> Answers:
        qidx, ids, dists = self.out
        return Answers(self.base, np.arange(self.base.shape[0]),
                       self.queries, qidx, ids, dists)


class ClosedBatch(Loop):
    def setup(self) -> None:
        b = self.traffic["batch"]
        n = self.queries.shape[0]
        if n % b:
            raise ValueError(f"query pool {n} is not a multiple of the "
                             f"batch {b}")
        self.build()
        # the service's pipelined batch path keeps this many searches
        # dispatched on the device ahead of the one it waits for
        self.svc.MAX_INFLIGHT = self.traffic["inflight"]
        self.batch_idx = datagen.batches(self.seed, n, b, datagen.QUERIES)
        self.batches = [self.queries[ix] for ix in self.batch_idx]
        for _ in range(2):
            self.svc.search_many(self.batches[:2])

    def window(self, seconds: float) -> None:
        """Batches go out in pool order until `seconds` have passed; then
        nothing more is sent, every batch sent is waited for, and the
        clock is read after that wait: all of them count, over all of
        that time."""
        b, nb = self.traffic["batch"], len(self.batches)
        qidx = []

        def batches():
            while time.perf_counter() - t0 < seconds:
                j = len(qidx) % nb
                qidx.append(self.batch_idx[j])
                yield self.batches[j]

        t0 = time.perf_counter()
        with annotate("bench.search"):
            tickets = self.svc.search_many(batches())
        elapsed = time.perf_counter() - t0
        n = len(tickets) * b
        self.attempted = n
        self.out = (np.concatenate(qidx),
                    np.concatenate([t.ids for t in tickets]),
                    np.concatenate([t.dists for t in tickets]))
        hops = np.concatenate([t.n_hops for t in tickets])
        self.counters.update(batches=len(tickets), queries=n,
                             hops_mean=float(np.mean(hops)))
        self.e2e = {"qps": n / elapsed}


LOOPS = {"closed_batch": ClosedBatch}

"""Inputs of the benchmark: base rows and query pools.

A copy of the repository's synthetic ANNS generator (`_manifold`,
`_clustered`, `make_anns_dataset`, `make_queries`), kept here so that no
change to the program moves the yardstick. Rows are a distribution-matched stand-in for the paper's
Table 3 datasets: Gaussian clusters on a 64-dimensional manifold inside
the ambient width, on the uint8 grid where the dataset is uint8 (SIFT).

The sets are fixed per configuration (its `data_seed`). The base rows
are built in the order they are generated, so every run builds the same
graph, and the query pool is cut into the same batches; a run's `--seed`
only orders the batches and the queries within each (`batches`). So
every seed does the same work in another order, and runs of different
seeds can be compared.
"""

from __future__ import annotations

import numpy as np

# streams of one configuration: base rows, query pool
BASE, QUERIES = 0, 1


def name_seed(name: str) -> int:
    return int(np.frombuffer(name.encode().ljust(8, b"x")[:8],
                             dtype=np.uint32)[0])


def manifold(name: str, dims: int, n_clusters: int = 64,
             intrinsic: int = 64):
    """Cluster centres in a low-intrinsic-dimension subspace, fixed per
    dataset name (isolated islands in a high ambient width are not
    navigable by a graph walk; real embeddings have low intrinsic
    dimension)."""
    rng = np.random.default_rng(name_seed(name))
    r = min(intrinsic, dims)
    basis = rng.normal(size=(r, dims)).astype(np.float32) / np.sqrt(r)
    centers_z = rng.normal(size=(n_clusters, r)).astype(np.float32)
    return basis, centers_z


def clustered(ds: dict, rng: np.random.Generator, n: int,
              spread: float = 0.35, ambient_noise: float = 0.02
              ) -> np.ndarray:
    basis, centers_z = manifold(ds["dataset"], ds["dims"])
    r = basis.shape[0]
    assign = rng.integers(0, centers_z.shape[0], n)
    z = centers_z[assign] + spread * rng.normal(size=(n, r)).astype(np.float32)
    x = z @ basis + ambient_noise * rng.normal(
        size=(n, ds["dims"])).astype(np.float32)
    if ds["dtype"] == "uint8":                    # BigANN/SIFT grid
        x = np.clip((x * 64 + 128), 0, 255).astype(np.uint8)
    return x.astype(np.float32)


def rows(ds: dict, n: int, stream: int = BASE) -> np.ndarray:
    """The configuration's first n rows of one stream."""
    ss = np.random.SeedSequence([ds["data_seed"], name_seed(ds["dataset"]),
                                 stream])
    return clustered(ds, np.random.default_rng(ss), n)


def batches(seed: int, n: int, b: int, stream: int) -> np.ndarray:
    """A run seed's order of n items of one stream in batches of b, as an
    (n // b, b) array of item indices. The batches are the
    configuration's (items i*b to i*b + b - 1): a batch's search lasts as
    long as its slowest query, so another cut would be other work. The
    seed orders the batches and the items within each."""
    ss = np.random.SeedSequence([int(seed) % (1 << 64), stream])
    rng = np.random.default_rng(ss)
    idx = np.arange(n).reshape(n // b, b)[rng.permutation(n // b)]
    return rng.permuted(idx, axis=1)

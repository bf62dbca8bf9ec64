"""AOT compiles of the main-path Pallas kernels for a TPU v5e.

Each test lowers one kernel at the widths a deployment runs (D in
{96, 128}, R = 64, L = 64, Q = 128, 4-bit codes, 10^6 rows) against a
described v5e:2x2 topology and compiles it with the TPU compiler: nothing
runs, so these tests pass on a CPU-only machine. What Mosaic refuses
(slices off the lane tiling, primitives it cannot lower, loads from HBM
refs) fails here, not on the chip. Each compiled program must hold the
kernel as a `tpu_custom_call`.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file. The persistent compile cache is off around these tests (a
compile for a described chip cannot be read back without one).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.rabitq import RaBitQCodes, RaBitQQuery
from repro.core.vamana import VamanaGraph
from repro.kernels.distance.ops import gather_l2_chunked, pairwise_l2
from repro.kernels.rabitq_dot.ops import rabitq_distance, rabitq_search_step
from repro.kernels.search_step.ops import fused_beam_search
from repro.kernels.topk.ops import topk

CAP, R, L, Q, BITS = 1_000_000, 64, 64, 128, 4
DIMS = (96, 128)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def compile_text(fn, *args) -> str:
    """Compile `fn` for the described chip; the optimized HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def packed_width(d: int) -> int:
    return (d * BITS + 7) // 8


@pytest.mark.parametrize("d", DIMS)
@pytest.mark.parametrize("quantized", [False, True], ids=["exact", "rabitq"])
@pytest.mark.parametrize("mode", ["hop", "megakernel"])
@pytest.mark.parametrize("masked", [False, True],
                         ids=["plain", "tomb-filter-telemetry"])
def test_fused_search_compiles(spec, d, quantized, mode, masked):
    """Both fused kernels, exact and RaBitQ, bare and with the exclude-mode
    tombstone gather, the label filter and the telemetry counters."""
    p = packed_width(d)

    def run(adj, nv, med, vec, sqn, qs, packed, dadd, drs, qrot, qadd, qsum,
            tomb, lab, fb):
        kw = dict(mode=mode, beam_width=L, max_iters=96, telemetry=masked,
                  interpret=False)
        if masked:
            kw.update(tombstone_bits=tomb, traverse_deleted=False,
                      labels=lab, filter_bytes=fb, filter_exclude=True)
        g = VamanaGraph(adj, nv, med)
        if quantized:
            r = fused_beam_search(
                g, codes=RaBitQCodes(packed, dadd, drs, BITS, d),
                rq_query=RaBitQQuery(qrot, qadd, qsum), **kw)
        else:
            r = fused_beam_search(g, queries=qs, vectors=vec,
                                  vec_sqnorm=sqn, **kw)
        return r.frontier_ids, r.frontier_dists, r.n_hops

    args = (spec((CAP, R), jnp.int32), spec((), jnp.int32),
            spec((), jnp.int32), spec((CAP, d), jnp.float32),
            spec((CAP,), jnp.float32), spec((Q, d), jnp.float32),
            spec((CAP, p), jnp.uint8), spec((CAP,), jnp.float32),
            spec((CAP,), jnp.float32), spec((Q, d), jnp.float32),
            spec((Q,), jnp.float32), spec((Q,), jnp.float32),
            spec((CAP // 8,), jnp.uint8), spec((CAP, 4), jnp.uint8),
            spec((4,), jnp.int32))
    assert "tpu_custom_call" in compile_text(run, *args)


@pytest.mark.parametrize("d", DIMS)
def test_rabitq_search_step_compiles(spec, d):
    """The `use_kernels=True` quantized scorer over one hop's candidates."""
    def run(packed, add, rs, ids, nv, qrot, qadd, qsum, live):
        return rabitq_search_step(packed, add, rs, ids, nv, qrot, qadd, qsum,
                                  bits=BITS, live=live, interpret=False)

    args = (spec((Q, R, packed_width(d)), jnp.uint8),
            spec((Q, R), jnp.float32), spec((Q, R), jnp.float32),
            spec((Q, R), jnp.int32), spec((), jnp.int32),
            spec((Q, d), jnp.float32), spec((Q,), jnp.float32),
            spec((Q,), jnp.float32), spec((Q, R), jnp.int32))
    assert "tpu_custom_call" in compile_text(run, *args)


@pytest.mark.parametrize("d", DIMS)
def test_rabitq_distance_compiles(spec, d):
    """All-rows estimated distances over the packed code plane."""
    def run(packed, add, rs, qrot, qadd, qsum):
        return rabitq_distance(packed, add, rs, qrot, qadd, qsum, bits=BITS,
                               interpret=False)

    args = (spec((CAP, packed_width(d)), jnp.uint8),
            spec((CAP,), jnp.float32), spec((CAP,), jnp.float32),
            spec((Q, d), jnp.float32), spec((Q,), jnp.float32),
            spec((Q,), jnp.float32))
    assert "tpu_custom_call" in compile_text(run, *args)


@pytest.mark.parametrize("d", DIMS)
def test_pairwise_l2_compiles(spec, d):
    """The tiled MXU brute-force kernel, queries against every row."""
    def run(q, x):
        return pairwise_l2(q, x, interpret=False)

    args = (spec((Q, d), jnp.float32), spec((CAP, d), jnp.float32))
    assert "tpu_custom_call" in compile_text(run, *args)


@pytest.mark.parametrize("d", DIMS)
def test_gather_l2_chunked_compiles(spec, d):
    """The exact kernel scorer: bulk-gathered candidate rows."""
    def run(q, db, db_sq, ids):
        return gather_l2_chunked(q, db, db_sq, ids, interpret=False)

    args = (spec((Q, d), jnp.float32), spec((CAP, d), jnp.float32),
            spec((CAP,), jnp.float32), spec((Q, R), jnp.int32))
    assert "tpu_custom_call" in compile_text(run, *args)


@pytest.mark.parametrize("k", [10, L])
def test_topk_compiles(spec, k):
    """The `merge="kernel"` frontier merge: top-L of frontier ++ candidates
    (k = L), and the final top-k of a search (k = 10)."""
    def run(dists, ids):
        return topk(dists, ids, k, interpret=False)

    args = (spec((Q, L + R), jnp.float32), spec((Q, L + R), jnp.int32))
    assert "tpu_custom_call" in compile_text(run, *args)

"""AOT compiles of the main-path Pallas kernels for a TPU v5e.

Each test lowers one kernel at the widths a deployment runs (D in
{96, 128}, R = 64, L = 64, Q = 128, 4-bit codes, 10^6 rows) against a
described v5e:2x2 topology and compiles it with the TPU compiler: nothing
runs, so these tests pass on a CPU-only machine. What Mosaic refuses
(slices off the lane tiling, primitives it cannot lower, loads from HBM
refs) fails here, not on the chip. Each compiled program must hold the
kernel as a `tpu_custom_call`. The unfused quantized search compiles here
too: its frontier merge (`hop.merge`) must hold no gather, and its scoring
(`hop.score`) one, of whole code rows. So do the 960-d build and encode
programs, whose every remaining matrix product must run at `highest`
precision.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file. The persistent compile cache is off around these tests (a
compile for a described chip cannot be read back without one).
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import rabitq
from repro.core.beam_search import beam_search_quantized
from repro.core.construction import (ConstructionParams, batch_insert_at,
                                     bootstrap_graph)
from repro.core.medoid import compute_medoid
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

    def run(adj, nv, med, vec, sqn, qs, rows, qrot, qadd, qsum, tomb, lab,
            fb):
        kw = dict(mode=mode, beam_width=L, max_iters=96, telemetry=masked,
                  interpret=False)
        if masked:
            kw.update(tombstone_bits=tomb, traverse_deleted=False,
                      labels=lab, filter_bytes=fb, filter_exclude=True)
        g = VamanaGraph(adj, nv, med)
        if quantized:
            r = fused_beam_search(
                g, codes=RaBitQCodes(rows, BITS, d),
                rq_query=RaBitQQuery(qrot, qadd, qsum), **kw)
        else:
            r = fused_beam_search(g, queries=qs, vectors=vec,
                                  vec_sqnorm=sqn, **kw)
        return r.frontier_ids, r.frontier_dists, r.n_hops

    args = (spec((CAP, R), jnp.int32), spec((), jnp.int32),
            spec((), jnp.int32), spec((CAP, d), jnp.float32),
            spec((CAP,), jnp.float32), spec((Q, d), jnp.float32),
            spec((CAP, p + 8), jnp.uint8), spec((Q, d), jnp.float32),
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


@pytest.fixture(scope="module")
def unfused_search_hlo(spec):
    """The default lane (jnp quantized, unfused) compiled over a table of
    code rows uint8[CAP, P + 8]: d -> optimized HLO, one compile per width."""
    compiled = {}

    def hlo(d: int) -> str:
        if d not in compiled:
            def run(adj, nv, med, rows, qrot, qadd, qsum):
                r = beam_search_quantized(
                    VamanaGraph(adj, nv, med), RaBitQCodes(rows, BITS, d),
                    RaBitQQuery(qrot, qadd, qsum), beam_width=L,
                    max_iters=96)
                return r.frontier_ids, r.frontier_dists, r.n_hops

            args = (spec((CAP, R), jnp.int32), spec((), jnp.int32),
                    spec((), jnp.int32),
                    spec((CAP, packed_width(d) + 8), jnp.uint8),
                    spec((Q, d), jnp.float32), spec((Q,), jnp.float32),
                    spec((Q,), jnp.float32))
            compiled[d] = compile_text(run, *args)
        return compiled[d]
    return hlo


def gathers_with_operand(hlo: str) -> list[tuple[str, str]]:
    """(gather line, type of its first operand) for every gather."""
    types = dict(re.findall(r"(%[\w.-]+) = (\S+?)\{", hlo))
    out = []
    for ln in hlo.splitlines():
        m = re.search(r"=\s.*\bgather\((%[\w.-]+),", ln)
        if m:
            out.append((ln, types.get(m.group(1), "")))
    return out


@pytest.mark.parametrize("d", DIMS)
def test_unfused_search_merge_holds_no_gather(unfused_search_hlo, d):
    """The default lane (jnp quantized, unfused): the hop's top-L merge
    carries ids and visited bits through its sort, so no gather in the
    compiled program carries `hop.merge` in its op_name; the code gathers
    of `hop.score` do."""
    gathers = [ln for ln, _ in gathers_with_operand(unfused_search_hlo(d))]
    assert any("hop.score" in ln for ln in gathers)
    assert not any("hop.merge" in ln for ln in gathers)


@pytest.mark.parametrize("d", DIMS + (960,))
def test_unfused_search_scores_with_one_row_gather(unfused_search_hlo, d):
    """`hop.score` of the default lane holds exactly one gather, of whole
    code rows from the 2-D uint8[CAP, P + 8] table: the RaBitQ factors come
    in the row, and no gather in the program reads a rank-1 f32 array."""
    gathers = gathers_with_operand(unfused_search_hlo(d))
    score = [(ln, t) for ln, t in gathers if "hop.score" in ln]
    assert len(score) == 1, score
    assert score[0][1] == f"u8[{CAP},{packed_width(d) + 8}]", score
    assert not [t for _, t in gathers if re.fullmatch(r"f32\[\d+\]", t)]


GIST_D, ROWS, BATCH = 960, 4096, 1024
BUILD = ConstructionParams(degree_bound=R, alpha=1.2, beam_width=L)


def _build_programs(spec):
    """The programs that see float32 rows when a 960-d index is built and
    encoded, each with the arguments of a small build."""
    rows, adj = spec((ROWS, GIST_D), jnp.float32), spec((ROWS, R), jnp.int32)
    one = spec((), jnp.int32)
    rot, cen = spec((GIST_D, GIST_D), jnp.float32), spec((GIST_D,), jnp.float32)
    return {
        "bootstrap_graph": (lambda v, a, n, m: bootstrap_graph(
            v, VamanaGraph(a, n, m), n0=1024, params=BUILD),
            (rows, adj, one, one)),
        "batch_insert_at": (lambda v, a, n, m, ids, sq: batch_insert_at(
            v, VamanaGraph(a, n, m), ids, params=BUILD, vec_sqnorm=sq),
            (rows, adj, one, one, spec((BATCH,), jnp.int32),
             spec((ROWS,), jnp.float32))),
        "compute_medoid": (compute_medoid,
                           (rows, spec((ROWS,), jnp.bool_))),
        "encode": (lambda v, r, c: rabitq._encode(v, r, c, BITS),
                   (rows, rot, cen)),
        "preprocess_query": (lambda q, r, c: rabitq._preprocess_query(
            q, r, c, BITS), (spec((Q, GIST_D), jnp.float32), rot, cen)),
    }


@pytest.mark.parametrize("name", ["bootstrap_graph", "batch_insert_at",
                                  "compute_medoid", "encode",
                                  "preprocess_query"])
def test_float_row_products_run_at_highest_on_the_chip(spec, name):
    """No product over float32 rows runs as one bfloat16 pass: every
    `dot` or `convolution` the TPU compiler leaves in a 960-d build or
    encode program carries `operand_precision={highest,highest}`. The
    walk's, the prunes' and the medoid's matrix-vector products hold none
    (the compiler makes them f32 multiply-reduce fusions)."""
    fn, args = _build_programs(spec)[name]
    products = [ln for ln in compile_text(fn, *args).splitlines()
                if re.search(r"=\s*\S+\s+(dot|convolution)\(", ln)]
    for ln in products:
        assert "operand_precision={highest,highest}" in ln, ln
    assert bool(products) == (name in ("bootstrap_graph", "encode",
                                       "preprocess_query"))

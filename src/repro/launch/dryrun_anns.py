import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""ANNS-at-scale dry-run: the paper's own workload on the production mesh.

Lowers + compiles the sharded Jasper search step at PAPER scale — e.g.
BigANN 100M rows over the (pod, data) axes with queries sharded over
`model` — and records the same roofline terms as the LM cells.

Since the IndexCore unification this file contains NO search logic: it
builds an abstract stacked `IndexCore` (ShapeDtypeStructs) and lowers the
SAME `sharded_search_fn` that `ShardedJasperIndex` serves with — the
shard-local `core_search` + all_gather merge, tombstone bitmaps included
(the production posture: per-shard liveness rides in every cell).

Variants per dataset:

    exact          full-precision beam search (paper "Jasper")
    exact_bf16     same with bf16-resident rows
    rabitq         estimated-distance search over PACKED codes, no rerank
                   — f32 rows NOT resident (degenerate 1-dim vector
                   buffer), the paper's memory-footprint story
    rabitq_rerank  packed-code search + tiled exact rerank — f32 rows
                   resident, the recall-recovery configuration
    exact_mega     exact search through the persistent whole-search
                   megakernel (fusion="megakernel", ISSUE 6)
    rabitq_mega    packed-code megakernel search, no rerank — the paper's
                   fused-kernel + memory-footprint posture combined
    bruteforce     one matmul tile over all rows (roofline sanity anchor)

Usage:
    python -m repro.launch.dryrun_anns [--dataset bigann] [--multi-pod]
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ANNS_DATASETS
from repro.core.distributed import ShardSpec, merge_topk, sharded_search_fn
from repro.core.index_core import IndexCore
from repro.core.search_spec import SearchSpec
from repro.core.mutations import MutationState
from repro.core.rabitq import RaBitQCodes, RaBitQParams
from repro.launch.mesh import make_production_mesh
from repro.roofline.analysis import TPU_V5E, roofline_terms
from repro.roofline.hlo_analyzer import analyze_hlo

DEGREE = 64          # paper: R = 64 everywhere
BEAM = 64            # overridable via --beam (hillclimb)
MAX_ITERS = 96       # overridable via --iters
EXPAND = 1           # overridable via --expand (multi-expansion, §Perf #C)
K = 10
N_QUERIES = 16384    # large batch = the paper's occupancy story


def abstract_core(n_shards: int, cap: int, dims: int, *,
                  vec_dtype=jnp.float32, vec_dims: int | None = None,
                  quantized: bool = False, bits: int = 4) -> IndexCore:
    """Stacked-core ShapeDtypeStructs: the dry-run's stand-in for real
    device buffers. vec_dims=1 gives the quantized-only memory posture
    (f32 rows not resident beyond a degenerate 4 B/row stub)."""
    rows = n_shards * cap
    vd = dims if vec_dims is None else vec_dims
    f32 = jnp.float32

    def st(shape, dt=f32):
        return jax.ShapeDtypeStruct(shape, dt)

    codes = rq = None
    if quantized:
        p_dim = (dims * bits + 7) // 8
        codes = RaBitQCodes(rows=st((rows, p_dim + 8), jnp.uint8),
                            bits=bits, dims=dims)
        rq = RaBitQParams(rotation=st((dims, dims)), centroid=st((dims,)),
                          bits=bits)
    return IndexCore(
        vectors=st((rows, vd), vec_dtype), vec_sqnorm=st((rows,)),
        adjacency=st((rows, DEGREE), jnp.int32),
        n_valid=st((n_shards,), jnp.int32),
        medoid=st((n_shards,), jnp.int32),
        mut=MutationState(tombstone_bits=st((rows // 8,), jnp.uint8),
                          free_ids=st((rows,), jnp.int32),
                          n_free=st((n_shards,), jnp.int32),
                          n_deleted=st((n_shards,), jnp.int32),
                          generation=st((n_shards,), jnp.int32)),
        codes=codes, rq_params=rq)


def lower_anns_cell(ds_name: str, variant: str, mesh, *, bits: int = 4,
                    n_queries: int = N_QUERIES) -> dict:
    ds = ANNS_DATASETS[ds_name]
    t0 = time.time()
    spec = ShardSpec(
        row_axes=tuple(a for a in mesh.axis_names if a != "model"),
        query_axis="model")
    n_shards = 1
    for ax in spec.row_axes:
        n_shards *= mesh.shape[ax]
    cap = -(-ds.full_n // n_shards)
    cap += (-cap) % 8                       # bitmap-aligned per-shard cap
    d = ds.dims + (1 if ds.metric == "mips" else 0)
    f32 = jnp.float32

    if variant in ("exact", "exact_bf16", "rabitq", "rabitq_rerank",
                   "exact_mega", "rabitq_mega"):
        quantized = variant.startswith("rabitq")
        rerank = variant == "rabitq_rerank"
        fusion = "megakernel" if variant.endswith("_mega") else "none"
        core = abstract_core(
            n_shards, cap, d,
            vec_dtype=jnp.bfloat16 if variant == "exact_bf16" else f32,
            # quantized cells without rerank keep f32 rows OFF-device
            # (degenerate stub): the paper's memory story, measured honestly
            vec_dims=(1 if quantized and not rerank else None),
            quantized=quantized, bits=bits)
        # the dry-run lowers the SAME resolved spec object the serving
        # driver compiles against — one configuration type, end to end
        search = SearchSpec(
            k=K, beam_width=BEAM, max_iters=MAX_ITERS,
            expand=1 if fusion != "none" else EXPAND,
            quantized=quantized, rerank=rerank, fusion=fusion).resolve()
        fn = sharded_search_fn(mesh, spec, core, id_stride=cap,
                               spec=search, filter_tombstones=True)
        queries = jax.ShapeDtypeStruct((n_queries, d), f32)
        lowered = fn.lower(core, queries)
    elif variant == "bruteforce":
        rows = n_shards * cap
        row_spec = P(spec.row_axes, None)
        sc_spec = P(spec.row_axes)
        q_spec = P("model", None)

        def bf(v, sq, nv, q):
            qs = jnp.sum(q * q, axis=-1)
            dist = qs[:, None] - 2.0 * (q @ v.T) + sq[None, :]
            neg, ids = jax.lax.top_k(-dist, K)
            # same hierarchical shard merge as the real search path
            return merge_topk(ids.astype(jnp.int32), -neg,
                              spec.row_axes, K)

        fn = jax.shard_map(
            bf, mesh=mesh,
            in_specs=(row_spec, sc_spec, sc_spec, q_spec),
            out_specs=(q_spec, q_spec), check_vma=False)
        args = (jax.ShapeDtypeStruct((rows, d), f32),
                jax.ShapeDtypeStruct((rows,), f32),
                jax.ShapeDtypeStruct((n_shards,), jnp.int32),
                jax.ShapeDtypeStruct((n_queries, d), f32))
        shardings = tuple(NamedSharding(mesh, s) for s in (
            row_spec, sc_spec, sc_spec, q_spec))
        lowered = jax.jit(fn, in_shardings=shardings).lower(*args)
    else:
        raise ValueError(variant)

    rec = {
        "dataset": ds_name, "variant": variant,
        "rows_total": ds.full_n, "dims": d, "n_queries": n_queries,
        "beam": BEAM, "max_iters": MAX_ITERS, "expand": EXPAND, "k": K,
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "n_shards": n_shards, "capacity_per_shard": cap,
        "lower_s": round(time.time() - t0, 2),
    }
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 2)
    mem = compiled.memory_analysis()
    rec["memory_per_device_gb"] = round(
        (mem.argument_size_in_bytes + mem.output_size_in_bytes
         + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 2**30, 3)
    ana = analyze_hlo(compiled.as_text())
    rec["cost_per_device"] = {"flops": ana["flops"],
                              "bytes_accessed": ana["bytes_accessed"]}
    rec["collectives_per_device"] = ana["collectives"]
    rec["roofline"] = roofline_terms(
        ana["flops"], ana["bytes_accessed"],
        ana["collectives"]["total"]["bytes"], 1, TPU_V5E)
    # paper's headline metric: queries/sec at the memory roof
    bound = rec["roofline"]["bound_s"]
    rec["queries_per_sec_at_roof"] = (n_queries / bound) if bound else None
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dataset", action="append", default=None)
    ap.add_argument("--variant", action="append", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--beam", type=int, default=None)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--expand", type=int, default=None)
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="results/dryrun_anns")
    args = ap.parse_args()

    global BEAM, MAX_ITERS, EXPAND
    if args.beam:
        BEAM = args.beam
    if args.iters:
        MAX_ITERS = args.iters
    if args.expand:
        EXPAND = args.expand

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    tag = ("multipod" if args.multi_pod else "singlepod") + args.tag
    datasets = args.dataset or list(ANNS_DATASETS)
    variants = args.variant or ["exact", "rabitq", "bruteforce"]
    # extra variants: exact_bf16, rabitq_rerank
    os.makedirs(args.out, exist_ok=True)
    n_err = 0
    for ds in datasets:
        for variant in variants:
            cell = f"{ds}__{variant}__{tag}"
            print(f"[cell] {cell} ...", flush=True)
            try:
                rec = lower_anns_cell(ds, variant, mesh, bits=args.bits)
                rec["status"] = "ok"
                r = rec["roofline"]
                print(f"  ok: compile {rec['compile_s']}s "
                      f"mem {rec['memory_per_device_gb']}GB "
                      f"dominant {r['dominant']} "
                      f"qps@roof {rec['queries_per_sec_at_roof']:.3e}",
                      flush=True)
            except Exception as e:  # noqa: BLE001
                rec = {"dataset": ds, "variant": variant, "status": "error",
                       "error": repr(e), "traceback": traceback.format_exc()}
                print(f"  ERROR: {e!r}", flush=True)
                n_err += 1
            with open(os.path.join(args.out, cell + ".json"), "w") as f:
                json.dump(rec, f, indent=2, default=str)
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

"""Sharded search benchmark: QPS vs shard count + merge-collective bytes.

Runs the SAME IndexCore search (`core_search` under shard_map) over 1, 2,
4, ... row shards, as many as the process has devices, and measures

  * end-to-end search wall time / QPS per shard count (quantized
    packed-code path, exact rerank on-shard),
  * recall@10 vs global brute force (shard-and-merge must not cost
    recall),
  * the merge collective's footprint from the compiled HLO (all_gather
    bytes per device) — the paper's argument that shard-and-merge moves
    only Q*k*8 bytes per hop,
  * a fused-Pallas-kernel-path cell at the max shard count (parity +
    no-tombstone-leak check after a delete wave).

Runs in the calling process over its real devices (one process holds a
chip, so no child may need it):

    PYTHONPATH=src:. python -m benchmarks.run --only distributed [--fast]

A CPU rehearsal of the multi-shard cells gives the process fake devices:
XLA_FLAGS=--xla_force_host_platform_device_count=8. Emits
BENCH_distributed.json.
"""

from __future__ import annotations

import json
import time

import numpy as np

DIMS = 64
K = 10
BEAM = 32
N_QUERIES = 256


def _shard_counts() -> tuple[int, ...]:
    """Powers of two up to the process's device count."""
    import jax
    n_dev = len(jax.devices())
    return tuple(s for s in (1, 2, 4, 8) if s <= n_dev)


def _make_mesh(n_shards: int):
    from repro.launch.mesh import make_mesh
    return make_mesh((n_shards,), ("data",))


def run(csv, n: int | None = None,
        out_json: str | None = "BENCH_distributed.json") -> list[dict]:
    import jax
    from benchmarks.common import BENCH_PARAMS, time_call
    from repro.core.distributed import ShardedJasperIndex, sharded_search_fn
    from repro.roofline.hlo_analyzer import analyze_hlo

    n = n or 8192
    rng = np.random.default_rng(0)
    data = rng.normal(size=(n, DIMS)).astype(np.float32)
    queries = rng.normal(size=(N_QUERIES, DIMS)).astype(np.float32)

    records: list[dict] = []
    idx = None
    for s in _shard_counts():
        mesh = _make_mesh(s)
        cap = -(-int(n * 1.25) // s)
        cap += (-cap) % 8
        idx = ShardedJasperIndex(mesh, DIMS, capacity_per_shard=cap,
                                 construction=BENCH_PARAMS,
                                 quantization="rabitq", bits=4)
        t0 = time.perf_counter()
        idx.build(data)
        build_s = time.perf_counter() - t0

        us = time_call(lambda: idx.search(queries, K, beam_width=BEAM,
                                          quantized=True))
        qps = N_QUERIES / (us * 1e-6)
        rec_q = idx.recall(queries, K, beam_width=BEAM, quantized=True)

        # merge-collective bytes from the compiled sharded search step
        from repro.core.search_spec import SearchSpec
        fn = sharded_search_fn(
            mesh, idx.spec, idx.core, id_stride=idx.id_stride,
            spec=SearchSpec(k=K, beam_width=BEAM,
                            max_iters=2 * BEAM + 12,
                            quantized=True).resolve(),
            filter_tombstones=False)
        q_dev = jax.numpy.asarray(queries)
        ana = analyze_hlo(fn.lower(idx.core, q_dev).compile().as_text())
        coll = ana["collectives"]["total"]
        csv.add(f"distributed/search_s{s}", us,
                f"qps={qps:.0f} recall={rec_q:.3f} "
                f"coll_bytes={coll['bytes']:.0f}")
        records.append({
            "n_shards": s, "rows": n, "dims": DIMS,
            "capacity_per_shard": idx.cap,
            "build_s": round(build_s, 2),
            "search_us": round(us, 1), "qps": round(qps, 1),
            "recall_at_10": round(rec_q, 4),
            "merge_collective_bytes_per_device": coll["bytes"],
            "merge_collective_count": coll["count"],
        })

    # kernel path at max shard count: parity + tombstone-leak check under
    # a delete wave (the fused epilogue must mask per-shard tombstones)
    dead = rng.choice(n // 2, 200, replace=False)
    gids = (dead // (n // idx.n_shards)) * idx.id_stride \
        + (dead % (n // idx.n_shards))
    idx.delete(gids)
    ids_k, _ = idx.search_rabitq(queries, K, beam_width=BEAM,
                                 use_kernels=True)
    leaked = int(np.isin(np.asarray(ids_k), gids).sum())
    rec_k = idx.recall(queries, K, beam_width=BEAM, quantized=True)
    csv.add(f"distributed/kernel_s{idx.n_shards}", 0.0,
            f"recall={rec_k:.3f} tombstone_leaks={leaked}")
    records.append({"n_shards": idx.n_shards, "path": "rabitq_kernel",
                    "recall_at_10_after_deletes": round(rec_k, 4),
                    "tombstone_leaks": leaked})

    # elastic reshard: a checkpoint saved at the max shard count
    # (tombstones and all) restores at the others — recall at EQUAL TOTAL
    # SEARCH BUDGET
    # (S' shards x TOTAL_BEAM/S' beam each) must hold, and the fused
    # kernel path must leak zero tombstones after the move
    reshard = _run_reshard(csv, data, queries, rng, n)

    if out_json:
        with open(out_json, "w") as f:
            json.dump({"shard_sweep": records, "reshard": reshard,
                       "n_queries": N_QUERIES, "k": K, "beam": BEAM}, f,
                      indent=2)
        print(f"# wrote {out_json}", flush=True)
    return records


def _run_reshard(csv, data, queries, rng, n: int) -> dict:
    import tempfile
    import time as _time

    from repro.core.distributed import ShardedJasperIndex
    from benchmarks.common import BENCH_PARAMS

    counts = _shard_counts()
    src = counts[-1]
    mesh_src = _make_mesh(src)
    cap = -(-int(n * 1.25) // src)
    cap += (-cap) % 8
    idx_src = ShardedJasperIndex(mesh_src, DIMS, capacity_per_shard=cap,
                                 construction=BENCH_PARAMS,
                                 quantization="rabitq", bits=4)
    idx_src.build(data)
    per = n // src
    dead = rng.choice(n, max(64, n // 16), replace=False)
    gids = (dead // per) * idx_src.id_stride + dead % per
    idx_src.delete(gids)
    path = f"{tempfile.mkdtemp()}/ck"
    idx_src.save(path)
    total_beam = src * BEAM
    base = idx_src.recall(queries, K, beam_width=total_beam // src,
                          quantized=True)
    restores = []
    for s in [c for c in counts if c != src] or [src]:
        t0 = _time.perf_counter()
        idx_r = ShardedJasperIndex.load(_make_mesh(s), path, n_shards=s)
        load_s = _time.perf_counter() - t0
        bw = max(K, total_beam // s)
        rec = idx_r.recall(queries, K, beam_width=bw, quantized=True)
        ids_k, _ = idx_r.search_rabitq(queries, K, beam_width=bw,
                                       use_kernels=True)
        ids_np = np.asarray(ids_k)
        ret = ids_np[ids_np >= 0]
        leaks = int(idx_r.tombstoned(ret).sum())
        tr = idx_r.reshard_translation
        csv.add(f"distributed/reshard_{src}to{s}", load_s * 1e6,
                f"recall={rec:.3f} d={rec - base:+.3f} leaks={leaks}")
        restores.append({
            "restore_shards": s, "restore_s": round(load_s, 2),
            "beam_width_per_shard": bw,
            "recall_at_10": round(rec, 4),
            "recall_delta_vs_source": round(rec - base, 4),
            "kernel_tombstone_leaks": leaks,
            "ids_translated": len(tr),
        })
    return {"from_shards": src, "n_deleted": int(dead.size),
            "total_beam": total_beam,
            "baseline_recall_at_10": round(base, 4),
            "restores": restores}


#!/usr/bin/env python3
"""Start the served Top-K SpMV path on a TPU and check its answers.

Builds the paper's deployment (``configs/topk_spmv.CONFIG``: 10M sparse
embedding rows x 512 columns, Gamma-distributed ~20 nnz/row, K=100, k=8 per
partition, BF16 values, block 256, fused stream) from ``--seed`` and serves it
through ``SparseEmbeddingIndex`` -> ``StreamingSimilarityService`` with the
micro-batching frontend, with the Pallas kernels compiled by Mosaic.  The
answers are checked against the same snapshot's jnp reference path on the
chip and against the exact top-K on the host, and ``dispatch_info()`` must
show compiled kernel dispatch with no retrace after warm-up and no retry,
failure, failover or degraded answer.

    python chip_smoke.py                  # one chip: the main path
    python chip_smoke.py --rows 2000000   # a cut index (printed as such)
    python chip_smoke.py --chips 4        # only the sharded plane, 4 chips

Lines before the last are progress and smoke timings (not benchmark
numbers).  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or without the repository next to it, the script exits
non-zero and prints no result.  The compile cache lives in
``$JAX_COMPILATION_CACHE_DIR`` when that is set, else in ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent

# Recall@100 of the served answers against the exact f32 top-K, 16 queries.
# A CPU rehearsal at seed 0 (reference path, the same approximation) gave
# 0.99625 at 200K rows, 0.993125 at 1M, 0.99375 at 2M and 0.991875 at 4M,
# the largest size run on a CPU; the floor is that value less 0.02.
RECALL_FLOOR = 0.971875
OVERLAP_FLOOR = 0.99          # kernel vs reference rows, same snapshot
GEN_CHUNK_ROWS = 500_000      # bounds the generator's host memory


def log(msg: str) -> None:
    print(msg, flush=True)


def timing(name: str, seconds: float) -> None:
    log(f"smoke timing (not a benchmark): {name} = {seconds:.3f} s")


def make_collection(rows: int, cols: int, mean_nnz: float, dist: str, seed: int):
    """The collection, generated in row chunks from ``seed`` (same law as one
    ``synthetic_embedding_csr`` call, bounded memory)."""
    import numpy as np
    from repro.core import bscsr

    parts, start = [], 0
    for i, lo in enumerate(range(0, rows, GEN_CHUNK_ROWS)):
        n = min(GEN_CHUNK_ROWS, rows - lo)
        parts.append(bscsr.synthetic_embedding_csr(n, cols, mean_nnz, dist, seed * 1000 + i))
    indptr = [np.zeros(1, np.int64)]
    for p in parts:
        indptr.append(p.indptr[1:] + start)
        start += p.nnz
    return bscsr.CSRMatrix(
        indptr=np.concatenate(indptr),
        indices=np.concatenate([p.indices for p in parts]),
        data=np.concatenate([p.data for p in parts]),
        shape=(rows, cols),
    )


def make_queries(n: int, cols: int, seed: int):
    import numpy as np

    return np.random.default_rng(seed + 1).standard_normal((n, cols)).astype(np.float32)


def service_config():
    from repro.configs.topk_spmv import CONFIG
    from repro.core.topk_spmv import TopKSpMVConfig

    return TopKSpMVConfig(
        big_k=CONFIG.big_k, k=CONFIG.k, block_size=CONFIG.block_size,
        value_format=CONFIG.value_format, stream_layout="fused",
    )


def row_overlap(a, b) -> float:
    """Mean |rows(a) & rows(b)| / K over a (Q, K) pair of answers."""
    return sum(len(set(x.tolist()) & set(y.tolist())) / len(x) for x, y in zip(a, b)) / len(a)


def exact_recall(csr, xs, rows, big_k: int) -> float:
    from repro.core.topk_spmv import topk_spmv_exact

    return row_overlap(rows, [topk_spmv_exact(csr, x, big_k)[1] for x in xs])


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    log(f"ok: {what}")


def build_data(args):
    from repro.configs.topk_spmv import CONFIG

    t0 = time.perf_counter()
    csr = make_collection(args.rows, CONFIG.n_cols, CONFIG.mean_nnz_per_row,
                          CONFIG.distribution, args.seed)
    timing("generate collection", time.perf_counter() - t0)
    log(f"collection: {csr.shape[0]} rows x {csr.shape[1]} cols, {csr.nnz} nnz "
        f"({csr.nnz / csr.shape[0]:.2f}/row), seed {args.seed}")
    return csr


def single_chip(args) -> None:
    import numpy as np
    from repro.core.similarity import SparseEmbeddingIndex
    from repro.serve.frontend import FrontendConfig
    from repro.serve.streaming import StreamingSimilarityService

    csr = build_data(args)
    cfg = service_config()
    t0 = time.perf_counter()
    index = SparseEmbeddingIndex(csr, cfg)
    timing("build index (host)", time.perf_counter() - t0)
    st = index.stats()
    log(f"index: {st.num_partitions} partitions, {st.value_format_histogram} "
        f"values, layout {st.stream_layout}, {st.stream_bytes} stream bytes, "
        f"{st.bytes_per_nnz:.4f} B/nnz, expected precision {st.expected_precision:.5f}")
    svc = StreamingSimilarityService(
        index, frontend=FrontendConfig(flush_deadline_s=0.005, max_batch=64)
    )
    big_k = cfg.big_k
    xs = make_queries(64, csr.shape[1], args.seed)

    # -- warm-up: compile every Q bucket the run uses ------------------------
    answers = {}
    for q in (1, 16, 64):
        t0 = time.perf_counter()
        answers[q] = index.query_batch(xs[:q])
        timing(f"first query_batch Q={q} (compile + run)", time.perf_counter() - t0)

    # -- answers: kernel vs reference (same snapshot, on the chip) and exact --
    ref_rows = []
    t0 = time.perf_counter()
    for i in range(16):
        ref_rows.append(index.query_batch(xs[i : i + 1], use_kernel=False)[1][0])
    timing("reference path, 16 queries one by one", time.perf_counter() - t0)
    overlap = row_overlap(answers[16][1], ref_rows)
    log(f"kernel vs reference row overlap (16 queries): {overlap:.5f}")
    check(overlap >= OVERLAP_FLOOR, f"reference overlap {overlap:.5f} >= {OVERLAP_FLOOR}")
    check(all(np.array_equal(answers[64][1][i], answers[q][1][i])
              for q in (1, 16) for i in range(q)),
          "Q=1/16/64 batches give identical rows")
    t0 = time.perf_counter()
    recall = exact_recall(csr, xs[:16], answers[16][1], big_k)
    timing("exact top-K on the host, 16 queries", time.perf_counter() - t0)
    log(f"recall@{big_k} vs exact (16 queries): {recall:.5f}")
    check(recall >= RECALL_FLOOR, f"recall@{big_k} {recall:.5f} >= {RECALL_FLOOR}")

    # -- the frontend: a few dozen submit() calls ----------------------------
    t0 = time.perf_counter()
    futures = [svc.submit(x) for x in xs[:48]]
    got = [f.result(timeout=600) for f in futures]
    timing("48 submit() queries through the frontend", time.perf_counter() - t0)
    check(all(np.array_equal(g[1], answers[64][1][i]) for i, g in enumerate(got)),
          "frontend answers match query_batch rows")

    # -- mutable path: upsert + delete + query (warm once, then steady) ------
    def mutate_and_query(probe):
        new_id = int(index.upsert(probe[None, :])[0])
        victim = int(index.query_batch(probe[None, :])[1][0][1])
        index.delete([victim])
        v, r = svc.search(probe[None, :])
        check(int(r[0][0]) == new_id, f"upserted row {new_id} answers its own probe")
        check(victim not in r[0].tolist(), f"deleted row {victim} never returned")

    t0 = time.perf_counter()
    mutate_and_query(xs[60])
    for q in (16, 64):
        index.query_batch(xs[:q])
    timing("first upsert/delete/query + Q=16/64 (compiles the segmented snapshot)",
           time.perf_counter() - t0)
    warm = svc.dispatch_info()

    # -- steady state: no build, no retrace ----------------------------------
    t0 = time.perf_counter()
    mutate_and_query(xs[61])
    timing("steady upsert/delete/query", time.perf_counter() - t0)
    for q in (1, 16, 64):
        t0 = time.perf_counter()
        index.query_batch(xs[:q])
        timing(f"query_batch Q={q}, wall per call", time.perf_counter() - t0)
    info = svc.dispatch_info()
    log("dispatch_info: " + json.dumps({k: v for k, v in info.items() if k != "signature"},
                                        default=str))
    check(info["interpret"] is False, "kernels compiled by Mosaic (interpret False)")
    check("kernel" in info["paths"] and info["gather_mode"] == "onehot",
          "kernel path built with the one-hot MXU gather")
    check(set(info["seg_slots"]) == {128},
          f"stage-2 segment space 128 of 640 on every pin ({info['seg_slots']})")
    check(info["retraces"] == warm["retraces"] and info["fn_builds"] == warm["fn_builds"],
          f"zero retraces and builds after warm-up (retraces {warm['retraces']} -> "
          f"{info['retraces']}, builds {warm['fn_builds']} -> {info['fn_builds']})")
    s = info["service"]
    check(s["failures"] == s["retries"] == 0, "no dispatch failure or retry")
    check(not s["last_search_degraded"] and s["degraded_queries"] == 0,
          "no degraded answer")
    log("failovers: 0 (single device)")


def four_chips(args) -> None:
    import jax
    import numpy as np
    from repro.core.similarity import SparseEmbeddingIndex
    from repro.launch.mesh import make_serving_mesh

    devices = jax.devices()[:4]
    check(len(devices) == 4, f"4 chips visible ({len(jax.devices())})")
    csr = build_data(args)
    cfg = service_config()
    big_k = cfg.big_k
    xs = make_queries(16, csr.shape[1], args.seed)

    t0 = time.perf_counter()
    single = SparseEmbeddingIndex(csr, cfg)
    want = single.query_batch(xs)
    timing("single-chip build + first query", time.perf_counter() - t0)
    recall = exact_recall(csr, xs, want[1], big_k)
    log(f"single-chip recall@{big_k} vs exact (16 queries): {recall:.5f}")
    check(recall >= RECALL_FLOOR, f"recall@{big_k} {recall:.5f} >= {RECALL_FLOOR}")
    del single

    for shards, replicas in ((4, 1), (2, 2)):
        mesh = make_serving_mesh(n_shards=shards, n_replicas=replicas, devices=devices)
        name = f"{replicas}x{shards} replicas x shards"
        check(set(mesh.devices.flat) == set(devices), f"{name} mesh spans jax.devices()[:4]")
        t0 = time.perf_counter()
        index = SparseEmbeddingIndex(csr, cfg, mesh=mesh)
        timing(f"{name}: build", time.perf_counter() - t0)
        t0 = time.perf_counter()
        got = index.query_batch(xs)
        timing(f"{name}: first query_batch Q=16 (compile + run)", time.perf_counter() - t0)
        t0 = time.perf_counter()
        got = index.query_batch(xs)
        timing(f"{name}: query_batch Q=16, wall per call", time.perf_counter() - t0)
        info = index.dispatch_info()
        log(f"{name} dispatch_info: " + json.dumps(
            {k: info[k] for k in ("path", "topology", "health", "fn_builds", "retraces",
                                  "interpret", "bundle")}, default=str))
        placed = index.index._spmd.bundle.placement("words")
        column = {d: j for row in mesh.devices for j, d in enumerate(row)}
        check(set(placed) == set(devices) and all(placed[d] == column[d] for d in placed),
              f"{name}: each chip holds the stream of its own mesh column")
        check(info["path"] == "spmd" and info["interpret"] is False,
              f"{name}: SPMD path, Mosaic-compiled")
        check(info["health"]["failovers"] == 0 and not info["health"]["last_query_degraded"],
              f"{name}: no failover, not degraded")
        check(np.array_equal(got[1], want[1]), f"{name}: rows identical to the single chip")
        del index


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=None,
                    help="collection rows (default: the config's 10M on one chip, "
                         "1M for --chips 4)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    if not (REPO / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {REPO / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(REPO / "src"))

    import jax

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(REPO / ".jax_cache"))
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}", file=sys.stderr)
        return 3
    kind = devices[0].device_kind
    log(f"device: {kind} x {len(devices)} ({devices[0].platform}), jax {jax.__version__}")

    from repro.configs.topk_spmv import CONFIG

    default_rows = CONFIG.n_rows if args.chips == 1 else 1_000_000
    if args.rows is None:
        args.rows = default_rows
    if args.rows != CONFIG.n_rows:
        log(f"cut: {args.rows} rows instead of the config's {CONFIG.n_rows}"
            + (" (four-chip phase, held to the chip budget)" if args.chips == 4 else ""))
    t0 = time.perf_counter()
    (single_chip if args.chips == 1 else four_chips)(args)
    timing("whole run", time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {"platform": devices[0].platform,
                                             "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

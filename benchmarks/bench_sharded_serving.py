"""Sharded serving plane: QPS scale-out + shard-scaling latency (8 devices).

Measures the two scaling axes of ``core.sharded.ShardedTopKSpMVIndex`` on a
simulated 8-device host (``--xla_force_host_platform_device_count=8``):

* replica scale-out — one index replicated across R query-replica groups,
  batches fanned out over the "replica" mesh axis.  Ideal hardware serves
  the R groups concurrently, so QPS grows ~linearly with R at flat p50.
* shard scaling — rows/device held FIXED while the collection grows with
  the shard count; per-shard kernels run concurrently and candidates merge
  through the log-depth ppermute tree, so ideal-parallel latency stays
  within a small factor of the single-shard latency.

Simulated devices SERIALIZE on the host CPUs (this box usually has one), so
the measured wall numbers understate real scale-out by ~n_devices.  Each
axis therefore records BOTH the measured wall time and the ideal-parallel
projection ``projected = t_wall / n_groups`` (device programs dominated by
per-device kernel work; the merge tree's cost is inside ``t_wall`` so the
projection slightly *overstates* merge cost at high shard counts).
``host_cpus`` is recorded so readers can judge the serialization assumption.

Every timed configuration is first asserted bit-identical to the
single-device ``topk_spmv``, and the steady-state dispatch is run under
``jax.transfer_guard("disallow")`` with retrace counters checked — the
scale-out numbers only count if the plane really is device-resident.

Results merge into ``BENCH_topk_spmv.json`` under ``sharded_serving``.
``--smoke`` (CI) runs tiny shapes through the same assertions, no json.

The measurement runs in a child process so the forced device count never
leaks into (or is blocked by) the parent's already-initialized jax.  The
child is pinned to the CPU backend (``JAX_PLATFORMS=cpu``): its devices are
simulated host devices, and on a TPU host the parent already holds the chip.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
_DEVICE_FLAG = "--xla_force_host_platform_device_count=8"

# ---------------------------------------------------------------------------
# child: runs under 8 forced host devices, prints one json line
# ---------------------------------------------------------------------------


def _child_main(smoke: bool) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (_DEVICE_FLAG + " " + flags).strip()
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.bscsr import synthetic_embedding_csr
    from repro.core.sharded import ShardedTopKSpMVIndex
    from repro.core.topk_spmv import (
        MutableTopKSpMVIndex,
        TopKSpMVConfig,
        topk_spmv,
        topk_spmv_batched,
    )
    from repro.launch.mesh import make_serving_mesh

    assert jax.device_count() == 8, jax.device_count()

    if smoke:
        rows_per_shard, n_cols, nnz, cps, block, qb, reps = 96, 64, 8, 2, 32, 2, 2
    else:
        rows_per_shard, n_cols, nnz, cps, block, qb, reps = 512, 128, 16, 4, 64, 4, 5

    rng = np.random.default_rng(0)

    def cfg_for(n_shards):
        return TopKSpMVConfig(big_k=32, k=8, num_partitions=cps * n_shards,
                              block_size=block)

    def timed(fn, n=reps):
        jax.block_until_ready(fn())  # warm: compile + pin streams
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(fn())
        return (time.perf_counter() - t0) / n

    out = {
        "host_cpus": os.cpu_count(),
        "n_devices": int(jax.device_count()),
        "assumption": (
            "simulated devices serialize on host CPUs; projected_* = "
            "t_wall / n_groups (ideal-parallel device programs)"
        ),
    }

    # -- replica scale-out: same index, R-way query fan-out ----------------
    csr = synthetic_embedding_csr(rows_per_shard, n_cols, nnz, "gamma", 1)
    single = MutableTopKSpMVIndex(csr, cfg_for(1))
    replica_axis = {}
    for r in (1, 8):
        mesh = make_serving_mesh(n_shards=1, n_replicas=r)
        idx = ShardedTopKSpMVIndex(csr, cfg_for(1), mesh=mesh)
        xs = rng.standard_normal((r * qb, n_cols)).astype(np.float32)
        got = idx.query_batched(jnp.asarray(xs))
        ref = topk_spmv_batched(single, jnp.asarray(xs))
        assert np.array_equal(np.asarray(got[0]), np.asarray(ref[0]))
        assert np.array_equal(np.asarray(got[1]), np.asarray(ref[1]))
        t = timed(lambda: idx.query_batched(jnp.asarray(xs)))
        replica_axis[str(r)] = {
            "queries_per_dispatch": r * qb,
            "wall_ms": t * 1e3,
            "measured_qps": (r * qb) / t,
            "projected_p50_ms": t / r * 1e3,
            "projected_qps": (r * qb) / (t / r),
        }
    qps1 = replica_axis["1"]["measured_qps"]
    replica_axis["projected_qps_ratio_8v1"] = (
        replica_axis["8"]["projected_qps"] / qps1
    )
    replica_axis["projected_p50_ratio_8v1"] = (
        replica_axis["8"]["projected_p50_ms"] / replica_axis["1"]["wall_ms"]
    )
    out["replica_scaleout"] = replica_axis

    # -- shard scaling: rows/device fixed, collection grows with S ---------
    shard_axis = {}
    for s in (1, 8):
        csr_s = synthetic_embedding_csr(
            rows_per_shard * s, n_cols, nnz, "gamma", 2
        )
        mesh = make_serving_mesh(n_shards=s, n_replicas=1)
        idx = ShardedTopKSpMVIndex(csr_s, cfg_for(s), mesh=mesh)
        oracle = MutableTopKSpMVIndex(csr_s, cfg_for(s))
        x = rng.standard_normal(n_cols).astype(np.float32)
        got = idx.query(jnp.asarray(x))
        ref = topk_spmv(oracle, jnp.asarray(x))
        assert np.array_equal(np.asarray(got[0]), np.asarray(ref[0]))
        assert np.array_equal(np.asarray(got[1]), np.asarray(ref[1]))
        t = timed(lambda: idx.query(jnp.asarray(x)))
        shard_axis[str(s)] = {
            "n_rows": rows_per_shard * s,
            "wall_ms": t * 1e3,
            "projected_p50_ms": t / s * 1e3,
        }
    shard_axis["projected_latency_ratio_8v1"] = (
        shard_axis["8"]["projected_p50_ms"] / shard_axis["1"]["wall_ms"]
    )
    out["shard_scaling"] = shard_axis

    # -- steady-state dispatch: device-resident or the numbers don't count --
    mesh = make_serving_mesh(n_shards=4, n_replicas=2)
    csr_m = synthetic_embedding_csr(rows_per_shard * 4, n_cols, nnz,
                                    "gamma", 3)
    idx = ShardedTopKSpMVIndex(csr_m, cfg_for(4), mesh=mesh)
    spec = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    xq = jax.device_put(
        jnp.asarray(rng.standard_normal(n_cols).astype(np.float32)), spec
    )
    idx.query(xq)  # pin + compile

    def fresh_row():
        cols = np.sort(rng.choice(n_cols, size=nnz, replace=False))
        return [(cols.astype(np.int32),
                 rng.standard_normal(nnz).astype(np.float32))]

    idx.query(xq)
    idx.add_rows(fresh_row())
    idx.query(xq)  # absorb the first-mutation packet-cap bucket jump
    base = idx.dispatch_info()
    shipped0 = base["bundle"]["partitions_shipped"]
    for _ in range(3):
        idx.add_rows(fresh_row())
        idx.query(xq)  # ships ONLY the dirty partitions
        with jax.transfer_guard("disallow"):  # steady dispatch: zero H2D
            v, r = idx.query(xq)
        np.asarray(v), np.asarray(r)
    info = idx.dispatch_info()
    assert info["retraces"] == base["retraces"], (
        "steady-state churn retraced", info["retraces"], base["retraces"])
    shipped = info["bundle"]["partitions_shipped"] - shipped0
    assert 0 < shipped < 3 * 4 * cps, shipped
    out["steady_state"] = {
        "transfer_guard": "disallow held across steady dispatch",
        "retraces_during_churn": info["retraces"] - base["retraces"],
        "dirty_partitions_shipped": int(shipped),
        "total_partitions_x_cycles": 3 * 4 * cps,
    }

    print("RESULT_JSON:" + json.dumps(out))


# ---------------------------------------------------------------------------
# parent: run.py entry point
# ---------------------------------------------------------------------------


def run(verbose: bool = True, smoke: bool = False) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # simulated host devices; never the chip
    env["PYTHONPATH"] = os.pathsep.join(
        [str(_REPO_ROOT / "src"), env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    cmd = [sys.executable, str(pathlib.Path(__file__).resolve()), "--child"]
    if smoke:
        cmd.append("--smoke")
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=1800)
    line = next((l for l in proc.stdout.splitlines()
                 if l.startswith("RESULT_JSON:")), None)
    if line is None:
        raise RuntimeError(
            f"sharded bench child failed:\n{proc.stderr[-3000:]}"
        )
    payload = json.loads(line[len("RESULT_JSON:"):])
    if verbose:
        rep, shd = payload["replica_scaleout"], payload["shard_scaling"]
        print(f"  host_cpus={payload['host_cpus']} "
              f"devices={payload['n_devices']} (simulated, serialized)")
        for r in ("1", "8"):
            e = rep[r]
            print(f"  replicas={r}: wall {e['wall_ms']:.2f} ms, "
                  f"measured {e['measured_qps']:.1f} qps, "
                  f"projected {e['projected_qps']:.1f} qps "
                  f"@ p50 {e['projected_p50_ms']:.2f} ms")
        print(f"  projected qps ratio 8v1: "
              f"{rep['projected_qps_ratio_8v1']:.2f}x "
              f"(p50 ratio {rep['projected_p50_ratio_8v1']:.2f})")
        for s in ("1", "8"):
            e = shd[s]
            print(f"  shards={s}: {e['n_rows']} rows, wall "
                  f"{e['wall_ms']:.2f} ms, projected p50 "
                  f"{e['projected_p50_ms']:.2f} ms")
        print(f"  projected latency ratio 8v1: "
              f"{shd['projected_latency_ratio_8v1']:.2f}x")
        ss = payload["steady_state"]
        print(f"  steady state: retraces={ss['retraces_during_churn']}, "
              f"dirty partitions shipped "
              f"{ss['dirty_partitions_shipped']}"
              f"/{ss['total_partitions_x_cycles']}")
    if not smoke:
        try:
            from benchmarks.bench_io import merge_into_bench_json
        except ImportError:
            from bench_io import merge_into_bench_json
        merge_into_bench_json(payload, section="sharded_serving")
    p50_us = payload["shard_scaling"]["1"]["wall_ms"] * 1e3
    ratio = payload["replica_scaleout"]["projected_qps_ratio_8v1"]
    return {
        "name": "sharded_serving",
        "us_per_call": p50_us,
        "derived": f"projected_qps_x{ratio:.1f}",
    }


if __name__ == "__main__":
    if "--child" in sys.argv[1:]:
        _child_main(smoke="--smoke" in sys.argv[1:])
    else:
        run(verbose=True, smoke="--smoke" in sys.argv[1:])

"""Kernel-path perf trajectory: inner loops x layouts x batching x formats.

Four sweeps at the paper's design point (B = 256, T = 2):

  * inner_loop: legacy (one-hot segmented sum + k-pass argmax) vs linear
    (cumsum-difference + threshold-filter-then-merge), per value format AND
    per stream layout — "split" (three BlockSpec streams per grid step) vs
    "fused" (one contiguous ``flags | cols | vals`` int32 word stream per
    core: one HBM burst per grid step, shift/mask decode in-kernel).  Each
    point records bytes/nnz so the layout table is tracked per format.
  * gather: stage-1 x-gather flavors (take vs onehot) on both layouts, plus
    the per-backend mode the one-shot microbenchmark resolves "auto" to.
  * batching: single vs multi-query at Q in {1, 8, 64} on both layouts — the
    batched call streams the matrix ONCE for all Q queries.
  * mixed precision: per-partition value formats chosen for a recall
    target on a hot/cold collection, bit-identical to the f32 twins on every
    inner loop, with recall@8 measured through the kernel.

The executor's per-query dispatch time is read on the chip, from the
program's ``repro.executor.dispatch`` spans (``bench/spans.py``).

Numbers are host-side interpret-mode timings (the correctness harness, not
TPU silicon), but the work ratio between paths is real.  Results merge into
``BENCH_topk_spmv.json`` at the repo root so the trajectory is tracked
across PRs.  ``smoke=True`` (CI) shrinks shapes, sweeps ALL four inner
loops on both layouts so no perf path can rot unexercised, and skips the
json write.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.bench_io import (
        BENCH_JSON, merge_into_bench_json, time_paired)
except ImportError:  # direct script run: benchmarks/ itself is sys.path[0]
    from bench_io import BENCH_JSON, merge_into_bench_json, time_paired
from repro.core import bscsr
from repro.kernels import ops
from repro.kernels.bscsr_topk_spmv import INNER_LOOPS

BLOCK = 256          # B — acceptance design point
T_STEP = 2           # T
CORES = 8
K = 8
BIG_K = 64

LAYOUTS = ("split", "fused")


def run(verbose: bool = True, n_rows: int = 8192, n_cols: int = 256,
        mean_nnz: int = 16, repeats: int = 9, smoke: bool = False,
        block: int = BLOCK, cores: int = CORES):
    if smoke:
        n_rows, n_cols, mean_nnz, repeats = 512, 64, 8, 1
        block, cores = 64, 2
    csr = bscsr.synthetic_embedding_csr(n_rows, n_cols, mean_nnz, "gamma", 0)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal(n_cols), jnp.float32)
    nnz = csr.nnz
    results = []

    packed = {
        layout: ops.pack_partitions(csr, cores, block, "F32",
                                    packets_multiple=T_STEP,
                                    stream_layout=layout)
        for layout in LAYOUTS
    }

    # --- sweep 1: inner loops x value formats x stream layouts (1 query) ---
    # Layouts are timed in interleaved rounds (time_paired) so background
    # load cancels out of the fused-vs-split ratio.
    loops = INNER_LOOPS if smoke else ("legacy", "linear")
    fused_ratio = {}
    for fmt in ("F32", "BF16", "Q15", "Q7"):
        p_by = (packed if fmt == "F32" else {
            layout: ops.pack_partitions(csr, cores, block, fmt,
                                        packets_multiple=T_STEP,
                                        stream_layout=layout)
            for layout in LAYOUTS
        })
        for loop in loops:
            ts = time_paired({
                layout: (lambda p=p_by[layout], l=loop: ops.topk_spmv_blocked(
                    x, p, BIG_K, k=K, packets_per_step=T_STEP, inner_loop=l,
                )[0].block_until_ready())
                for layout in LAYOUTS
            }, repeats)
            # split/fused ratio per interleaved round: adjacent calls see the
            # same background load, so the median round ratio is the robust
            # layout comparison on a drifting host.
            ratio = float(np.median(
                [a / b for a, b in zip(ts["split"], ts["fused"])]))
            if loop == "linear":
                fused_ratio[fmt] = ratio
            for layout, samples in ts.items():
                t = float(np.median(samples))
                results.append({
                    "sweep": "inner_loop", "fmt": fmt, "inner_loop": loop,
                    "layout": layout, "q": 1,
                    "bytes_per_nnz": p_by[layout].bytes_per_nnz,
                    "fused_vs_split": ratio,
                    "us_per_call": t * 1e6, "gnnz_per_s": nnz / t / 1e9,
                })
                if verbose:
                    print(f"inner_loop fmt={fmt:5s} {loop:11s} {layout:5s} "
                          f"{p_by[layout].bytes_per_nnz:5.2f} B/nnz "
                          f"{t*1e3:8.2f} ms  {nnz/t/1e9:.4f} GNNZ/s")

    # --- sweep 2: stage-1 gather flavors on both layouts (F32, linear) ---
    auto_mode = ops.resolve_gather_mode("auto")
    interpret = ops.default_interpret()
    # "take" is the interpret-only reference gather; compiled kernels refuse it
    for gather in ("take", "onehot") if interpret else ("onehot",):
        ts = time_paired({
            layout: (lambda g=gather, l=layout: ops.topk_spmv_blocked(
                x, packed[l], BIG_K, k=K, packets_per_step=T_STEP,
                gather_mode=g,
            )[0].block_until_ready())
            for layout in LAYOUTS
        }, repeats)
        for layout, samples in ts.items():
            t = float(np.median(samples))
            results.append({
                "sweep": "gather", "fmt": "F32", "inner_loop": "linear",
                "layout": layout, "gather_mode": gather, "q": 1,
                "us_per_call": t * 1e6, "gnnz_per_s": nnz / t / 1e9,
            })
            if verbose:
                print(f"gather     {gather:6s} {layout:5s} "
                      f"{t*1e3:8.2f} ms  {nnz/t/1e9:.4f} GNNZ/s")
    if verbose:
        print(f"gather     auto -> {auto_mode} on {jax.default_backend()}")

    # --- sweep 3: single vs batched query on both layouts (F32) ---
    qs = (1, 8) if smoke else (1, 8, 64)
    t_single = {
        layout: float(np.median(samples))
        for layout, samples in time_paired({
            layout: (lambda l=layout: ops.topk_spmv_blocked(
                x, packed[l], BIG_K, k=K, packets_per_step=T_STEP,
            )[0].block_until_ready())
            for layout in LAYOUTS
        }, repeats).items()
    }
    for q in qs:
        xs = jnp.asarray(rng.standard_normal((q, n_cols)), jnp.float32)
        ts = time_paired({
            layout: (lambda xs=xs, l=layout: ops.topk_spmv_batched(
                xs, packed[l], BIG_K, k=K, packets_per_step=T_STEP,
            )[0].block_until_ready())
            for layout in LAYOUTS
        }, repeats)
        for layout, samples in ts.items():
            t_batch = float(np.median(samples))
            # effective nnz throughput: all Q queries consume the stream once
            results.append({
                "sweep": "batching", "fmt": "F32", "inner_loop": "linear",
                "layout": layout, "q": q,
                "us_per_call": t_batch * 1e6,
                "gnnz_per_s": nnz * q / t_batch / 1e9,
                "sequential_us": t_single[layout] * q * 1e6,
                "speedup_vs_sequential": t_single[layout] * q / t_batch,
            })
            if verbose:
                print(f"batching   Q={q:3d} {layout:5s} "
                      f"batched {t_batch*1e3:8.2f} ms  "
                      f"sequential {t_single[layout]*q*1e3:8.2f} ms  "
                      f"speedup {t_single[layout]*q/t_batch:5.1f}x  "
                      f"{nnz*q/t_batch/1e9:.4f} GNNZ/s")

    # --- sweep 4: per-partition mixed-precision streams (recall-targeted) ---
    # Hot/cold collection: a few partitions carry full-magnitude scores, the
    # rest are scaled down (cold shards never contend for the global top-k) —
    # the regime where per-partition formats beat any single uniform format.
    # recall@8 is measured THROUGH the kernel at big_k = k = 8, where the
    # Eq. (1) partition term is exactly zero, so the measurement isolates
    # quantization loss.  Parity: the grouped tagged-stream dispatch must be
    # bit-identical to the same snapshot's f32 split twins on every inner
    # loop, single and batched.
    from repro.core import partition as partition_lib
    from repro.core.adaptive import assign_partition_formats
    from repro.kernels import ref as ref_lib

    recall_target = 0.99
    hot_parts = max(1, cores // 4)
    pplan = partition_lib.PartitionPlan.build(n_rows, cores)
    hot_end = int(pplan.row_starts[hot_parts]) if hot_parts < cores else n_rows
    scales = np.ones(n_rows, np.float32)
    scales[hot_end:] = 0.1 if smoke else 0.25
    mp_csr = bscsr.scale_rows(csr, scales)

    fmt_plan, _ = assign_partition_formats(
        mp_csr, cores, recall_target, k=K, n_queries=16
    )
    mp_packs = {
        "mixed": ops.pack_partitions(
            mp_csr, cores, block, packets_multiple=T_STEP,
            stream_layout="fused", value_formats=fmt_plan.formats,
        ),
        "BF16": ops.pack_partitions(mp_csr, cores, block, "BF16",
                                    packets_multiple=T_STEP,
                                    stream_layout="fused"),
        "F32": ops.pack_partitions(mp_csr, cores, block, "F32",
                                   packets_multiple=T_STEP,
                                   stream_layout="fused"),
    }

    s_eval = 8 if smoke else 64
    xs_eval = rng.standard_normal((s_eval, n_cols)).astype(np.float32)
    exact_rows = [
        set(ref_lib.csr_topk_numpy(
            mp_csr.indptr, mp_csr.indices, mp_csr.data, xq, K)[1].tolist())
        for xq in xs_eval
    ]

    def measured_recall(p) -> float:
        # big_k == k kills the partition term: recall@8 here is pure
        # quantization loss, the quantity the autotuner budgets.
        _, rr = ops.topk_spmv_batched(
            jnp.asarray(xs_eval), p, big_k=K, k=K, packets_per_step=T_STEP
        )
        rr = np.asarray(rr)
        return float(np.mean([
            len(set(rr[i].tolist()) & exact_rows[i]) / K
            for i in range(s_eval)
        ]))

    recalls = {name: measured_recall(p) for name, p in mp_packs.items()}
    vbpn = {name: p.value_bytes_per_nnz for name, p in mp_packs.items()}
    value_bytes_ratio_bf16 = vbpn["BF16"] / vbpn["mixed"]

    parity = {}
    x_par = jnp.asarray(xs_eval[0])
    for loop in (INNER_LOOPS if smoke else ("legacy", "linear")):
        fv, fr = ops.topk_spmv_blocked(
            x_par, mp_packs["mixed"], BIG_K, k=K, packets_per_step=T_STEP,
            inner_loop=loop,
        )
        sv, sr = ops.topk_spmv_blocked(
            x_par, mp_packs["mixed"], BIG_K, k=K, packets_per_step=T_STEP,
            inner_loop=loop, stream_layout="split",
        )
        bfv, bfr = ops.topk_spmv_batched(
            jnp.asarray(xs_eval), mp_packs["mixed"], BIG_K, k=K,
            packets_per_step=T_STEP, inner_loop=loop,
        )
        bsv, bsr = ops.topk_spmv_batched(
            jnp.asarray(xs_eval), mp_packs["mixed"], BIG_K, k=K,
            packets_per_step=T_STEP, inner_loop=loop, stream_layout="split",
        )
        parity[loop] = bool(
            np.array_equal(np.asarray(fv), np.asarray(sv))
            and np.array_equal(np.asarray(fr), np.asarray(sr))
            and np.array_equal(np.asarray(bfv), np.asarray(bsv))
            and np.array_equal(np.asarray(bfr), np.asarray(bsr))
        )

    ts = time_paired({
        name: (lambda p=p: ops.topk_spmv_blocked(
            x_par, p, BIG_K, k=K, packets_per_step=T_STEP,
        )[0].block_until_ready())
        for name, p in mp_packs.items()
    }, repeats)
    for name, samples in ts.items():
        t = float(np.median(samples))
        results.append({
            "sweep": "mixed_precision", "fmt": name, "inner_loop": "linear",
            "layout": "fused", "q": 1,
            "value_bytes_per_nnz": vbpn[name],
            "recall_at_8_vs_exact": recalls[name],
            "us_per_call": t * 1e6, "gnnz_per_s": nnz / t / 1e9,
        })
        if verbose:
            print(f"mixed_prec fmt={name:5s} "
                  f"{vbpn[name]:5.3f} value B/nnz  "
                  f"recall@8 {recalls[name]:.4f}  {t*1e3:8.2f} ms")
    mixed_precision = {
        "recall_target": recall_target,
        "format_histogram": fmt_plan.histogram,
        "formats": list(fmt_plan.formats),
        "predicted_recall": fmt_plan.predicted_recall,
        "measured_recall_at_8": recalls,
        "value_bytes_per_nnz": vbpn,
        "value_bytes_ratio_vs_bf16": value_bytes_ratio_bf16,
        "value_bytes_ratio_vs_f32": vbpn["F32"] / vbpn["mixed"],
        "heterogeneous_parity_by_inner_loop": parity,
    }
    if verbose:
        print(f"mixed_prec assignment {fmt_plan.histogram} -> "
              f"{value_bytes_ratio_bf16:.2f}x fewer value bytes than BF16 "
              f"at recall@8 {recalls['mixed']:.4f} "
              f"(BF16 {recalls['BF16']:.4f}, target {recall_target})")
    if smoke:
        # CI tripwires: heterogeneous decode must stay bit-exact against the
        # f32 twins, beat uniform F32 on value bytes, and hold the target.
        assert all(parity.values()), f"heterogeneous parity broke: {parity}"
        assert vbpn["mixed"] < vbpn["F32"], vbpn
        assert recalls["mixed"] >= recall_target, recalls

    by = {
        (r["sweep"], r["fmt"], r["inner_loop"], r["layout"],
         r.get("gather_mode"), r["q"]): r
        for r in results
    }

    def us(sweep, fmt, loop, layout, gather=None, q=1):
        return by[(sweep, fmt, loop, layout, gather, q)]["us_per_call"]

    speedup_inner = (us("inner_loop", "F32", "legacy", "split")
                     / us("inner_loop", "F32", "linear", "split"))
    qmax = qs[-1]
    speedup_batch = by[("batching", "F32", "linear", "fused", None, qmax)][
        "speedup_vs_sequential"]
    # Headline layout comparison at the deployment format (configs/topk_spmv
    # and the serving head ship BF16); the full per-format table is in
    # fused_vs_split_by_format.  On CPU interpret the fused decode has no
    # HBM burst to win back, so narrow-int formats hover just under 1.0
    # there — the layout's target is the TPU DMA path (ROADMAP).
    speedup_fused = fused_ratio.get("BF16", float("nan"))
    payload = {
        "bench": "bench_kernel_paths",
        "backend": jax.default_backend(),
        "interpret": interpret,
        "matrix": {"n_rows": n_rows, "n_cols": n_cols, "nnz": nnz,
                   "distribution": "gamma"},
        "design_point": {"block_size": block, "packets_per_step": T_STEP,
                         "cores": cores, "k": K, "big_k": BIG_K},
        "results": results,
        "auto_gather_mode": auto_mode,
        "speedup_linear_vs_legacy_f32": speedup_inner,
        "fused_vs_split_by_format": fused_ratio,
        "speedup_fused_vs_split_bf16": speedup_fused,
        f"speedup_batched_q{qmax}_vs_sequential": speedup_batch,
        "mixed_precision": mixed_precision,
    }
    if not smoke:  # CI smoke must not clobber the tracked repo-root numbers
        merge_into_bench_json(payload)
    if verbose:
        ratios = " ".join(f"{f}={r:.2f}x" for f, r in fused_ratio.items())
        print(f"linear vs legacy (F32, split): {speedup_inner:.1f}x   "
              f"fused vs split: {ratios}   "
              f"batched Q={qmax} vs sequential: {speedup_batch:.1f}x")
        if not smoke:
            print(f"wrote {BENCH_JSON}")
    return {
        "name": "bench_kernel_paths",
        "us_per_call": us("inner_loop", "F32", "linear", "fused"),
        "derived": (f"linear_vs_legacy={speedup_inner:.1f}x "
                    f"fused_vs_split_bf16={speedup_fused:.2f}x "
                    f"batchQ{qmax}_vs_seq={speedup_batch:.1f}x"),
    }


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, all inner loops + layouts, no json write")
    args = ap.parse_args()
    run(smoke=args.smoke)

"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus verbose detail per benchmark).
``--smoke`` runs the CI perf-path smoke instead: tiny shapes through the
kernel-path sweep (all inner loops, both stream layouts), the
serve-while-ingest churn axis (both signature modes with retrace
counting), the 8-simulated-device sharded serving plane (bit-identity +
transfer-guard/retrace assertions), and the iterative graph workloads
(accumulate-mode PPR/eigen: parity, zero-transfer/zero-retrace loops,
bit-identical incremental re-solves) — no json writes.
"""
from __future__ import annotations

import pathlib
import sys

# Script-style invocation (CI: `python benchmarks/run.py --smoke`) puts
# benchmarks/ itself at sys.path[0]; the package imports need the repo root.
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main(smoke: bool = False) -> None:
    from benchmarks import (
        bench_graph_workloads,
        bench_kernel_paths,
        bench_recovery,
        bench_sharded_serving,
        bench_streaming_updates,
        fig5_throughput,
        fig6_roofline,
        fig7_accuracy,
        kernel_validation,
        table1_precision,
        table2_designs,
    )

    if smoke:
        mods = [bench_kernel_paths, bench_streaming_updates,
                bench_sharded_serving, bench_recovery, bench_graph_workloads]
        kwargs, banner = {"smoke": True}, " [smoke]"
    else:
        mods = [table1_precision, table2_designs, fig5_throughput,
                fig6_roofline, fig7_accuracy, kernel_validation,
                bench_kernel_paths, bench_streaming_updates,
                bench_sharded_serving, bench_recovery, bench_graph_workloads]
        kwargs, banner = {}, ""
    rows = []
    for mod in mods:
        print(f"\n=== {mod.__name__.split('.')[-1]}{banner} ===")
        rows.append(mod.run(verbose=True, **kwargs))
    print("\nname,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")


if __name__ == '__main__':
    main(smoke="--smoke" in sys.argv[1:])

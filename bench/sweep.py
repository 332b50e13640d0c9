#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains, in one process and one set-up.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 2,4,8

Builds and warms the cell once, then offers its traffic at each rate in turn
for ``--seconds`` and prints one JSON line per rate: the reads answered per
second, the 95th percentile of read latency from due time, how long the
answers ran past the window (a backlog that grows shows here), and, for a mix
with updates, the 90th percentile of update visibility.  A rate is sustained
when the reads answered per second reach 95% of the reads offered and the
answers end within one pass of the window.  The cell's traffic file then
takes 4/5 of the highest sustained rate as a number.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated total rates per second")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    cell = run.find_cell(run.ROOT, args.workload)
    if cell.traffic["loop"] != "open":
        run.log("sweep: only an open-loop cell has a rate to sweep")
        return 2

    import jax
    import numpy as np

    from bench import gen, loops

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(run.ROOT / ".jax_cache"))
    if jax.devices()[0].platform != "tpu":
        run.log("sweep: needs a TPU")
        return 3
    coll = gen.make_collection(cell.cfg, args.seed)
    index, svc = run.build_service(cell.cfg, cell.traffic, coll)
    first = loops.OpenLoop(svc, index, cell.cfg, cell.traffic, args.seed)
    first.warm()
    run.log(f"set-up {time.perf_counter() - run.T_START:.3f} s")
    read_share = 1.0 - cell.traffic.get("update_share", 0.0)
    for rate in (float(r) for r in args.rates.split(",")):
        loop = loops.OpenLoop(svc, index, cell.cfg, cell.traffic, args.seed)
        loop.warm_acks = first.warm_acks
        window = loop.run(args.seconds, rate)
        lat = loop.read_latencies_ms()
        passes = loop.passes()
        last = max((r.done for r in loop.requests if r.done), default=window["start"])
        row = {
            "rate_per_s": rate,
            "reads_offered_per_s": rate * read_share,
            "qps": window["qps"],
            "p50_ms": float(np.percentile(lat, 50)) if lat.size else None,
            "p95_ms": float(np.percentile(lat, 95)) if lat.size else None,
            "drain_s": last - window["start"] - args.seconds,
            "passes": len(passes),
            "batch_q_mean": sum(passes) / len(passes) if passes else None,
            "lateness_s": window["lateness_s"],
        }
        if cell.traffic.get("update_share", 0.0) > 0:
            seen, missed = loop.visibility()
            row["updates"] = len(loop.acks)
            row["visible_p90_ms"] = float(np.percentile(seen, 90)) if seen.size else None
            row["ingest_ms_median"] = (float(np.median([a.ingest_s for a in loop.acks])) * 1e3
                                      if loop.acks else None)
            row["probes_missed"] = missed
        print(json.dumps(row), flush=True)
    svc.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

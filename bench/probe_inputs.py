#!/usr/bin/env python3
"""Time the generator and the reference at a configuration's full size on the chip.

    python3 bench/probe_inputs.py --config bench/tests/splade_shape.json --seed <n> \
        [--queries 256 1024]

Makes the configuration's collection from ``--seed`` (``gen.make_collection``),
times a block of 64 queries under its query law (as a closed-batch window
draws them, between its searches), then for each count in ``--queries``
runs the reference's per-partition top-k over that many queries
(``Reference.base_topk``), as a run's check does over the answers it
compares.  Prints one JSON line: the wall seconds of each step, the columns
the queries set (the reference's dense width), the device's peak bytes in
use, and the host's peak resident set.  These size a cell before it is
added: how much ``setup_s`` the generator takes, what the query draw costs
in the window, and how many answers the check can compare (a traffic
file's ``check_answers``).  Without a TPU it exits non-zero and prints
nothing.
"""
from __future__ import annotations

import argparse
import json
import resource
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def host_peak_bytes() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024   # Linux: KiB


def probe(cfg: dict, seed: int, n_queries: list) -> dict:
    import jax
    import numpy as np

    from bench import gen, reference

    dev = jax.devices()[0]

    def peak() -> int:
        return int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))

    out = {"device": dev.device_kind, "n_rows": cfg["n_rows"], "n_cols": cfg["n_cols"]}
    t = time.perf_counter()
    coll = gen.make_collection(cfg, seed)
    out["gen_s"] = time.perf_counter() - t
    lens = np.diff(coll.indptr)
    out.update(nnz=coll.nnz, len_mean=float(lens.mean()), len_max=int(lens.max()),
               gen_device_peak_bytes=peak(), gen_host_peak_bytes=host_peak_bytes())
    rng = gen.rng_for(seed, "queries")
    gen.queries(cfg, rng, 64)
    block_s = []
    for _ in range(20):
        t = time.perf_counter()
        gen.queries(cfg, rng, 64)
        block_s.append(time.perf_counter() - t)
    out["query_block64_ms"] = {"median": float(np.median(block_s)) * 1e3,
                               "max": max(block_s) * 1e3}
    t = time.perf_counter()
    ref = reference.Reference(coll.indptr, coll.indices, coll.data, coll.n_cols, cfg)
    out["reference_init_s"] = time.perf_counter() - t
    xs = gen.queries(cfg, gen.rng_for(seed, "check"), max(n_queries))
    out["base_topk"] = []
    for n in n_queries:
        t = time.perf_counter()
        vals, _ = ref.base_topk(xs[:n])
        out["base_topk"].append({
            "queries": n, "s": time.perf_counter() - t,
            "query_nnz_mean": float((xs[:n] != 0).sum(axis=1).mean()),
            "columns_used": int(np.any(xs[:n] != 0, axis=0).sum()),
            "answers_finite": bool(np.isfinite(vals).all())})
    out.update(device_peak_bytes=peak(), host_peak_bytes=host_peak_bytes())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--queries", type=int, nargs="+", default=[256])
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("probe_inputs: needs a TPU", file=sys.stderr)
        return 3
    with open(args.config) as f:
        cfg = json.load(f)
    print(json.dumps(dict(probe(cfg, args.seed, args.queries), seed=args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

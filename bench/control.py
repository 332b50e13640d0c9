#!/usr/bin/env python3
"""Run a cell on several seeds in one process and print what each compares.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10 [--value-format Q7]

Each seed is a whole run of the cell (collection, build, warm-up, window and
comparison with the reference) and prints one JSON line with its checks and
end-to-end metrics.  With ``--value-format`` the program serves the
collection in that format while the reference keeps the configured one: the
control, which the limits in ``limits.json`` must fail (the program's own
Q7 path is the nearest precision below BF16).  Without it, the lines are
sound readings, from which the limits' lower ends are read.  The benchmark's
own runs never run this.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--value-format", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    cell = run.find_cell(run.ROOT, args.workload)

    import jax

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(run.ROOT / ".jax_cache"))
    for seed in (int(s) for s in args.seeds.split(",")):
        run.T_START = time.perf_counter()
        try:
            res = run.run_cell(cell, seed, args.seconds, False, value_format=args.value_format)
        except run.NoChip as e:
            run.log(f"control: {e}")
            return 3
        print(json.dumps({"seed": seed, "value_format": args.value_format,
                          "correct": res["correct"], "metrics": res["metrics"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

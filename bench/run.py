#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration file and its traffic file are found by name
from ``BENCHMARK.json``; the per-layer metrics of a ``--trace 1`` run are read
by the files ``bench/metrics/<metric>.py``.  A run makes the collection and
the traffic from ``--seed``, builds the index through the program's entry
(``SparseEmbeddingIndex`` under ``StreamingSimilarityService``), warms the
shapes its traffic uses, measures for ``--seconds``, then checks every answer
of the window (or the traffic's ``check_answers`` of them, drawn from the
seed) against the plain reference (``reference.py``) and prints, as the last
line of standard output, one JSON object.  The numbers compared and their
limits (``limits.json``, or the configuration's ``limits``) close standard
error and the result line.

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.  JAX's compilation cache lives in
``$JAX_COMPILATION_CACHE_DIR`` when that is set, else in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
OUT = ROOT / ".bench_out"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    end_to_end: list     # metric entries this cell reports
    per_layer: list


def find_cell(root: Path, name: str) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg = load_json(root / conf["file"])
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")

    def mine(m):
        return name in m.get("workloads", [name])

    return Cell(name, w["chips"], cfg, traffic,
                [m for m in spec["end_to_end"] if mine(m)],
                [m for m in spec["per_layer"] if mine(m)])


def limits_for(cfg: dict) -> dict:
    """The limits of the numbers compared: ``limits.json``, but where the
    configuration's own ``limits`` set a number's limit."""
    return dict(load_json(HERE / "limits.json"), **cfg.get("limits", {}))


def load_reader(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def program_config(cfg: dict, value_format: str | None = None):
    from repro.core.topk_spmv import TopKSpMVConfig

    return TopKSpMVConfig(
        big_k=cfg["big_k"], k=cfg["k"], num_partitions=cfg["partitions"],
        block_size=cfg["block_size"], value_format=value_format or cfg["value_format"],
        stream_layout=cfg["stream_layout"],
    )


def build_service(cfg: dict, traffic: dict, coll, value_format: str | None = None):
    from repro.core import bscsr
    from repro.core.similarity import SparseEmbeddingIndex
    from repro.serve.frontend import FrontendConfig
    from repro.serve.streaming import CompactionPolicy, StreamingSimilarityService

    csr = bscsr.CSRMatrix(indptr=coll.indptr, indices=coll.indices, data=coll.data,
                          shape=(coll.n_rows, coll.n_cols))
    index = SparseEmbeddingIndex(csr, program_config(cfg, value_format),
                                 nnz_per_row=cfg.get("upsert_nnz_per_row", 32))
    frontend = FrontendConfig(**cfg["frontend"]) if traffic["loop"] == "open" else None
    svc = StreamingSimilarityService(index, policy=CompactionPolicy(**cfg.get("compaction", {})),
                                     frontend=frontend)
    return index, svc


@dataclasses.dataclass
class RunContext:
    """What a per-layer reader may read."""
    cfg: dict
    loop: object
    trace: object          # trace_reduce.TraceSummary
    device_kind: str
    live_nnz: int


def live_nnz(coll, acks, m: int) -> int:
    """nnz of the live rows after the acknowledged replaces (``m`` per new row)."""
    lens = {}
    for a in acks:
        lens.setdefault(a.gid, int(coll.indptr[a.gid + 1] - coll.indptr[a.gid]))
    return coll.nnz - sum(lens.values()) + m * len(lens)


def judge(cfg: dict, traffic: dict, seed: int, coll, loop, info: dict) -> dict:
    """Compare the window's answers with the reference; the checked numbers.

    Every answer is compared, or, where the traffic file sets
    ``check_answers``, that many of them drawn from the seed: the reference's
    time grows with the answers and with the columns their queries set."""
    import bisect

    import numpy as np

    from bench import gen, reference

    ref = reference.Reference(coll.indptr, coll.indices, coll.data, coll.n_cols, cfg)
    acks = loop.warm_acks + loop.acks
    for a in acks:
        ref.apply(a.gid, a.x, cfg.get("upsert_nnz_per_row", 32))
    acked = [a.acked for a in acks]
    reqs = loop.requests
    answered = [r for r in reqs if r.done and not r.error and r.rows is not None]
    checked = answered
    n_check = traffic.get("check_answers")
    if n_check is not None and n_check < len(answered):
        pick = gen.rng_for(seed, "check").choice(len(answered), n_check, replace=False)
        checked = [answered[i] for i in np.sort(pick)]
    verdict = reference.Verdict()
    if checked:
        xs = np.stack([r.x for r in checked])
        base_vals, base_rows = ref.base_topk(xs)
        upd_scores = ref.update_scores(xs)
        for i, r in enumerate(checked):
            lo = bisect.bisect_right(acked, r.sent)
            hi = bisect.bisect_right(acked, r.done)
            reference.judge_one(ref, r.x, r.vals, r.rows, base_vals[:, i], base_rows[:, i],
                                upd_scores[i], lo, hi, verdict)
    log(f"compared {verdict.compared} of {len(answered)} answers with the reference")
    checks = {
        "score_gap": verdict.score_gap,
        "row_score_gap": verdict.row_score_gap,
        "malformed": verdict.malformed,
        "unanswered": len(reqs) - len(answered),
        "compactions": info["service"]["compactions"],
    }
    if hasattr(loop, "visibility"):
        checks["probes_missed"] = loop.visibility()[1]
    return checks


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, value_format: str | None = None,
             out_dir: Path = OUT) -> dict:
    """Set up, measure and check one cell; returns the result object."""
    import jax
    import numpy as np

    from bench import gen, loops
    from bench import trace_reduce

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(f"the cell needs {cell.chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": cell.chips}
    cfg, traffic = cell.cfg, cell.traffic

    t = time.perf_counter()
    coll = gen.make_collection(cfg, seed)
    log(f"collection: {coll.n_rows} rows x {coll.n_cols} cols, {coll.nnz} nnz, seed {seed}, "
        f"made in {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    index, svc = build_service(cfg, traffic, coll, value_format)
    st = index.stats()
    log(f"index built in {time.perf_counter() - t:.3f} s: {st.num_partitions} partitions, "
        f"{st.stream_bytes} stream bytes, {st.bytes_per_nnz:.4f} B/nnz ({st.stream_layout})")
    loop = loops.LOOPS[traffic["loop"]](svc, index, cfg, traffic, seed)
    loop.warm()
    info0 = svc.dispatch_info()
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s (builds {info0['fn_builds']}, retraces {info0['retraces']})")

    trace_dir = out_dir / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            window = loop.run(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    info = svc.dispatch_info()
    stats = devices[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    builds = info["fn_builds"] - info0["fn_builds"]
    retraces = info["retraces"] - info0["retraces"]
    log(f"window: {window}; builds {builds}, retraces {retraces} inside it; "
        f"generator at most {window['lateness_s'] * 1e3:.3f} ms late")
    svc.close()
    loop.svc = loop.index = None
    del svc, index
    gc.collect()

    metrics = {}
    for m in cell.end_to_end:
        if m["name"] == "setup_s":
            value = setup_s
        elif m["name"] == "qps":
            value = window["qps"]
        elif m["name"] == "p95_ms":
            value = float(np.percentile(loop.read_latencies_ms(), 95))
        elif m["name"] == "visible_p90_ms":
            seen, _ = loop.visibility()
            log(f"updates shown by their probe: {seen.size} of {len(loop.acks)}")
            if not seen.size:
                continue
            value = float(np.percentile(seen, 90))
        else:
            raise KeyError(f"no measure for end-to-end metric {m['name']!r}")
        if not trace:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    t = time.perf_counter()
    checks = judge(cfg, traffic, seed, coll, loop, info)
    log(f"reference comparison took {time.perf_counter() - t:.3f} s")
    limits = limits_for(cfg)
    correct = all(checks[k] <= limits[k] for k in checks)

    result = {"correct": correct}
    reqs = loop.requests
    result["attempted"] = len(reqs) + len(loop.acks)
    result["failed"] = sum(1 for r in reqs if not r.done or r.error)
    if trace:
        summary = trace_reduce.load(trace_dir)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ctx = RunContext(cfg, loop, summary, device["kind"],
                         live_nnz(coll, loop.warm_acks + loop.acks,
                                  cfg.get("upsert_nnz_per_row", 32)))
        for m in cell.per_layer:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = summary.breakdown()
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": limits[k]} for k, v in checks.items()}
    for k, v in checks.items():
        log(f"check {k} = {v!r} (limit {limits[k]!r})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        log(f"run: the program is missing: no package under {ROOT / 'src'}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    cell = find_cell(ROOT, args.workload)

    import jax

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"run: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

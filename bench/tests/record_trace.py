#!/usr/bin/env python3
"""Record the small trace that ``test_bench_trace.py`` reduces (run on a TPU).

    python3 bench/tests/record_trace.py <out.xplane.pb>

Builds a 200K-row index, then inside a ``bench.window`` span makes three
``search`` calls of 8 queries, each in a ``bench.search`` span, with a
``bench.pause`` span of 50 ms between the second and the third, and copies
the profiler's ``.xplane.pb`` to the given path.  Python tracing is off, so
the file holds device ops and the benchmark's spans.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent.parent))
sys.path.insert(0, str(HERE.parent.parent / "src"))


def main() -> int:
    import jax

    from bench import gen, run

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 3
    cfg = dict(run.load_json(HERE.parent / "configs" / "paper-10m-bf16.json"), n_rows=200_000)
    coll = gen.make_collection(cfg, 1)
    _, svc = run.build_service(cfg, {"loop": "closed_batch"}, coll)
    xs = gen.queries(cfg, gen.rng_for(1, "queries"), 8)
    svc.search(xs)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    scratch = HERE.parent.parent / ".bench_out"
    scratch.mkdir(exist_ok=True)
    with tempfile.TemporaryDirectory(dir=scratch) as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(3):
                if i == 2:
                    with jax.profiler.TraceAnnotation("bench.pause"):
                        time.sleep(0.05)
                with jax.profiler.TraceAnnotation("bench.search"):
                    svc.search(xs)
        jax.profiler.stop_trace()
        src = sorted(Path(tmp).rglob("*.xplane.pb"))[-1]
        shutil.copyfile(src, sys.argv[1])
    print(Path(sys.argv[1]).stat().st_size, "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())

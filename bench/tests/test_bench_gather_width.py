"""The ``gather_width_cols.sparse`` reader, on a trace recorded on the CPU.

A tiny learned-sparse index (2,500 columns: three gather chunks) serves
three query blocks inside a ``bench.window`` span while the profiler
records; the blocks set 300, 1,100 and 1,200 columns, so the program's
``executor.columns`` spans carry widths 1,024, 2,048 and 2,048 and the
reader reads their median.  On ``small_spans.xplane.pb``, recorded on a v5e before
the program had the span, it reads nothing.
"""
import sys
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import run, spans  # noqa: E402

HERE = Path(__file__).resolve().parent
N_COLS = 2500
READER = "gather_width_cols.sparse"


def _block(rng, q: int, n_set: int) -> np.ndarray:
    """``q`` queries that together set exactly ``n_set`` columns."""
    xs = np.zeros((q, N_COLS), np.float32)
    cols = rng.choice(N_COLS, n_set, replace=False)
    xs[np.arange(n_set) % q, cols] = rng.random(n_set).astype(np.float32) + 0.5
    return xs


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax
    from jax.profiler import ProfileData

    import repro.core as core
    from repro.core.topk_spmv import TopKSpMVConfig

    rng = np.random.default_rng(5)
    dense = np.abs(rng.standard_normal((200, N_COLS))).astype(np.float32)
    # a big_k no other test uses: the interned executor starts afresh
    cfg = TopKSpMVConfig(big_k=13, k=4, num_partitions=2, block_size=32)
    index = core.SparseEmbeddingIndex.from_dense(dense, nnz_per_row=16, config=cfg)
    blocks = [_block(rng, 4, n) for n in (300, 1100, 1200)]
    index.query_batch(blocks[0])                 # warm: build and compile
    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for xs in blocks:
                index.query_batch(xs)
    finally:
        jax.profiler.stop_trace()
    path = sorted(Path(out).rglob("*.xplane.pb"))[-1]
    return ProfileData.from_file(str(path)), index.dispatch_info()


def _ctx(profile):
    return types.SimpleNamespace(spans=spans.summarize(profile))


def test_reads_the_median_width_of_the_window(recorded):
    profile, info = recorded
    got = spans.summarize(profile).in_window("executor.columns")
    assert [(s[2]["q"], s[2]["cols"], s[2]["width"]) for s in got] == [
        (4, 300, 1024), (4, 1100, 2048), (4, 1200, 2048)]
    assert run.load_reader(READER)(_ctx(profile)) == 2048.0
    assert info["gather_widths"] == {1024: 2, 2048: 2}    # the warm-up's too
    assert info["retraces"] == 0


def test_reads_nothing_without_the_span():
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(str(HERE / "small_spans.xplane.pb"))
    assert spans.summarize(profile).in_window("executor.dispatch")
    assert run.load_reader(READER)(_ctx(profile)) is None

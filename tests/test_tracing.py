"""Program spans: recorded inside an open profiler session, nested, with ids.

One session covers a tiny index's build, two ``search`` calls around an
``ingest``, and one frontend ``submit``; the trace is read back with
``jax.profiler.ProfileData``.  Outside a session a span records nothing.
"""
import pathlib

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.core as core
from repro.core.topk_spmv import TopKSpMVConfig
from repro.serve import FrontendConfig, StreamingSimilarityService
from repro.utils.tracing import PREFIX, span

N_COLS = 64


@pytest.fixture(scope="module")
def events(tmp_path_factory):
    """[(line index, name, start_ns, end_ns, stats)] of every ``repro.`` span,
    in start order."""
    rng = np.random.default_rng(0)
    dense = rng.standard_normal((300, N_COLS)).astype(np.float32)
    # a big_k no other test uses: the interned executor builds afresh
    cfg = TopKSpMVConfig(big_k=11, k=8, num_partitions=2, block_size=32)
    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        index = core.SparseEmbeddingIndex.from_dense(
            dense, nnz_per_row=8, config=cfg)
        svc = StreamingSimilarityService(
            index, frontend=FrontendConfig(flush_deadline_s=0.01, max_batch=4))
        svc.search(dense[:3])
        svc.ingest(dense[5:6], ids=[7])
        svc.search(dense[:3])
        svc.submit(dense[9]).result(timeout=120)
        svc.close()
    finally:
        jax.profiler.stop_trace()
    profile = ProfileData.from_file(
        str(sorted(pathlib.Path(out).rglob("*.xplane.pb"))[-1]))
    got = []
    lines = [line for p in profile.planes if p.name.startswith("/host:")
             for line in p.lines]
    for i, line in enumerate(lines):
        got += [(i, e.name[len(PREFIX):], e.start_ns, e.end_ns, dict(e.stats))
                for e in line.events if e.name.startswith(PREFIX)]
    # in start order: the profiler may list the frontend thread's line first
    return sorted(got, key=lambda e: e[2])


def _named(events, name):
    return [e for e in events if e[1] == name]


def _parent(events, child):
    """The innermost span on the child's thread that encloses it."""
    outer = [e for e in events if e is not child and e[0] == child[0]
             and e[2] <= child[2] and child[3] <= e[3]]
    return min(outer, key=lambda e: e[3] - e[2])[1] if outer else None


def test_build_stages_nest_under_index_build(events):
    (build,) = _named(events, "index.build")
    assert build[4] == {"kind": "init", "rows": 300, "nnz": 2400}
    encodes = _named(events, "index.encode")
    assert sorted(e[4]["partition"] for e in encodes) == [0, 1]
    for name in ("index.partition", "index.encode", "index.row_maps"):
        assert {_parent(events, e) for e in _named(events, name)} == {"index.build"}
    first_refresh = _named(events, "index.refresh")[0]
    assert _parent(events, first_refresh) == "index.build"
    assert first_refresh[4]["version"] == 0
    assert first_refresh[4]["partitions_copied"] == 2


def test_search_spans_nest_and_carry_ids(events):
    searches = _named(events, "service.search")
    assert len(searches) == 2 and all(s[4] == {"q": 3} for s in searches)
    for name in ("index.upload", "executor.dispatch", "index.fetch"):
        inside = [e for e in _named(events, name) if _parent(events, e) == "service.search"]
        assert len(inside) == 2, name
    for d in _named(events, "executor.dispatch"):
        assert d[4]["bucket"] >= d[4]["q"]
    # the first search pins the snapshot, builds and compiles the Q=4 bucket
    for name in ("executor.pin", "executor.build", "executor.compile"):
        assert {_parent(events, e) for e in _named(events, name)} == {"executor.dispatch"}
    pins = _named(events, "executor.pin")
    assert len(pins) == 2 and all(p[4]["bytes"] > 0 for p in pins)
    builds = _named(events, "executor.build")
    assert builds[0][4] == {"path": "kernel", "q": 4, "retrace": 0}
    compiles = _named(events, "executor.compile")
    assert len(compiles) == len(builds)          # one first call per built fn


def test_each_pass_finds_its_set_columns(events):
    # two searches of 3 dense queries and one frontend pass of 1, each
    # setting all 64 columns: one gather chunk, 64 wide
    cols = _named(events, "executor.columns")
    assert [(_parent(events, c), c[4]["q"]) for c in cols] == [
        ("service.search", 3), ("service.search", 3), ("frontend.flush", 1)]
    assert all(c[4]["cols"] == N_COLS and c[4]["width"] == N_COLS for c in cols)


def test_ingest_wraps_its_refresh(events):
    (ingest,) = _named(events, "service.ingest")
    assert ingest[4] == {"rows": 1}
    refreshes = [e for e in _named(events, "index.refresh")
                 if _parent(events, e) == "service.ingest"]
    assert len(refreshes) == 1 and refreshes[0][4]["version"] == 1


def test_frontend_flush_carries_its_pass(events):
    (flush,) = _named(events, "frontend.flush")
    ids = flush[4]
    assert ids["pass"] == 1 and ids["q"] == 1
    assert ids["reason"] in ("target", "deadline", "capacity", "drain")
    assert 0 <= ids["wait_max_us"] <= ids["wait_sum_us"]
    dispatch = [e for e in _named(events, "executor.dispatch")
                if _parent(events, e) == "frontend.flush"]
    assert len(dispatch) == 1 and dispatch[0][4]["q"] == 1


def test_spans_record_only_inside_a_session():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with span("test.idle", q=1) as s:
        s.set_metadata(bytes=3)
        value = 7
    assert value == 7

"""Program-span reduction and the readers that use it.

Checked on hand-made traces (XSpace text protos, times in ns, each span's
ids as event stats), on ``small.xplane.pb`` (recorded before the program had
spans: everything reads empty, as on a parent without them) and on
``small_spans.xplane.pb``, recorded on a TPU v5e by the same
``record_trace.py`` once the program had its spans: there the spans and the
device's operations share one clock, up to an offset of about a millisecond.
"""
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run, spans, trace_reduce  # noqa: E402

HERE = Path(__file__).resolve().parent
KERNEL = "bscsr_topk_spmv_multiquery"


def _xspace(device_ops, host_lines):
    """``device_ops``: [(name, start_ns, dur_ns)]; ``host_lines``: one list per
    thread of (name, start_ns, dur_ns, {id: value})."""
    names, stat_names, planes = {}, {}, []

    def mid(table, name):
        return table.setdefault(name, len(table) + 1)

    def stat(k, v):
        value = f'str_value: "{v}"' if isinstance(v, str) else f"int64_value: {v}"
        return f"stats {{ metadata_id: {mid(stat_names, k)} {value} }}"

    def line(i, lname, events):
        evs = " ".join(
            f"events {{ metadata_id: {mid(names, n)} offset_ps: {s * 1000} "
            f"duration_ps: {d * 1000} {' '.join(stat(k, v) for k, v in ids.items())} }}"
            for n, s, d, ids in events)
        return f'lines {{ id: {i} name: "{lname}" timestamp_ns: 0 {evs} }}'

    def metadata():
        ev = " ".join(f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}'
                      for n, k in names.items())
        st = " ".join(f'stat_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}'
                      for n, k in stat_names.items())
        return ev + " " + st

    dev = line(1, "XLA Ops", [(n, s, d, {}) for n, s, d in device_ops])
    planes.append(f'planes {{ id: 1 name: "/device:TPU:0" {dev} {metadata()} }}')
    names.clear()
    stat_names.clear()
    host = " ".join(line(i + 1, f"thread{i}", evs) for i, evs in enumerate(host_lines))
    planes.append(f'planes {{ id: 2 name: "/host:CPU" {host} {metadata()} }}')
    return "\n".join(planes)


def _profile(device_ops, host_lines):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(_xspace(device_ops, host_lines))


OP = f"%{KERNEL}.1 = (f32[8]) custom-call()"

# set-up: a build at 0..100 with its stages; the window 200..1200 holds two
# passes, each upload -> dispatch -> kernel -> fetch, one refresh and one pin;
# the scheduler thread flushes a pass of 3 queries inside the window.
MAIN = [
    ("repro.index.build", 0, 100, {"kind": "init", "rows": 10}),
    ("repro.index.encode", 10, 40, {"partition": 0}),
    ("repro.index.encode", 50, 20, {"partition": 1}),
    ("repro.index.refresh", 80, 15, {"version": 0, "partitions_copied": 2}),
    ("bench.window", 200, 1000, {}),
    ("repro.service.search", 250, 300, {"q": 8}),
    ("repro.index.upload", 250, 20, {"q": 8}),
    ("repro.executor.dispatch", 270, 40, {"q": 8, "bucket": 8}),
    ("repro.executor.pin", 275, 20, {"bytes": 3_000_000}),
    ("repro.index.fetch", 310, 240, {"q": 8}),
    ("repro.service.ingest", 600, 100, {"rows": 1}),
    ("repro.index.refresh", 610, 80, {"version": 1, "partitions_copied": 1}),
    ("repro.service.search", 800, 300, {"q": 8}),
    ("repro.index.upload", 800, 10, {"q": 8}),
    ("repro.executor.dispatch", 810, 60, {"q": 8, "bucket": 8}),
    ("repro.index.fetch", 870, 230, {"q": 8}),
]
SCHED = [("repro.frontend.flush", 900, 100,
          {"pass": 4, "q": 3, "reason": "target", "wait_sum_us": 3000, "wait_max_us": 1500})]
DEVICE = [(OP, 310, 230), (OP, 870, 220)]


@pytest.fixture()
def made():
    return _profile(DEVICE, [MAIN, SCHED])


def test_spans_are_read_whole_with_their_ids(made):
    got = spans.summarize(made)
    assert got.window == (200, 1200)
    assert [s[:2] for s in got.spans["repro.index.encode"]] == [(10, 50), (50, 70)]
    assert got.spans["repro.index.build"][0][2] == {"kind": "init", "rows": 10}
    flush = got.spans["repro.frontend.flush"][0][2]
    assert flush["reason"] == "target" and flush["pass"] == 4
    assert "bench.window" not in got.spans
    assert [s[0] for s in got.in_window("executor.dispatch")] == [270, 810]
    assert [s[0] for s in got.before_window("index.refresh")] == [80]
    assert got.children(got.spans["repro.index.build"][0]) == pytest.approx(
        {"repro.index.encode": 60e-9, "repro.index.refresh": 15e-9})


def test_program_gaps_name_the_innermost_span_and_sum_to_the_idle(made):
    got = spans.summarize(made)
    # idle: 200..310 (mid 255: upload), 540..870 (mid 705: outside),
    # 1090..1200 (mid 1145: outside)
    assert got.program_gap_seconds == pytest.approx(
        {"repro.index.upload": 110e-9, spans.OUTSIDE: 440e-9})
    s = trace_reduce.summarize(made)
    idle = s.window_s - s.busy_s
    assert sum(got.program_gap_seconds.values()) == pytest.approx(idle)
    assert sum(s.gap_seconds.values()) == pytest.approx(idle)


def _ctx(profile, acks=0):
    loop = types.SimpleNamespace(acks=[None] * acks)
    return types.SimpleNamespace(spans=spans.summarize(profile), loop=loop,
                                 trace=trace_reduce.summarize(profile))


def test_readers_on_a_made_trace(made):
    ctx = _ctx(made, acks=2)
    assert run.load_reader("executor_dispatch_ms.batch")(ctx) == pytest.approx(50e-6)
    assert run.load_reader("index_build_s")(ctx) == pytest.approx(100e-9)
    assert run.load_reader("refresh_ms")(ctx) == pytest.approx(80e-6)
    assert run.load_reader("pin_mb_per_update")(ctx) == pytest.approx(1.5)
    assert run.load_reader("queue_wait_ms.served")(ctx) == pytest.approx(1.0)


def test_readers_read_nothing_without_program_spans():
    bare = _profile(DEVICE, [[("bench.window", 200, 1000, {})]])
    ctx = _ctx(bare, acks=2)
    for name in ("executor_dispatch_ms.batch", "index_build_s", "refresh_ms",
                 "pin_mb_per_update", "queue_wait_ms.served"):
        assert run.load_reader(name)(ctx) is None, name
    assert spans.summarize(bare).program_gap_seconds == pytest.approx(
        {spans.OUTSIDE: 1000e-9 - 450e-9})


def test_for_run_takes_only_the_trace_of_its_own_window(tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    trace_dir = tmp_path / "trace" / "cell" / "plugins" / "profile" / "1"
    trace_dir.mkdir(parents=True)
    (trace_dir / "host.xplane.pb").write_bytes((HERE / "small.xplane.pb").read_bytes())
    monkeypatch.setattr(spans, "TRACES", tmp_path / "trace")
    s = trace_reduce.summarize(ProfileData.from_file(str(HERE / "small.xplane.pb")))
    got = spans.for_run(types.SimpleNamespace(trace=s))
    assert got is not None and got.window is not None
    other = types.SimpleNamespace(trace=types.SimpleNamespace(window_s=s.window_s + 1e-9))
    assert spans.for_run(other) is None


@pytest.fixture(scope="module", params=["small.xplane.pb", "small_spans.xplane.pb"])
def recorded(request):
    from jax.profiler import ProfileData

    return request.param, ProfileData.from_file(str(HERE / request.param))


def test_recorded_gaps_sum_to_the_idle_the_existing_reduction_reads(recorded):
    name, profile = recorded
    s = trace_reduce.summarize(profile)
    got = spans.summarize(profile)
    idle = s.window_s - s.busy_s
    assert sum(got.program_gap_seconds.values()) == pytest.approx(idle, rel=1e-9)
    assert sum(s.gap_seconds.values()) == pytest.approx(idle, rel=1e-9)
    if name == "small.xplane.pb":       # recorded before the program had spans
        assert got.spans == {} and set(got.program_gap_seconds) == {spans.OUTSIDE}
    else:
        assert s.gap_seconds.get("bench.pause", 0.0) >= 0.045
        assert got.program_gap_seconds.get(spans.OUTSIDE, 0.0) >= 0.045


def test_recorded_spans_share_the_device_clock_to_within_a_constant_offset():
    # Each pass's kernel runs inside its own search: it cannot start before
    # the launch (inside executor.dispatch) nor end after its answers were
    # fetched.  On this v5e trace the device's timestamps run ~1 ms behind
    # the host's, so the order holds only once one constant offset, the
    # same for every pass, moves the device's events: the lower bounds
    # (dispatch start - kernel start) and upper bounds (fetch end - kernel
    # end) of the offset must overlap, and the offset is small next to a pass.
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(str(HERE / "small_spans.xplane.pb"))
    got = spans.summarize(profile)
    device = [p for p in profile.planes if p.name == "/device:TPU:0"][0]
    kernels = sorted((e.start_ns, e.end_ns) for line in device.lines
                     if line.name == trace_reduce.OPS_LINE for e in line.events
                     if trace_reduce.op_name(e.name) == KERNEL)
    dispatches = got.in_window("executor.dispatch")
    fetches = got.in_window("index.fetch")
    assert len(kernels) == len(dispatches) == len(fetches) == 3
    assert all(d[2] == {"q": 8, "bucket": 8} for d in dispatches)
    lower = max(d[0] - k0 for (k0, _), d in zip(kernels, dispatches))
    upper = min(f[1] - k1 for (_, k1), f in zip(kernels, fetches))
    assert lower <= upper
    assert abs(lower) < 2e6 and upper > 0                 # ns: under 2 ms
    pass_ns = min(k1 - k0 for k0, k1 in kernels)
    assert upper - lower < pass_ns / 4

"""Trace reduction: device busy union, kernel time, idle share and idle gaps.

Checked on hand-made traces (XSpace text protos, times in ps from the line's
``timestamp_ns``) and on ``small.xplane.pb``, a trace recorded on a TPU v5e
by ``record_trace.py``: three ``search`` calls of 8 queries over a 200K-row
index inside ``bench.window``, with a 50 ms ``bench.pause`` before the third.
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run, trace_reduce  # noqa: E402

KERNEL = "bscsr_topk_spmv_multiquery"
RECORDED = Path(__file__).resolve().parent / "small.xplane.pb"


def _plane(pid, name, line, events, names):
    ev = "\n".join(f"events {{ metadata_id: {m} offset_ps: {int(s * 1e3)} "
                   f"duration_ps: {int(d * 1e3)} }}" for m, s, d in events)
    md = "\n".join(f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}'
                   for k, n in names.items())
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 name: "{line}" '
            f"timestamp_ns: 0 {ev} }} {md} }}")


def _summary(*planes):
    from jax.profiler import ProfileData

    return trace_reduce.summarize(ProfileData.from_text_proto("\n".join(planes)))


OPS = {1: f"%{KERNEL}.1 = (f32[8]) custom-call()", 2: "%copy.3 = s32[2] copy()",
       3: "%fusion.2 = f32[4] fusion()"}
HOST = {1: "bench.window", 2: "bench.pause", 3: "bench.search"}


def test_union_clips_to_the_window_and_merges_overlaps():
    # window 100..1100 ns; ops 0..300 (clipped to 100..300), 200..500 (overlaps), 700..800
    dev = _plane(1, "/device:TPU:0", "XLA Ops",
                 [(1, 0, 300), (3, 200, 300), (2, 700, 100)], OPS)
    host = _plane(2, "/host:CPU", "python3",
                  [(1, 100, 1000), (2, 500, 200), (3, 650, 300)], HOST)
    s = _summary(dev, host)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx((500 - 100 + 100) * 1e-9)
    assert s.kernel_seconds(KERNEL) == pytest.approx(200e-9)   # only the part inside
    # gaps: 500..700 (mid 600: pause and search open; pause is shorter), 800..1100
    assert s.gap_seconds["bench.pause"] == pytest.approx(200e-9)
    assert s.gap_seconds["bench.search"] == pytest.approx(300e-9)
    assert s.breakdown()["device_ops"][0][0] == "fusion"


def test_two_chips_are_averaged():
    dev0 = _plane(1, "/device:TPU:0", "XLA Ops", [(1, 0, 600)], OPS)
    dev1 = _plane(3, "/device:TPU:1", "XLA Ops", [(1, 0, 200)], OPS)
    host = _plane(2, "/host:CPU", "python3", [(1, 0, 1000)], HOST)
    s = _summary(dev0, dev1, host)
    assert s.n_chips == 2
    assert s.busy_s == pytest.approx(400e-9)
    assert s.kernel_seconds(KERNEL) == pytest.approx(800e-9)


def test_a_trace_without_device_plane_is_refused():
    host = _plane(2, "/host:CPU", "python3", [(1, 0, 1000)], HOST)
    with pytest.raises(ValueError):
        _summary(host)


def test_op_names():
    assert trace_reduce.op_name(f"%{KERNEL}.1 = (f32[32,64,8]) custom-call(...)") == KERNEL
    assert trace_reduce.op_name("%copy-start.2 = (s32[32]) copy-start(...)") == "copy-start"
    assert trace_reduce.op_name("%fusion = pred[16384] fusion(...)") == "fusion"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(RECORDED))


def test_recorded_trace_reduces_to_what_its_events_say(recorded):
    s = trace_reduce.summarize(recorded)
    device = [p for p in recorded.planes if p.name == "/device:TPU:0"][0]
    ops = [e for line in device.lines if line.name == "XLA Ops" for e in line.events]
    kernel = [e for e in ops if e.name.startswith(f"%{KERNEL}")]
    assert len(kernel) == 3                       # one pass per search call
    window = [e for p in recorded.planes if p.name.startswith("/host:")
              for line in p.lines for e in line.events if e.name == "bench.window"][0]
    inside = sum(min(e.end_ns, window.end_ns) - max(e.start_ns, window.start_ns)
                 for e in kernel) * 1e-9
    assert s.kernel_seconds(KERNEL) == pytest.approx(inside)
    assert s.window_s == pytest.approx(window.duration_ns * 1e-9)
    assert 0 < s.busy_s < s.window_s
    assert s.kernel_seconds(KERNEL) <= s.busy_s
    assert s.gap_seconds.get("bench.pause", 0.0) >= 0.045    # the 50 ms sleep is idle
    idle_pct = run.load_reader("device_idle_pct.batch")(type("R", (), {"trace": s})())
    assert 0 < idle_pct < 100

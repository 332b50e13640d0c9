"""Reduction of a JAX profiler trace to device busy time, kernel time and gaps.

A TPU trace holds one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops``
line has one event per operation that ran, named by its HLO text
(``%bscsr_topk_spmv_multiquery.1 = (...) custom-call(...)``), and a
``/host:CPU`` plane whose threads carry the benchmark's own
``TraceAnnotation`` spans, on the same clock.  Busy time is the union of the
op intervals inside the window span; idle gaps are the holes in that union,
named by the innermost benchmark span open at the gap's middle.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_OP_NAME = re.compile(r"^%?([A-Za-z_][A-Za-z0-9_\-]*?)(?:\.\d+)?(?: =|$)")


def op_name(event_name: str) -> str:
    """``%copy.24 = s32[...] copy(...)`` -> ``copy``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0]


def union_ns(intervals, lo: float, hi: float) -> tuple:
    """Total length of the union of intervals clipped to [lo, hi], and its gaps."""
    spans = sorted((max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi)
    busy, gaps, cur_s, cur_e = 0.0, [], None, lo
    for s, e in spans:
        if cur_s is None or s > cur_e:
            if cur_s is not None:
                busy += cur_e - cur_s
            if s > cur_e:
                gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_s is not None:
        busy += cur_e - cur_s
    if cur_e < hi:
        gaps.append((cur_e, hi))
    return busy, gaps


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                     # averaged over the chips in the trace
    op_seconds: dict                  # op name -> device seconds, summed over chips
    gap_seconds: dict                 # host activity -> idle seconds, averaged
    n_chips: int

    def kernel_seconds(self, kernel: str) -> float:
        return self.op_seconds.get(kernel, 0.0)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gap_seconds.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": [[n, s] for n, s in gaps]}


def summarize(profile) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`TraceSummary`."""
    devices, spans = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.start_ns, e.end_ns, e.name) for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.end_ns, e.name) for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not devices or not windows:
        raise ValueError("trace holds no TPU device plane or no window span")
    lo, hi = windows[0]
    inner = sorted(((s, e, n) for s, e, n in spans if n != WINDOW_SPAN),
                   key=lambda t: t[1] - t[0])
    busy_total, op_seconds, gap_seconds = 0.0, {}, {}
    for ops in devices:
        busy, gaps = union_ns([(s, e) for s, e, _ in ops], lo, hi)
        busy_total += busy
        for s, e, name in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                key = op_name(name)
                op_seconds[key] = op_seconds.get(key, 0.0) + d * 1e-9
        for s, e in gaps:
            mid = (s + e) / 2
            who = next((n for a, b, n in inner if a <= mid <= b), "no benchmark span")
            gap_seconds[who] = gap_seconds.get(who, 0.0) + (e - s) * 1e-9 / len(devices)
    return TraceSummary((hi - lo) * 1e-9, busy_total * 1e-9 / len(devices), op_seconds,
                        gap_seconds, len(devices))


def load(trace_dir: Path) -> TraceSummary:
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return summarize(ProfileData.from_file(str(files[-1])))

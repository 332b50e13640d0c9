"""Reduction of the program's own spans in a profiler trace.

The program records ``repro.<layer>.<stage>`` spans through
``repro.utils.tracing.span`` (``jax.profiler.TraceAnnotation``), on the host
planes of the same trace as the device's operations and on the same clock,
up to an offset of about a millisecond (device timestamps trail the host's
on a v5e); each span's ids arrive as the event's stats.  This module reads
them beside ``trace_reduce``, which it leaves as it is:

- ``spans``: every ``repro.`` host event of the session, by name, as
  ``(start_ns, end_ns, stats)`` in start order, unclipped;
- ``program_gap_seconds``: the device's idle gaps inside ``bench.window``
  (the same gaps ``trace_reduce`` names by benchmark span), each named by
  the innermost ``repro.`` span open at the gap's middle, or
  ``OUTSIDE`` where none is; averaged over the chips, so the values sum to
  the window's idle time.

A per-layer reader gets the spans of its run from :func:`for_run`.  A trace
of a program that records no spans reads empty, and the readers then return
nothing.
"""
from __future__ import annotations

import dataclasses
import functools
import statistics
import sys
from pathlib import Path

from bench import trace_reduce

PREFIX = "repro."
OUTSIDE = "outside program spans"
TRACES = Path(__file__).resolve().parent.parent / ".bench_out" / "trace"


@dataclasses.dataclass
class ProgramSpans:
    spans: dict                  # name -> [(start_ns, end_ns, stats)], by start
    window: tuple | None         # (start_ns, end_ns) of bench.window
    program_gap_seconds: dict    # innermost repro. span -> idle seconds

    def in_window(self, name: str) -> list:
        """The spans of ``name`` that start inside the window."""
        if self.window is None:
            return []
        lo, hi = self.window
        return [s for s in self.spans.get(PREFIX + name, []) if lo <= s[0] < hi]

    def before_window(self, name: str) -> list:
        """The spans of ``name`` that end before the window starts (set-up)."""
        lo = self.window[0] if self.window is not None else float("inf")
        return [s for s in self.spans.get(PREFIX + name, []) if s[1] <= lo]

    def median_ms(self, name: str) -> float | None:
        got = self.in_window(name)
        return statistics.median(e - s for s, e, _ in got) * 1e-6 if got else None

    def children(self, parent: tuple) -> dict:
        """Seconds per span name inside the interval of ``parent``, summed."""
        lo, hi = parent[0], parent[1]
        out: dict = {}
        for name, spans in self.spans.items():
            for s, e, _ in spans:
                if lo <= s and e <= hi and (s, e) != (lo, hi):
                    out[name] = out.get(name, 0.0) + (e - s) * 1e-9
        return out


def summarize(profile) -> ProgramSpans:
    """Reduce a ``jax.profiler.ProfileData`` to its program spans."""
    spans: dict = {}
    windows = []
    devices = []
    for plane in profile.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            devices.append([(e.start_ns, e.end_ns) for line in plane.lines
                            if line.name == trace_reduce.OPS_LINE for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.end_ns, dict(e.stats)))
                    elif e.name == trace_reduce.WINDOW_SPAN:
                        windows.append((e.start_ns, e.end_ns))
    for v in spans.values():
        v.sort(key=lambda t: t[0])
    window = windows[0] if windows else None
    gaps: dict = {}
    if window is not None and devices:
        inner = sorted(((s, e, n) for n, v in spans.items() for s, e, _ in v),
                       key=lambda t: t[1] - t[0])
        for ops in devices:
            _, holes = trace_reduce.union_ns(ops, *window)
            for s, e in holes:
                mid = (s + e) / 2
                who = next((n for a, b, n in inner if a <= mid <= b), OUTSIDE)
                gaps[who] = gaps.get(who, 0.0) + (e - s) * 1e-9 / len(devices)
    return ProgramSpans(spans, window, gaps)


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int) -> ProgramSpans:
    from jax.profiler import ProfileData

    got = summarize(ProfileData.from_file(path))
    log = functools.partial(print, file=sys.stderr, flush=True)
    for name, sec in sorted(got.program_gap_seconds.items(), key=lambda kv: -kv[1]):
        log(f"program gap: {name} {sec:.6f} s")
    for build in got.before_window("index.build"):
        parts = ", ".join(f"{n} {s:.3f} s" for n, s in sorted(got.children(build).items()))
        log(f"index.build {(build[1] - build[0]) * 1e-9:.3f} s: {parts}")
    return got


def for_run(run) -> ProgramSpans | None:
    """The program spans of the run that ``run`` (a ``RunContext``) reads.

    ``run.spans`` where the harness hands them over; else the newest trace
    under ``.bench_out/trace``, taken only if its ``bench.window`` has the
    length of the run's own (``run.trace.window_s``), so a trace of another
    run is never read.
    """
    given = getattr(run, "spans", None)
    if given is not None:
        return given
    files = sorted(TRACES.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime_ns)
    if not files:
        return None
    got = _load(str(files[-1]), files[-1].stat().st_mtime_ns)
    if got.window is None or (got.window[1] - got.window[0]) * 1e-9 != run.trace.window_s:
        return None
    return got

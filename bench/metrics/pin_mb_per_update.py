"""Bytes pinned on the device per acknowledged update, in megabytes (1e6 B).

The sum of the ``bytes`` id of the program's ``repro.executor.pin`` spans
(``kernels/executor.py`` ``device_snapshot``: one per new snapshot a pass
reads) that start inside ``bench.window``, over the updates acknowledged in
the window.  None where the window holds no update or no pin span.
"""
from bench import spans


def read(run):
    got = spans.for_run(run)
    pins = got.in_window("executor.pin") if got is not None else []
    if not pins or not run.loop.acks:
        return None
    return sum(s["bytes"] for _, _, s in pins) / len(run.loop.acks) * 1e-6

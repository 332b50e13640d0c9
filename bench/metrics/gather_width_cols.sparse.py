"""Median columns the kernel's stage-1 gather covers per pass in the window.

Read from the program's ``repro.executor.columns`` spans (ids ``q``,
``cols``, the block's set columns, and ``width``, the gather chunks times
their columns; ``kernels/executor.py`` ``QueryExecutor.set_columns``) that
start inside ``bench.window``.  None where the program records no such span.
"""
import statistics

from bench import spans


def read(run):
    got = spans.for_run(run)
    if got is None:
        return None
    widths = [ids["width"] for _, _, ids in got.in_window("executor.columns") if "width" in ids]
    return float(statistics.median(widths)) if widths else None

"""Median host time of one executor dispatch in the window, in milliseconds.

Read from the program's ``repro.executor.dispatch`` spans (ids ``q`` and
``bucket``; ``kernels/executor.py`` ``query_batched``) that start inside
``bench.window``: resolving the compiled query and the device snapshot,
padding the batch and launching the compiled call, which returns before the
device has finished.  None where the program records no such span.
"""
from bench import spans


def read(run):
    got = spans.for_run(run)
    return got.median_ms("executor.dispatch") if got is not None else None

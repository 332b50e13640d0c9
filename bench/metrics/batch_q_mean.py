"""Queries per kernel pass over the window, from the frontend's batch histogram.

The difference of ``dispatch_info()["frontend"]["batch_histogram"]`` across
the window; probes count, as they share the passes.
"""


def read(run):
    passes = run.loop.passes()
    return sum(passes) / len(passes) if passes else None

"""Wall time of the index build in set-up, in seconds.

The duration of the program's first ``repro.index.build`` span with
``kind=init`` (``MutableTopKSpMVIndex.__init__``) that ends before
``bench.window``; its children (``index.partition``, ``index.encode`` per
partition, ``index.row_maps`` and the first ``index.refresh``) split it.  It
reads only where the profiler session was open during set-up; None otherwise.
"""
from bench import spans


def read(run):
    got = spans.for_run(run)
    if got is None:
        return None
    builds = [b for b in got.before_window("index.build") if b[2].get("kind") == "init"]
    return (builds[0][1] - builds[0][0]) * 1e-9 if builds else None

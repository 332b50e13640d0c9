"""Median wall time of one snapshot refresh in the window, in milliseconds.

Read from the program's ``repro.index.refresh`` spans (ids ``version`` and
``partitions_copied``; ``MutableTopKSpMVIndex._refresh``) that start inside
``bench.window``: the host work that makes an update visible to the next
pass.  None where the window holds no refresh.
"""
from bench import spans


def read(run):
    got = spans.for_run(run)
    return got.median_ms("index.refresh") if got is not None else None

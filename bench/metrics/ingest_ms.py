"""Median wall time of one ``ingest`` call in the window, in milliseconds.

Read from the benchmark's own span around each call on the writer thread; the
refresh of the host snapshot is synchronous and lies inside it; the device
pin of the new snapshot lands in the next kernel pass.
"""
import numpy as np


def read(run):
    acks = run.loop.acks
    if not acks:
        return None
    return float(np.median([a.ingest_s for a in acks])) * 1e3

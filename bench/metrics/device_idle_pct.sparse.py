"""Share of the traced window in which no operation ran on the device, in percent.

Busy time is the union of the ``XLA Ops`` intervals of the trace inside the
``bench.window`` span, averaged over the chips (``trace.summarize``).
"""


def read(run):
    if run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)

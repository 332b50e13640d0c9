"""Share of the HBM/compute roofline reached by the BS-CSR Top-K kernel.

The least time is the configuration's work for every kernel pass of the
traced window (``work.least_seconds``: the configured bytes per entry and
2 * nnz * q operations per pass), over the device time of the kernel's events
in the trace (``bscsr_topk_spmv_multiquery``), in percent.
"""
from bench import work

KERNEL = "bscsr_topk_spmv_multiquery"


def read(run):
    kernel_s = run.trace.kernel_seconds(KERNEL)
    passes = run.loop.passes()
    if kernel_s <= 0 or not passes:
        return None
    least = work.least_seconds([(run.live_nnz, q) for q in passes], run.cfg["n_cols"],
                               run.cfg["value_format"], run.cfg["big_k"], run.device_kind)
    return 100.0 * least / kernel_s

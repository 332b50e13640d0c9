"""The kernel's work and the chip's peaks: the roofline yardstick.

The work is that of the configuration, not of the program's stream: a pass
over ``nnz`` entries for ``q`` queries must read each entry's value in the
configured format, its column index in ``ceil(log2 n_cols)`` bits and one
row-boundary bit, plus the query block, and write the ``q x big_k``
answers (a 4-byte score and a 4-byte row id each).  It does ``2 * nnz * q``
operations.  A layout that spends more bytes per entry does not raise the
count, so a leaner stream shows as a larger share.
"""
from __future__ import annotations

import math

VALUE_BITS = {"F32": 32, "BF16": 16, "Q15": 16, "Q7": 8}

# Per chip, from Google Cloud's "TPU v5e" page (cloud.google.com/tpu/docs/v5e):
# 16 GB HBM at 819 GB/s, 197 TFLOP/s bf16, 393 TOP/s int8.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add them to PEAKS "
                       f"with their published source") from None


def pass_bytes(nnz: int, n_cols: int, value_format: str, q: int, big_k: int) -> float:
    entry_bits = VALUE_BITS[value_format] + math.ceil(math.log2(n_cols)) + 1
    return nnz * entry_bits / 8 + q * n_cols * 4 + q * big_k * 8


def pass_flops(nnz: int, q: int) -> float:
    return 2.0 * nnz * q


def least_seconds(passes: list, n_cols: int, value_format: str, big_k: int,
                  device_kind: str) -> float:
    """The least time for passes given as (nnz, q) pairs on one chip."""
    pk = peaks(device_kind)
    total = 0.0
    for nnz, q in passes:
        total += max(pass_bytes(nnz, n_cols, value_format, q, big_k) / pk["hbm_bytes_per_s"],
                     pass_flops(nnz, q) / pk["flops_per_s"])
    return total

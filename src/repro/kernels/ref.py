"""Pure-jnp oracles for the BS-CSR Top-K SpMV kernel (used by tests + benchmarks).

``topk_dense_ref`` is the exact ground truth (dense matmul).
``bscsr_spmv_ref`` evaluates the BS-CSR stream semantics end-to-end (row
recovery from flag bits + segment sums) without any blocking — it is the
oracle the Pallas kernel is asserted against, and doubles as the jit-compiled
CPU baseline (the sparse_dot_topn analogue) in benchmarks.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantization import FORMATS, ValueFormat

NEG_INF = float(np.finfo(np.float32).min)


def _dequant(vals: jnp.ndarray, fmt: ValueFormat) -> jnp.ndarray:
    if fmt.is_fixed_point:
        return vals.astype(jnp.float32) * jnp.float32(fmt.scale)
    return vals.astype(jnp.float32)


def unpack_flags(flags: jnp.ndarray, block_size: int) -> jnp.ndarray:
    """(P, B//32) int32 -> (P*B,) bool row-start bits (little-endian)."""
    words = flags.reshape(-1).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, None] >> shifts[None, :]) & jnp.uint32(1)
    return bits.reshape(-1).astype(bool)


def topk_sorted(scores: jnp.ndarray, big_k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-K by value desc, ties broken toward the lower row id.

    Always returns ``(big_k,)`` arrays: when fewer than ``big_k`` scores
    exist (e.g. a compacted index shrank below k rows per partition), the
    tail is padded with ``NEG_INF`` / sentinel row id ``len(scores)`` so
    downstream masking treats it like any other sentinel candidate.
    """
    n = scores.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    if n < big_k:
        scores = jnp.concatenate(
            [scores, jnp.full((big_k - n,), NEG_INF, scores.dtype)]
        )
        rows = jnp.concatenate(
            [rows, jnp.full((big_k - n,), n, jnp.int32)]
        )
    order = jnp.lexsort((rows, -scores))
    top = order[:big_k]
    return scores[top], rows[top].astype(jnp.int32)


@partial(jax.jit, static_argnames=("big_k",))
def topk_dense_ref(dense: jnp.ndarray, x: jnp.ndarray, big_k: int):
    """Exact Top-K of A @ x for a dense A — the ground-truth oracle."""
    scores = dense.astype(jnp.float32) @ x.astype(jnp.float32)
    return topk_sorted(scores, big_k)


def bscsr_row_scores(
    vals: jnp.ndarray,
    cols: jnp.ndarray,
    flags: jnp.ndarray,
    x: jnp.ndarray,
    n_rows: int,
    fmt: ValueFormat | str = "F32",
) -> jnp.ndarray:
    """All row scores of one BS-CSR stream (sentinel/padding rows dropped)."""
    fmt = FORMATS[fmt] if isinstance(fmt, str) else fmt
    block = vals.shape[-1]
    f = unpack_flags(flags, block)
    row_ids = jnp.cumsum(f.astype(jnp.int32)) - 1
    v = _dequant(vals.reshape(-1), fmt)
    xv = jnp.take(x.astype(jnp.float32), cols.reshape(-1).astype(jnp.int32))
    sums = jax.ops.segment_sum(v * xv, row_ids, num_segments=n_rows + 1)
    return sums[:n_rows]


def bscsr_topk_ref(
    vals, cols, flags, x, n_rows: int, k: int, fmt: ValueFormat | str = "F32"
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Local top-k of one BS-CSR partition — per-core oracle."""
    scores = bscsr_row_scores(vals, cols, flags, x, n_rows, fmt)
    return topk_sorted(scores, k)


def bscsr_topk_ref_stacked(
    vals: jnp.ndarray,        # (C, P, B) storage dtype
    cols: jnp.ndarray,        # (C, P, B)
    flags: jnp.ndarray,       # (C, P, B//32)
    x: jnp.ndarray,           # (M,) f32
    rows_per_core: jnp.ndarray,  # (C,) real rows of each partition
    max_rows: int,
    k: int,
    fmt: ValueFormat | str = "F32",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All cores' local top-k in one vmap over the stacked partition arrays.

    Scores are computed over a uniform ``max_rows`` segment budget; rows
    beyond a core's real count (sentinel/padding, which sum to 0, not
    NEG_INF) are masked before the local top-k so they can never displace
    real candidates.  This mask is load-bearing for churn-stable snapshot
    bucketing: ``max_rows`` may be a power-of-two pad of the live slot
    count, and the phantom slots it budgets MUST be materialized at NEG_INF
    or their 0.0 segment sums would outrank real negative-score candidates
    (the scratch-shape analysis in ``bscsr_topk_spmv.py``).  Returns (C, k)
    values and partition-local row ids.
    """
    fmt = FORMATS[fmt] if isinstance(fmt, str) else fmt

    def one_core(v, c, fl, rows_c):
        scores = bscsr_row_scores(v, c, fl, x, max_rows, fmt)
        scores = jnp.where(jnp.arange(max_rows) < rows_c, scores, NEG_INF)
        return topk_sorted(scores, k)

    return jax.vmap(one_core)(vals, cols, flags, rows_per_core)


def bscsr_slot_sums_stacked(
    vals: jnp.ndarray,        # (C, P, B) storage dtype
    cols: jnp.ndarray,        # (C, P, B)
    flags: jnp.ndarray,       # (C, P, B//32)
    x: jnp.ndarray,           # (M,) f32
    max_rows: int,
    fmt: ValueFormat | str = "F32",
) -> jnp.ndarray:
    """Accumulate-mode oracle: every core's raw per-slot row sums, (C, max_rows).

    The dense analogue of ``bscsr_spmv``'s kernel output: no top-k, no
    NEG_INF masking — phantom/padded slots simply stay 0.0, exactly as the
    kernel's dense accumulator leaves them (the caller's slot->row scatter is
    responsible for dropping them, never ``finalize_candidates``).
    """
    fmt = FORMATS[fmt] if isinstance(fmt, str) else fmt

    def one_core(v, c, fl):
        return bscsr_row_scores(v, c, fl, x, max_rows, fmt)

    return jax.vmap(one_core)(vals, cols, flags)


def csr_topk_numpy(indptr, indices, data, x, big_k: int):
    """Numpy CSR Top-K — the host-side 'sparse_dot_topn' style baseline."""
    prods = data * x[indices]
    n = len(indptr) - 1
    scores = np.zeros(n, dtype=np.float32)
    filled = np.diff(indptr) > 0       # empty rows score 0
    if filled.any():
        scores[filled] = np.add.reduceat(prods, indptr[:-1][filled])
    cand = np.arange(n)
    if big_k < n:                      # ties at the K-th value stay in
        cand = np.nonzero(scores >= np.partition(scores, n - big_k)[n - big_k])[0]
    order = cand[np.lexsort((cand, -scores[cand]))][:big_k]
    return scores[order], order.astype(np.int32)

"""The reference's per-partition top-k, scored in blocks of rows.

Values and queries are multiples of 1/8 small enough that every score is
exact in float32, so any order of summation gives the same bits and the
blocked answer must equal the single-block one exactly.
"""
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import reference  # noqa: E402


def _csr(rows: list) -> tuple:
    """CSR arrays of rows given as {column: value} dicts."""
    indptr = np.zeros(len(rows) + 1, np.int64)
    indptr[1:] = np.cumsum([len(r) for r in rows])
    indices = np.array([c for r in rows for c in sorted(r)], np.int32)
    data = np.array([r[c] for r in rows for c in sorted(r)], np.float32)
    return indptr, indices, data


def _cfg(partitions: int, k: int) -> dict:
    return {"value_format": "BF16", "k": k, "big_k": 4, "partitions": partitions}


def test_blocks_merge_to_the_single_block_answer():
    """Ties across a block boundary keep the earlier slot, as in one block."""
    n_cols, rng = 64, np.random.default_rng(3)
    rows = [{int(c): float(rng.integers(1, 9)) / 8
             for c in rng.choice(n_cols, int(rng.integers(1, 9)), replace=False)}
            for _ in range(40)]
    top = {0: 2.0, 5: 1.5, 9: 1.0}
    for r in (4, 5, 6, 7, 8, 9, 25, 26, 27, 33):   # duplicates straddling blocks of 3 rows
        rows[r] = dict(top)
    ref = reference.Reference(*_csr(rows), n_cols, _cfg(partitions=2, k=4))
    xs = np.zeros((3, n_cols), np.float32)
    xs[:, [0, 5, 9]] = [[1.0, 0.5, 0.25], [0.5, 0.5, 0.5], [0.0, 0.0, 0.125]]
    xs[2, 1:4] = 0.25
    one = ref.base_topk(xs)
    blocked = ref.base_topk(xs, block_bytes=3 * n_cols * 4)
    np.testing.assert_array_equal(one[0], blocked[0])
    np.testing.assert_array_equal(one[1], blocked[1])
    np.testing.assert_array_equal(one[1][0, 0], [4, 5, 6, 7])     # earlier slot first
    np.testing.assert_array_equal(one[1][1, 0], [25, 26, 27, 33])


def test_wide_partition_past_int32():
    """One partition of 70,400 rows x 30,522 columns: 2.15e9 dense cells, past int32."""
    n_rows, n_cols, k = 70_400, 30_522, 8
    assert (n_rows - 1) * n_cols > np.iinfo(np.int32).max
    cols = (np.arange(n_rows, dtype=np.int64) * 7_919) % n_cols
    vals = (1 + np.arange(n_rows) % 8).astype(np.float32) / 8
    indptr = np.arange(n_rows + 1, dtype=np.int64)
    ref = reference.Reference(indptr, cols.astype(np.int32), vals, n_cols, _cfg(1, k))
    xs = np.zeros((2, n_cols), np.float32)
    xs[0, cols[-5:]] = 1.0          # the last rows, whose flat index passes int32
    xs[1, ::3] = 0.5
    got_s, got_r = ref.base_topk(xs, q_block=2, block_bytes=64 << 20)
    score = xs[:, cols] * vals[None, :]                 # exact: one entry per row
    slot = np.broadcast_to(np.arange(n_rows), score.shape)
    want = np.lexsort((slot, -score), axis=1)[:, :k]
    np.testing.assert_array_equal(got_r[0], want)
    np.testing.assert_array_equal(got_s[0], np.take_along_axis(score, want, 1))
    assert (got_r[0, 0] * n_cols > np.iinfo(np.int32).max).any()


def test_sparse_queries_score_as_at_full_width():
    """Densifying only the queried columns changes no score and no row."""
    n_cols, rng = 3_000, np.random.default_rng(5)
    rows = [{int(c): float(rng.integers(1, 9)) / 8
             for c in rng.choice(n_cols, int(rng.integers(1, 40)), replace=False)}
            for _ in range(500)]
    ref = reference.Reference(*_csr(rows), n_cols, _cfg(partitions=4, k=4))
    xs = np.zeros((5, n_cols), np.float32)
    for x in xs:
        x[rng.choice(n_cols, 200, replace=False)] = rng.integers(1, 9, 200) / 8
    dense = np.vstack([xs, np.full((1, n_cols), 0.125, np.float32)])   # every column set
    narrow, full = ref.base_topk(xs), ref.base_topk(dense)
    np.testing.assert_array_equal(narrow[0], full[0][:, :5])
    np.testing.assert_array_equal(narrow[1], full[1][:, :5])

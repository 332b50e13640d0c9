"""The plain reference: the configuration's approximate Top-K, computed anew.

It imports nothing of the program.  The semantics are those the
configuration file states (the paper's §III-A approximation):

- values are stored in the configured format (BF16: round to nearest even);
  a score is the exact dot product of the stored row with the f32 query;
- rows are split into ``partitions`` contiguous ranges, the first
  ``n_rows % partitions`` one row longer;
- each partition keeps its ``k`` best slots (score descending, earlier slot
  first); the ``big_k`` best of the survivors (score descending, lower row id
  first) are the answer;
- for a live collection, a replaced row is appended to the partition holding
  the fewest slots (the lowest such index), after one separator slot; its old
  copy keeps its slot and competes for its partition's ``k`` until
  compaction, and is dropped from the answer.

Base partitions are scored on the device, one block of rows at a time as a
dense ``(rows, columns)`` block times the query block, over the columns that
some query sets (all of them for dense queries); a block holds a whole
partition where that fits ``BLOCK_BYTES``, else as many of its rows as fit,
and the blocks' top-k are merged under the partition's tie rule.  Appended
rows, and the score of any single row, are scored on the host in float64.
"""
from __future__ import annotations

import collections
import dataclasses
from functools import lru_cache

import numpy as np

NEG = -np.inf
BLOCK_BYTES = 1 << 30   # most bytes of one dense float32 block of rows on the device
WIDTH_STEP = 1024       # the dense columns of a sparse query block, padded to a multiple
IN_FLIGHT = 4           # blocks enqueued on the device ahead of the host's fetch


def stored_values(values: np.ndarray, value_format: str) -> np.ndarray:
    """The configured storage rounding of f32 values, in a dtype that holds them
    exactly: bfloat16 for BF16, else float32."""
    if value_format == "F32":
        return np.asarray(values, np.float32)
    if value_format == "BF16":
        import ml_dtypes

        return np.asarray(values, np.float32).astype(ml_dtypes.bfloat16)
    if value_format in ("Q15", "Q7"):
        frac = 15 if value_format == "Q15" else 7
        lim = 2 ** frac
        q = np.clip(np.round(np.asarray(values, np.float64) * lim), -lim, lim - 1)
        return (q / lim).astype(np.float32)
    raise ValueError(f"unknown value format {value_format!r}")


def partition_bounds(n_rows: int, partitions: int) -> np.ndarray:
    base, rem = divmod(n_rows, partitions)
    sizes = np.full(partitions, base, np.int64)
    sizes[:rem] += 1
    return np.concatenate([[0], np.cumsum(sizes)])


@dataclasses.dataclass
class Update:
    gid: int
    cols: np.ndarray      # sorted
    vals: np.ndarray      # stored (rounded) values, as ``stored_values`` gives them
    partition: int = -1
    slot: int = -1


def sparsify(x: np.ndarray, m: int) -> tuple:
    """Magnitude top-m of a dense vector, columns sorted, unit L2 norm."""
    keep = np.sort(np.argsort(-np.abs(x), kind="stable")[:m])
    v = x[keep].astype(np.float32)[None, :]
    norm = np.sqrt(np.add.reduce(v * v, axis=1, keepdims=True))   # f32, per row
    return keep.astype(np.int32), (v / np.maximum(norm, 1e-12))[0]


@lru_cache(maxsize=None)
def _partition_fn(r_max: int, n_cols: int, nnz_cap: int, k: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fn(flat, vals, n_valid_rows, xs):
        dense = jnp.zeros(r_max * n_cols, jnp.float32).at[flat].set(
            vals, mode="drop", unique_indices=True)
        dense = dense.reshape(r_max, n_cols)
        s = jnp.dot(xs, dense.T, precision=jax.lax.Precision.HIGHEST)   # (Q, R)
        s = jnp.where(jnp.arange(r_max)[None, :] < n_valid_rows, s, -jnp.inf)
        return jax.lax.top_k(s, k)

    return fn


class Reference:
    def __init__(self, indptr, indices, data, n_cols: int, cfg: dict):
        self.cfg = cfg
        self.n_cols = n_cols
        self.n_rows = indptr.shape[0] - 1
        self.indptr, self.indices = indptr, indices
        self.values = stored_values(data, cfg["value_format"])
        self.k, self.big_k = cfg["k"], cfg["big_k"]
        self.bounds = partition_bounds(self.n_rows, cfg["partitions"])
        self.slots = list(np.diff(self.bounds))   # slot counts, for placement
        self.updates: list[Update] = []

    # -- live collection -------------------------------------------------
    def apply(self, gid: int, x: np.ndarray, m: int) -> Update:
        """Record an acknowledged replace of ``gid`` by dense embedding ``x``."""
        cols, vals = sparsify(np.asarray(x, np.float32), m)
        u = Update(gid, cols, stored_values(vals, self.cfg["value_format"]))
        u.partition = int(np.argmin(self.slots))
        u.slot = int(self.slots[u.partition]) + 1
        self.slots[u.partition] += 2
        self.updates.append(u)
        return u

    def content(self, gid: int, n_updates: int) -> tuple:
        for u in reversed(self.updates[:n_updates]):
            if u.gid == gid:
                return u.cols, u.vals
        lo, hi = self.indptr[gid], self.indptr[gid + 1]
        return self.indices[lo:hi], self.values[lo:hi]

    def row_score(self, gid: int, x: np.ndarray, n_updates: int) -> float:
        cols, vals = self.content(gid, n_updates)
        return float(np.dot(vals.astype(np.float64), x[cols].astype(np.float64)))

    # -- base partitions on the device ------------------------------------
    def base_topk(self, xs: np.ndarray, q_block: int = 256, block_bytes: int = BLOCK_BYTES):
        """Per-partition top-k over the base rows: (C, Q, k) scores, global ids.

        Only the columns that some query sets are made dense, in rising order
        and padded to a multiple of ``WIDTH_STEP``: the others add nothing."""
        import jax.numpy as jnp

        c = len(self.bounds) - 1
        used = np.flatnonzero(np.any(xs != 0, axis=0))
        width = min(self.n_cols, -(-max(used.size, 1) // WIDTH_STEP) * WIDTH_STEP)
        slot = np.full(self.n_cols, -1, np.int32)   # each column's dense place, -1 if none
        slot[used] = np.arange(used.size)
        r_blk = int(min(np.diff(self.bounds).max(), max(self.k, block_bytes // (4 * width))))
        blocks = [[(lo, min(lo + r_blk, self.bounds[p + 1]))
                   for lo in range(self.bounds[p], self.bounds[p + 1], r_blk)] for p in range(c)]
        nnz_b = max(int(self.indptr[hi] - self.indptr[lo]) for part in blocks for lo, hi in part)
        nnz_cap = int(-(-nnz_b // 65536) * 65536)
        fn = _partition_fn(r_blk, width, nnz_cap, self.k)
        q = xs.shape[0]
        xp = np.zeros((-(-q // q_block) * q_block, width), np.float32)
        xp[:q, : used.size] = xs[:, used]
        xq = [jnp.asarray(xp[b : b + q_block]) for b in range(0, xp.shape[0], q_block)]
        found = [[None] * len(part) for part in blocks]   # (Q, k) scores and ids per block
        pending = collections.deque()

        def fetch():
            p, j, r0, outs = pending.popleft()
            found[p][j] = (np.concatenate([np.asarray(s) for s, _ in outs])[:q],
                           np.concatenate([np.asarray(i) for _, i in outs])[:q] + r0)

        for p, part in enumerate(blocks):
            for j, (r0, r1) in enumerate(part):
                lo, hi = self.indptr[r0], self.indptr[r1]
                flat = np.full(nnz_cap, r_blk * width, np.int32)   # past the block: dropped
                flat[: hi - lo] = np.repeat(np.arange(r1 - r0, dtype=np.int32) * width,
                                            np.diff(self.indptr[r0 : r1 + 1]))
                at = slot[self.indices[lo:hi]]
                flat[: hi - lo] = np.where(at >= 0, flat[: hi - lo] + at, r_blk * width)
                v = np.zeros(nnz_cap, self.values.dtype)
                v[: hi - lo] = self.values[lo:hi]
                flat_d, v_d = jnp.asarray(flat), jnp.asarray(v).astype(jnp.float32)
                pending.append((p, j, r0, [fn(flat_d, v_d, int(r1 - r0), x) for x in xq]))
                if len(pending) > IN_FLIGHT:
                    fetch()
        while pending:
            fetch()
        vals = np.full((c, q, self.k), NEG, np.float64)
        rows = np.zeros((c, q, self.k), np.int64)
        for p, part in enumerate(found):
            # the blocks' top-k merged: score descending, earlier slot first
            s_p = np.concatenate([s for s, _ in part], axis=1).astype(np.float64)
            r_p = np.concatenate([r for _, r in part], axis=1).astype(np.int64)
            order = np.lexsort((r_p, -s_p), axis=1)[:, : self.k]
            vals[p] = np.take_along_axis(s_p, order, 1)
            rows[p] = np.take_along_axis(r_p, order, 1)
        return vals, rows

    # -- the answer under the first ``n_updates`` updates ------------------
    def update_scores(self, xs: np.ndarray) -> np.ndarray:
        """(Q, n_updates) float64 scores of every recorded update's new row."""
        out = np.zeros((xs.shape[0], len(self.updates)), np.float64)
        for j, u in enumerate(self.updates):
            out[:, j] = xs[:, u.cols].astype(np.float64) @ u.vals.astype(np.float64)
        return out

    def answer(self, base_vals: np.ndarray, base_rows: np.ndarray, upd_scores: np.ndarray,
               n_updates: int) -> tuple:
        """(big_k,) scores and row ids for one query.

        ``base_*`` are its (C, k) base-partition candidates, ``upd_scores``
        its scores of the recorded updates' rows.
        """
        applied = self.updates[:n_updates]
        dead_base = {u.gid for u in applied if u.gid < self.n_rows}
        last = {u.gid: j for j, u in enumerate(applied)}
        cands = [[] for _ in range(base_vals.shape[0])]   # (score, slot, gid, alive)
        for p in range(base_vals.shape[0]):
            for s, g in zip(base_vals[p], base_rows[p]):
                if np.isfinite(s):
                    cands[p].append((float(s), int(g - self.bounds[p]), int(g),
                                     int(g) not in dead_base))
        for j, u in enumerate(applied):
            cands[u.partition].append((float(upd_scores[j]), u.slot, u.gid, last[u.gid] == j))
        survivors = []
        for cand in cands:
            cand.sort(key=lambda t: (-t[0], t[1]))
            survivors += [(s, g) for s, _, g, alive in cand[: self.k] if alive]
        survivors.sort(key=lambda t: (-t[0], t[1]))
        top = survivors[: self.big_k]
        return (np.array([s for s, _ in top], np.float64),
                np.array([g for _, g in top], np.int64))


@dataclasses.dataclass
class Verdict:
    score_gap: float = 0.0      # widest gap between served and reference sorted scores
    row_score_gap: float = 0.0  # widest gap between a served score and its row's score
    malformed: int = 0          # answers of wrong length, with repeated or unknown rows
    compared: int = 0


def judge_one(ref: Reference, x, served_vals, served_rows, base_vals, base_rows,
              upd_scores, n_lo: int, n_hi: int, verdict: Verdict) -> int:
    """Fold one served answer into ``verdict``; returns the state it matched.

    The answer may reflect any prefix of the update log between ``n_lo``
    (every update acknowledged before the query was sent) and ``n_hi``
    (every update acknowledged before it was answered); the closest wins.
    """
    served_vals = np.asarray(served_vals, np.float64)
    served_rows = np.asarray(served_rows, np.int64)
    n_ids = ref.n_rows
    ok_shape = served_rows.shape == (ref.big_k,) and served_vals.shape == (ref.big_k,)
    if (not ok_shape or len(set(served_rows.tolist())) != served_rows.size
            or served_rows.min(initial=0) < 0 or served_rows.max(initial=0) >= n_ids):
        verdict.malformed += 1
        verdict.compared += 1
        return n_lo
    best = None
    for n in range(n_lo, n_hi + 1):
        rv, _ = ref.answer(base_vals, base_rows, upd_scores, n)
        gap = float(np.max(np.abs(np.sort(served_vals)[::-1] - rv), initial=0.0)) \
            if rv.shape == served_vals.shape else np.inf
        row_gap = max((abs(ref.row_score(int(g), x, n) - float(s))
                       for s, g in zip(served_vals, served_rows)), default=0.0)
        if best is None or max(gap, row_gap) < max(best[0], best[1]):
            best = (gap, row_gap, n)
    verdict.score_gap = max(verdict.score_gap, best[0])
    verdict.row_score_gap = max(verdict.row_score_gap, best[1])
    verdict.compared += 1
    return best[2]

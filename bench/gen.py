"""Inputs made from the seed: the collection, query blocks and traffic schedules.

The collection follows the law of the paper's synthetic set (Parravicini et
al., arXiv:2103.04808, Table III): row lengths Gamma(3, 4/3) scaled to the
configured mean (at least 1, at most ``n_cols``), distinct columns drawn
uniformly without replacement and stored sorted, standard normal values,
rows L2-normalised.  Row lengths and values are drawn on the host with one
vectorised call each; the columns are chosen on the device, in fixed-shape
chunks, as the ``len`` smallest of ``n_cols`` uniform keys per row.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

SHORT_CAP = 64          # columns per row chosen by the wide pass
CHUNK_ROWS = 262_144    # rows per device call of the wide pass
LONG_CHUNK_ROWS = 4_096  # rows per device call for rows longer than SHORT_CAP


@dataclasses.dataclass(frozen=True)
class Collection:
    indptr: np.ndarray   # (n_rows + 1,) int64
    indices: np.ndarray  # (nnz,) int32, sorted and distinct within a row
    data: np.ndarray     # (nnz,) float32, rows of unit L2 norm
    n_cols: int

    @property
    def n_rows(self) -> int:
        return self.indptr.shape[0] - 1

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator for one named use of the run's seed."""
    tag = int.from_bytes(stream.encode(), "little")
    return np.random.default_rng([seed, tag])


def device_seed(seed: int, stream: str) -> int:
    return int(np.random.SeedSequence([seed, int.from_bytes(stream.encode(), "little")])
               .generate_state(1)[0])


def row_lengths(n_rows: int, n_cols: int, law: dict, rng: np.random.Generator) -> np.ndarray:
    if law["kind"] != "gamma":
        raise ValueError(f"unknown row-length law {law['kind']!r}")
    shape, scale, mean = law["shape"], law["scale"], law["mean"]
    raw = rng.gamma(shape=shape, scale=scale, size=n_rows)
    lens = np.maximum(1, np.round(raw * (mean / (shape * scale)))).astype(np.int64)
    return np.minimum(lens, n_cols)


def _pick_columns(key, lens, n_cols: int, cap: int):
    """(R, cap) sorted distinct columns per row, ``n_cols`` past each row's length."""
    import jax
    import jax.numpy as jnp

    u = jax.random.uniform(key, (lens.shape[0], n_cols))
    _, idx = jax.lax.top_k(u, cap)
    keep = jnp.arange(cap)[None, :] < lens[:, None]
    return jnp.sort(jnp.where(keep, idx, n_cols), axis=1).astype(jnp.int16)


def _columns(lens: np.ndarray, n_cols: int, seed: int) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    pick = jax.jit(partial(_pick_columns, n_cols=n_cols, cap=min(SHORT_CAP, n_cols)))
    pick_long = jax.jit(partial(_pick_columns, n_cols=n_cols, cap=n_cols))
    key = jax.random.key(device_seed(seed, "columns"))
    long_rows = np.flatnonzero(lens > SHORT_CAP)
    short_lens = np.where(lens > SHORT_CAP, 0, lens)

    def run(fn, lens_all, rows_per_call, salt):
        out = []
        for i, lo in enumerate(range(0, lens_all.shape[0], rows_per_call)):
            part = np.zeros(rows_per_call, np.int32)
            chunk = lens_all[lo : lo + rows_per_call]
            part[: chunk.shape[0]] = chunk
            cols = fn(jax.random.fold_in(key, salt + i), jnp.asarray(part))
            out.append((cols, chunk))
        flat = []
        for cols, chunk in out:   # fetch after every chunk was enqueued
            cols = np.asarray(cols)[: chunk.shape[0]]
            flat.append(cols[np.arange(cols.shape[1])[None, :] < chunk[:, None]])
        return np.concatenate(flat) if flat else np.zeros(0, np.int16)

    short = run(pick, short_lens, min(CHUNK_ROWS, 1 << max(lens.shape[0] - 1, 1).bit_length()), 0)
    indices = np.empty(int(lens.sum()), np.int32)
    if long_rows.size:
        is_long = np.repeat(lens > SHORT_CAP, lens)
        indices[~is_long] = short
        indices[is_long] = run(pick_long, lens[long_rows], LONG_CHUNK_ROWS, 1 << 20)
    else:
        indices[:] = short
    return indices


def make_collection(cfg: dict, seed: int) -> Collection:
    """The configuration's collection, deterministic in ``seed``."""
    n_rows, n_cols = cfg["n_rows"], cfg["n_cols"]
    rng = rng_for(seed, "collection")
    lens = row_lengths(n_rows, n_cols, cfg["row_length"], rng)
    indptr = np.zeros(n_rows + 1, np.int64)
    np.cumsum(lens, out=indptr[1:])
    data = rng.standard_normal(int(indptr[-1]), dtype=np.float32)
    norms = np.sqrt(np.add.reduceat(data * data, indptr[:-1]), dtype=np.float32)
    data /= np.repeat(norms, lens)
    return Collection(indptr, _columns(lens, n_cols, seed), data, n_cols)


def dense_normal(rng: np.random.Generator, n: int, n_cols: int) -> np.ndarray:
    return rng.standard_normal((n, n_cols), dtype=np.float32)


def zipf_ids(rng: np.random.Generator, n: int, n_items: int, theta: float) -> np.ndarray:
    """Scrambled Zipfian ids as YCSB draws keys (Cooper et al., SoCC 2010).

    A rank r in [0, n_items) is drawn with probability proportional to
    1 / (r + 1)**theta, then hashed with 64-bit FNV-1a over its eight bytes
    and taken modulo ``n_items``, so hot keys scatter over the id space.
    """
    cdf = np.cumsum(np.arange(1, n_items + 1, dtype=np.float64) ** -theta)
    ranks = np.searchsorted(cdf, rng.random(n) * cdf[-1], side="right")
    return fnv1a64(ranks.astype(np.uint64)) % np.uint64(n_items)


def fnv1a64(x: np.ndarray) -> np.ndarray:
    h = np.full(x.shape, 0xCBF29CE484222325, np.uint64)
    prime = np.uint64(0x100000001B3)
    with np.errstate(over="ignore"):
        for shift in range(0, 64, 8):
            h = (h ^ ((x >> np.uint64(shift)) & np.uint64(0xFF))) * prime
    return h


@dataclasses.dataclass(frozen=True)
class Schedule:
    """An open-loop schedule: ``due`` seconds from the window's start."""

    due: np.ndarray        # (n,) float64, ascending
    is_update: np.ndarray  # (n,) bool
    update_ids: np.ndarray  # (n_updates,) int64, in schedule order


def open_schedule(traffic: dict, n_items: int, seconds: float,
                  rng: np.random.Generator) -> Schedule:
    """A fixed number of arrivals, Poisson given their count, from the seed.

    Every seed gets ``round(rate * seconds)`` arrivals with exactly the
    configured update share: the times are sorted uniform draws over the
    window (a Poisson process conditioned on its count) and the updates a
    random subset of them, so seeds differ in order and not in amount.
    """
    n = int(round(traffic["rate_per_s"] * seconds))
    due = np.sort(rng.random(n) * seconds)
    n_upd = int(round(traffic.get("update_share", 0.0) * n))
    is_update = np.zeros(n, bool)
    is_update[rng.permutation(n)[:n_upd]] = True
    ids = np.zeros(0, np.int64)
    if n_upd:
        ids = zipf_ids(rng, n_upd, n_items, traffic["update_key_theta"]).astype(np.int64)
    return Schedule(due, is_update, ids)

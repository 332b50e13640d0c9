"""Inputs made from the seed: the collection, query blocks and traffic schedules.

A configuration file states its collection by laws, each optional but the
row lengths, with the paper's synthetic set (Parravicini et al.,
arXiv:2103.04808, Table III) as the default:

- ``row_length``: ``{"kind": "gamma", "shape", "scale", "mean"}``, Gamma
  lengths scaled to the mean (the paper's law), or ``{"kind": "lognormal",
  "mean", "sigma", "max"}``, heavy-tailed with that mean before clipping;
  every length is rounded and clipped to ``[1, min(max, n_cols)]``;
- ``column_law``: ``{"kind": "uniform"}`` (the default) or ``{"kind":
  "zipf", "exponent": s}``: each row's distinct columns are drawn without
  replacement, weighted by ``1 / (rank + 1)**s`` of the column's popularity
  rank; ranks follow one fixed permutation of the column ids, so the hot
  columns scatter over the ids and are the same for every seed, for the
  collection and its queries alike;
- ``value_law``: ``"normal"`` (the default) or ``"half_normal"``; rows are
  L2-normalised in float32;
- ``queries``: ``{"kind": "dense_normal"}`` (the default: standard normal,
  every column set) or ``{"kind": "sparse", "nnz": <row-length law>,
  "column_law": "collection" | <column law>, "value_law": ...}``, unit-norm
  rows, handed to the program as dense float32 blocks with zeros.

Row lengths and values are drawn on the host with one vectorised call each;
the columns are chosen on the device, in fixed-shape calls sized so that
no call's keys take more than ``ARRAY_BYTES``, as the ``len`` largest of
``n_cols`` uniform keys per row, or for a weighted law as the first ``len``
distinct columns of independent weighted draws (``_pick_columns``).  Rows go
in passes by length, each picking as many columns as its longest row needs:
uniform, up to ``SHORT_CAP`` and up to the law's longest row (a pick wider
than ``TOPK_CAP`` sorts whole rows, so its calls take fewer rows); weighted,
up to caps that double from ``SHORT_CAP``.
"""
from __future__ import annotations

import collections
import dataclasses
from functools import lru_cache, partial

import numpy as np

SHORT_CAP = 64          # columns per row chosen by the wide pass
TOPK_CAP = 256          # most columns a pick takes without sorting whole rows (TPU TopK)
SORT_COPIES = 4         # key-sized arrays a wider pick holds while it sorts whole rows
CHUNK_ROWS = 262_144    # most rows per device call of the wide pass
LONG_CHUNK_ROWS = 4_096  # most rows per device call of the passes after the wide one
ARRAY_BYTES = 1 << 29   # most bytes of the (rows, n_cols) float32 keys of one device call
IN_FLIGHT = 4           # device calls enqueued ahead of the host's fetch
NORM_ROWS = 1 << 20     # rows normalised at a time


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


def longest_row(n_cols: int, law: dict) -> int:
    """The most non-zeros the row-length law gives a row."""
    return int(min(law.get("max", n_cols), n_cols))


def row_lengths(n_rows: int, n_cols: int, law: dict, rng: np.random.Generator) -> np.ndarray:
    if law["kind"] == "gamma":
        shape, scale, mean = law["shape"], law["scale"], law["mean"]
        raw = rng.gamma(shape=shape, scale=scale, size=n_rows)
        lens = np.maximum(1, np.round(raw * (mean / (shape * scale)))).astype(np.int64)
        return np.minimum(lens, longest_row(n_cols, law))
    if law["kind"] == "lognormal":
        sigma = law["sigma"]
        raw = rng.lognormal(np.log(law["mean"]) - sigma * sigma / 2, sigma, size=n_rows)
        return np.clip(np.round(raw), 1, longest_row(n_cols, law)).astype(np.int64)
    raise ValueError(f"unknown row-length law {law['kind']!r}")


def _law_key(law: dict | None) -> tuple:
    """A column law as a hashable key: ``("uniform",)`` or ``("zipf", s)``."""
    kind = (law or {}).get("kind", "uniform")
    if kind == "uniform":
        return (kind,)
    if kind != "zipf":
        raise ValueError(f"unknown column law {kind!r}")
    return (kind, float(law["exponent"]))


def column_probabilities(n_cols: int, law: dict | None) -> np.ndarray | None:
    """(n_cols,) float64 chance of each column id in one draw; None for uniform.

    Read-only: it, the alias tables and the draws per cap are made once per
    law, since query blocks need them inside the window."""
    return _probabilities(n_cols, _law_key(law))


@lru_cache(maxsize=None)
def _probabilities(n_cols: int, key: tuple) -> np.ndarray | None:
    if key[0] == "uniform":
        return None
    by_rank = rng_for(0, "popularity").permutation(n_cols)   # column id of each rank
    p = np.empty(n_cols, np.float64)
    p[by_rank] = np.arange(1, n_cols + 1, dtype=np.float64) ** -key[1]
    p /= p.sum()
    p.flags.writeable = False
    return p


def alias_table(p: np.ndarray) -> tuple:
    """Walker's alias tables (Vose's construction): draw i uniformly, keep it
    with chance ``keep[i]``, else take ``alias[i]``."""
    n = p.shape[0]
    scaled = p * n
    keep, alias = np.ones(n, np.float64), np.arange(n, dtype=np.int32)
    small = [i for i in range(n) if scaled[i] < 1.0]
    large = [i for i in range(n) if scaled[i] >= 1.0]
    while small and large:
        lo, hi = small.pop(), large.pop()
        keep[lo], alias[lo] = scaled[lo], hi
        scaled[hi] += scaled[lo] - 1.0
        (small if scaled[hi] < 1.0 else large).append(hi)
    return keep.astype(np.float32), alias


@lru_cache(maxsize=None)
def _alias(n_cols: int, key: tuple) -> tuple:
    """The law's alias tables, made once: uniform keeps every draw."""
    p = _probabilities(n_cols, key)
    if p is None:
        keep, alias = np.ones(n_cols, np.float32), np.arange(n_cols, dtype=np.int32)
    else:
        keep, alias = alias_table(p)
    keep.flags.writeable = alias.flags.writeable = False
    return keep, alias


def draws_for(n_cols: int, law: dict | None, cap: int) -> int:
    """Draws per row, a multiple of 128, whose distinct columns number at least
    ``cap`` with ten standard deviations to spare (independent draws)."""
    return _draws_for(n_cols, _law_key(law), cap)


@lru_cache(maxsize=None)
def _draws_for(n_cols: int, key: tuple, cap: int) -> int:
    p = _probabilities(n_cols, key)
    p = np.full(n_cols, 1.0 / n_cols) if p is None else p
    for draws in range(-(-cap // 128) * 128, 64 * cap + 128, 128):
        hit = -np.expm1(draws * np.log1p(-p))     # chance a column is drawn at all
        if hit.sum() - 10 * np.sqrt((hit * (1 - hit)).sum()) >= cap:
            return draws
    raise ValueError(f"the column law cannot give {cap} distinct columns of {n_cols}")


def values(rng: np.random.Generator, shape, law: str) -> np.ndarray:
    v = rng.standard_normal(shape, dtype=np.float32)
    if law == "half_normal":
        np.abs(v, out=v)
    elif law != "normal":
        raise ValueError(f"unknown value law {law!r}")
    return v


def _first_distinct(c, cap: int, n_cols: int):
    """(R, cap) first distinct values of each row of ``c`` in draw order, and
    where each was first drawn (``c.shape[1]`` past the row's distinct ones).

    Two sorts of one int32 key each: (value, position), then (first position
    or ``draws``, value), so both fields must fit 31 bits together."""
    import jax
    import jax.numpy as jnp

    draws = c.shape[1]
    p_bits, c_bits = int(draws).bit_length(), int(n_cols - 1).bit_length()
    if p_bits + c_bits > 31:
        raise ValueError(f"{draws} draws over {n_cols} columns do not fit an int32 sort key")
    pos = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
    k = jnp.sort((c << p_bits) | pos, axis=1)
    c_s, pos_s = k >> p_bits, k & ((1 << p_bits) - 1)
    first = jnp.concatenate([jnp.ones_like(c_s[:, :1], bool), c_s[:, 1:] != c_s[:, :-1]], 1)
    k = jnp.sort((jnp.where(first, pos_s, draws) << c_bits) | c_s, axis=1)[:, :cap]
    return k & ((1 << c_bits) - 1), k >> c_bits


def _pick_columns(key, lens, table, n_cols: int, cap: int, draws: int | None):
    """(R, cap) sorted distinct columns per row, ``n_cols`` past each row's
    length, and how many rows drew fewer distinct columns than their length.

    Uniform (``table`` None): the ``len`` largest of ``n_cols`` uniform keys.
    Weighted: ``draws`` independent draws from the alias ``table``; the first
    ``len`` distinct in draw order are a weighted sample without replacement
    (successive sampling, the law of Gumbel-top-k)."""
    import jax
    import jax.numpy as jnp

    keep = jnp.arange(cap)[None, :] < lens[:, None]
    if table is None:
        _, idx = jax.lax.top_k(jax.random.uniform(key, (lens.shape[0], n_cols)), cap)
        short = jnp.int32(0)
    else:
        k_col, k_keep = jax.random.split(key)
        shape = (lens.shape[0], draws)
        i = jax.random.randint(k_col, shape, 0, n_cols)
        c = jnp.where(jax.random.uniform(k_keep, shape) < table[0][i], i, table[1][i])
        idx, at = _first_distinct(c, cap, n_cols)
        short = jnp.sum(jnp.any(keep & (at >= draws), axis=1))
    dtype = jnp.int16 if n_cols <= np.iinfo(np.int16).max else jnp.int32
    return jnp.sort(jnp.where(keep, idx, n_cols), axis=1).astype(dtype), short


def _pow2_above(n: int) -> int:
    """The least power of two at or above ``n`` (at least 2)."""
    return 1 << max(n - 1, 1).bit_length()


def rows_per_call(width: int) -> int:
    """The most rows one column-draw call may take with ``width`` keys per row."""
    return max(1, ARRAY_BYTES // (4 * width))


def _columns(lens: np.ndarray, indptr: np.ndarray, n_cols: int, longest: int,
             law: dict | None, seed: int) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    key = jax.random.key(device_seed(seed, "columns"))
    p = column_probabilities(n_cols, law)
    table = None if p is None else tuple(jnp.asarray(a) for a in _alias(n_cols, _law_key(law)))
    indices = np.empty(int(indptr[-1]), np.int32)

    def run(rows, n_rows, per_call, cap, draws, salt, flat=None):
        """Draw ``n_rows`` columns for each of ``rows`` (ascending): into
        ``indices`` at the rows' places, or appended to ``flat`` in row order."""
        pick = jax.jit(partial(_pick_columns, n_cols=n_cols, cap=cap, draws=draws))
        pending = collections.deque()

        def fetch():
            (cols, short), chunk, n = pending.popleft()
            if int(short):
                raise RuntimeError(f"{int(short)} rows drew fewer than their length "
                                   f"in {draws} draws")
            got = np.asarray(cols)[: chunk.shape[0]][np.arange(cap)[None, :] < n[:, None]]
            if flat is not None:
                flat.append(got)
                return
            # each row's first entry, less the entries of the rows before it in the chunk
            at = np.repeat(indptr[chunk] - (np.cumsum(n) - n), n)
            at += np.arange(at.shape[0])
            indices[at] = got

        for i, lo in enumerate(range(0, rows.shape[0], per_call)):
            chunk, n = rows[lo : lo + per_call], n_rows[lo : lo + per_call]
            part = np.zeros(per_call, np.int32)
            part[: chunk.shape[0]] = n
            pending.append((pick(jax.random.fold_in(key, salt + i), jnp.asarray(part), table),
                            chunk, n))
            if len(pending) > IN_FLIGHT:
                fetch()
        while pending:
            fetch()

    # one pass per cap: a row goes to the first that holds it.  Uniform: the
    # wide pass walks every row (the others with length 0) in chunks of at
    # most the power of two above n_rows, and its columns land through one
    # mask; the long pass takes LONG_CHUNK_ROWS at a time, fewer where it
    # sorts whole rows.  Weighted: caps double, and each pass walks its own
    # rows only, in calls as large as the budget takes.
    if p is None:
        caps = [c for c in (SHORT_CAP,) if c < longest] + [longest]
    else:
        caps = [c for c in (SHORT_CAP << i for i in range(32)) if c < longest] + [longest]
    for i, cap in enumerate(caps):
        in_pass = (lens > (caps[i - 1] if i else 0)) & (lens <= cap)
        rows = np.flatnonzero(in_pass)
        if not rows.size:
            continue
        if p is None and i == 0:
            wide, flat = min(CHUNK_ROWS, _pow2_above(lens.shape[0]), rows_per_call(n_cols)), []
            run(np.arange(lens.shape[0]), np.where(in_pass, lens, 0), wide, cap, None, 0, flat)
            flat = np.concatenate(flat)
            if rows.size == lens.shape[0]:
                indices[:] = flat
            else:
                indices[np.repeat(in_pass, lens)] = flat
        elif p is None:
            width = n_cols * (SORT_COPIES if cap > TOPK_CAP else 1)
            run(rows, lens[rows], min(LONG_CHUNK_ROWS, rows_per_call(width)), cap, None, i << 20)
        else:
            draws = draws_for(n_cols, law, cap)
            run(rows, lens[rows], min(rows_per_call(draws), _pow2_above(rows.size)), cap,
                draws, i << 20)
    return indices


def make_collection(cfg: dict, seed: int) -> Collection:
    """The configuration's collection, deterministic in ``seed``."""
    n_rows, n_cols = cfg["n_rows"], cfg["n_cols"]
    rng = rng_for(seed, "collection")
    lens = row_lengths(n_rows, n_cols, cfg["row_length"], rng)
    indptr = np.zeros(n_rows + 1, np.int64)
    np.cumsum(lens, out=indptr[1:])
    data = values(rng, int(indptr[-1]), cfg.get("value_law", "normal"))
    for lo in range(0, n_rows, NORM_ROWS):   # in blocks of rows, to bound the temporaries
        hi = min(lo + NORM_ROWS, n_rows)
        block = data[indptr[lo] : indptr[hi]]
        norms = np.sqrt(np.add.reduceat(block * block, indptr[lo:hi] - indptr[lo]),
                        dtype=np.float32)
        block /= np.repeat(norms, lens[lo:hi])
    indices = _columns(lens, indptr, n_cols, longest_row(n_cols, cfg["row_length"]),
                       cfg.get("column_law"), seed)
    return Collection(indptr, indices, data, n_cols)


def queries(cfg: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """(n, n_cols) float32 queries under the configuration's ``queries`` law."""
    n_cols = cfg["n_cols"]
    law = cfg.get("queries", {"kind": "dense_normal"})
    if law["kind"] == "dense_normal":
        return rng.standard_normal((n, n_cols), dtype=np.float32)
    if law["kind"] != "sparse":
        raise ValueError(f"unknown query law {law['kind']!r}")
    lens = row_lengths(n, n_cols, law["nnz"], rng)
    col_law = law.get("column_law", "collection")
    col_law = cfg.get("column_law") if col_law == "collection" else col_law
    top = int(lens.max())
    draws = draws_for(n_cols, col_law, top)
    stay, alias = _alias(n_cols, _law_key(col_law))   # as the device draws columns
    i = rng.integers(0, n_cols, (n, draws))
    c = np.where(rng.random((n, draws)) < stay[i], i, alias[i])
    # the first ``len`` distinct columns of each row in draw order, as on the device
    order = np.argsort(c, axis=1, stable=True)
    c_s = np.take_along_axis(c, order, 1)
    first = np.ones(c.shape, bool)
    first[:, 1:] = c_s[:, 1:] != c_s[:, :-1]
    at = np.where(first, order, draws)
    pick = np.argsort(at, axis=1, stable=True)[:, :top]
    keep = np.arange(top)[None, :] < lens[:, None]
    if (np.take_along_axis(at, pick, 1)[keep] >= draws).any():
        raise RuntimeError(f"a query drew fewer distinct columns than its length in {draws}")
    vals = values(rng, (n, top), law.get("value_law", "normal"))
    vals[~keep] = 0.0
    vals /= np.sqrt(np.add.reduce(vals * vals, axis=1, keepdims=True))
    row, slot = np.nonzero(keep)
    out = np.zeros((n, n_cols), np.float32)
    out[row, np.take_along_axis(c_s, pick, 1)[row, slot]] = vals[row, slot]
    return out


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

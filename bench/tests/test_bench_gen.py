"""The generator: the paper's laws drawn as before, the general laws, and their budget."""
import hashlib
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import gen, loops, run  # noqa: E402

LAW = {"kind": "gamma", "shape": 3.0, "scale": 4 / 3, "mean": 20.0}
SEED = 2**31 + 12_345


def test_length_law_mean_and_bounds():
    lens = gen.row_lengths(400_000, 512, LAW, gen.rng_for(SEED, "collection"))
    assert lens.mean() == pytest.approx(20.0, abs=0.1)
    assert lens.min() >= 1 and lens.max() <= 512
    assert (lens > 64).any()   # the long-row path is exercised at this law


@pytest.fixture(scope="module")
def small():
    cfg = run.load_json(ROOT / "bench" / "tests" / "small.json")
    return cfg, gen.make_collection(cfg, SEED)


def test_columns_distinct_sorted_in_range(small):
    cfg, c = small
    lens = np.diff(c.indptr)
    assert c.indices.shape == (c.nnz,) and c.data.shape == (c.nnz,)
    step = np.diff(c.indices.astype(np.int64))
    inside = np.ones(c.nnz - 1, bool)
    inside[c.indptr[1:-1] - 1] = False          # pairs that cross a row boundary
    assert (step[inside] > 0).all()
    assert c.indices.min() >= 0 and c.indices.max() < cfg["n_cols"]
    assert (lens > gen.SHORT_CAP).any()


def test_rows_have_unit_norm(small):
    _, c = small
    sq = np.add.reduceat(c.data.astype(np.float64) ** 2, c.indptr[:-1])
    assert np.abs(sq - 1).max() < 1e-5


def test_deterministic_per_seed(small):
    cfg, c = small
    again = gen.make_collection(cfg, SEED)
    other = gen.make_collection(cfg, SEED + 1)
    for a, b in ((c.indptr, again.indptr), (c.indices, again.indices), (c.data, again.data)):
        np.testing.assert_array_equal(a, b)
    assert not np.array_equal(c.indptr, other.indptr)


def test_open_schedule_has_the_same_work_for_every_seed():
    traffic = {"rate_per_s": 7.0, "update_share": 0.5, "update_key_theta": 0.99}
    for seed in (1, SEED, 3**30):
        s = gen.open_schedule(traffic, 1000, 10.0, gen.rng_for(seed, "schedule"))
        assert s.due.shape == (70,) and s.is_update.sum() == 35
        assert (np.diff(s.due) >= 0).all() and 0 <= s.due.min() and s.due.max() < 10.0
        assert s.update_ids.shape == (35,) and s.update_ids.max() < 1000


def test_zipf_ids_are_skewed_and_scrambled():
    ids = gen.zipf_ids(gen.rng_for(5, "z"), 20_000, 10_000, 0.99)
    assert ids.min() >= 0 and ids.max() < 10_000
    counts = np.bincount(ids.astype(np.int64), minlength=10_000)
    hottest = int(np.argmax(counts))
    assert counts[hottest] > 20_000 / 20     # rank 0 draws about 1/zeta(10^4, 0.99) ~ 10%
    assert hottest == int(gen.fnv1a64(np.array([0], np.uint64))[0] % 10_000)


# -- the paper cell's inputs, as they were drawn before the laws were general --

DIGESTS = {   # sha256 of small.json's collection and first three Q=8 query blocks
    "indptr": "a9bc48f5eaf35def10224218721eabb8909656a8bdde2cac763098c8c937d8a1",
    "indices": "a7e035509a94f3df634d04a3648a19083e3a31924612301c8543c1c01843135c",
    "data": "b7b7a9e6a96f314f6d5018ec47141a1852147de5d8dc2a42ead994b1c0ef3a52",
    "queries": "821c123d333dbc65f694a1e52f4a8f884314c7d2f026c4b102cfea0e5c6abe81",
}


def _sha(a: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


class _RecordingService:
    """Answers instantly and records each block; the third call outlasts the window."""

    def __init__(self, window_s: float):
        self.blocks, self.window_s = [], window_s

    def search(self, xs):
        self.blocks.append(np.array(xs))
        if len(self.blocks) == 3:
            time.sleep(self.window_s)
        return np.zeros((xs.shape[0], 16)), np.zeros((xs.shape[0], 16), np.int64)


def test_paper_inputs_are_drawn_as_before(small):
    """The default laws draw today's collection and query stream, bit for bit."""
    cfg, c = small
    for name in ("indptr", "indices", "data"):
        assert _sha(getattr(c, name)) == DIGESTS[name], name
    svc = _RecordingService(0.3)
    loops.ClosedBatch(svc, None, cfg, {"q": 8}, SEED).run(0.2)
    assert len(svc.blocks) == 3
    assert _sha(np.stack(svc.blocks)) == DIGESTS["queries"]


# -- the general laws ------------------------------------------------------------

LOGNORMAL = {"kind": "lognormal", "mean": 126.0, "sigma": 0.6}


@pytest.mark.parametrize("law, n_cols, mean, top", [
    (LOGNORMAL, 30_522, 126.0, 30_522),                    # the tail is never clipped
    (dict(LOGNORMAL, max=256), 30_522, None, 256),         # clipped at the law's max
    (dict(LOGNORMAL, max=256), 100, None, 100),            # and at the width
    ({"kind": "lognormal", "mean": 49.0, "sigma": 0.5, "max": 256}, 30_522, 49.0, 256),
])
def test_lognormal_length_law(law, n_cols, mean, top):
    lens = gen.row_lengths(400_000, n_cols, law, gen.rng_for(SEED, "collection"))
    assert lens.dtype == np.int64 and lens.min() >= 1 and lens.max() <= top
    assert gen.longest_row(n_cols, law) == top
    if mean is not None:
        assert lens.mean() == pytest.approx(mean, rel=0.005)
    else:
        assert lens.max() == top and lens.mean() < law["mean"]


def _check_rows(c, n_cols: int) -> None:
    """Distinct sorted columns in range, unit rows."""
    step = np.diff(c.indices.astype(np.int64))
    inside = np.ones(c.nnz - 1, bool)
    inside[c.indptr[1:-1] - 1] = False
    assert (step[inside] > 0).all()
    assert c.indices.min() >= 0 and c.indices.max() < n_cols
    sq = np.add.reduceat(c.data.astype(np.float64) ** 2, c.indptr[:-1])
    assert np.abs(sq - 1).max() < 1e-5


def _sparse_cfg(**over) -> dict:
    cfg = run.load_json(ROOT / "bench" / "tests" / "small_sparse.json")
    cfg.update(n_rows=2_000, n_cols=2_048, row_length=dict(LOGNORMAL, mean=40.0, max=512))
    cfg.update(over)
    return cfg


def _by_rank(counts: np.ndarray, law: dict) -> np.ndarray:
    """Counts per column id reordered by the law's popularity rank, hottest first."""
    p = gen.column_probabilities(counts.shape[0], law)
    return counts if p is None else counts[np.argsort(-p, kind="stable")]


@pytest.mark.parametrize("law", [{"kind": "uniform"}, {"kind": "zipf", "exponent": 1.0}])
def test_column_law(law):
    """Columns are distinct, sorted and in range; their frequency follows the law."""
    cfg = _sparse_cfg(column_law=law)
    c = gen.make_collection(cfg, SEED)
    _check_rows(c, cfg["n_cols"])
    counts = _by_rank(np.bincount(c.indices, minlength=cfg["n_cols"]), law)
    head, body, tail = counts[:32].mean(), counts[256:512].mean(), counts[-1024:].mean()
    if law["kind"] == "uniform":
        assert head == pytest.approx(tail, rel=0.1) and body == pytest.approx(tail, rel=0.1)
    else:   # frequency falls with popularity rank; the hottest sit in nearly every row
        assert head > 5 * body and body > 2.5 * tail   # 1/rank: ~3.8 from body to tail
        assert counts[0] > 0.9 * cfg["n_rows"]
    assert (gen.make_collection(cfg, SEED).indices == c.indices).all()


def test_zipf_popularity_is_one_fixed_scatter():
    law = {"kind": "zipf", "exponent": 1.0}
    p = gen.column_probabilities(30_522, law)
    assert p is gen.column_probabilities(30_522, dict(law)) and not p.flags.writeable  # made once
    assert p.sum() == pytest.approx(1.0)
    ranked = np.sort(p)[::-1]
    np.testing.assert_allclose(ranked[:3] / ranked[0], [1, 1 / 2, 1 / 3])
    assert np.argsort(-p)[:100].max() > 20_000              # the hot columns scatter


def test_alias_table_draws_the_law():
    p = gen.column_probabilities(1_000, {"kind": "zipf", "exponent": 1.0})
    keep, alias = gen.alias_table(p)
    back = keep.astype(np.float64) / 1_000
    np.add.at(back, alias, (1 - keep.astype(np.float64)) / 1_000)
    np.testing.assert_allclose(back, p, rtol=1e-5, atol=1e-9)


@pytest.mark.parametrize("law", ["normal", "half_normal"])
def test_value_law(law):
    cfg = _sparse_cfg(value_law=law)
    c = gen.make_collection(cfg, SEED)
    _check_rows(c, cfg["n_cols"])
    assert ((c.data < 0).mean() > 0.4) if law == "normal" else (c.data >= 0).all()


def test_sparse_queries_follow_their_laws():
    cfg = _sparse_cfg()
    xs = gen.queries(cfg, gen.rng_for(SEED, "queries"), 4_000)
    assert xs.shape == (4_000, cfg["n_cols"]) and xs.dtype == np.float32
    nnz = (xs != 0).sum(axis=1)
    law = cfg["queries"]["nnz"]
    assert nnz.mean() == pytest.approx(law["mean"], rel=0.02)
    assert nnz.min() >= 1 and nnz.max() <= law["max"]
    assert (xs >= 0).all()
    np.testing.assert_allclose(np.sqrt((xs.astype(np.float64) ** 2).sum(axis=1)), 1.0, atol=1e-6)
    counts = _by_rank((xs != 0).sum(axis=0), cfg["column_law"])   # "collection": its law
    assert counts[:32].mean() > 5 * counts[256:512].mean()
    again = gen.queries(cfg, gen.rng_for(SEED, "queries"), 4_000)
    np.testing.assert_array_equal(xs, again)


def test_dense_queries_are_the_default():
    cfg = {"n_cols": 512}
    xs = gen.queries(cfg, gen.rng_for(SEED, "q"), 64)
    np.testing.assert_array_equal(
        xs, gen.rng_for(SEED, "q").standard_normal((64, 512), dtype=np.float32))


def test_column_draw_fits_its_budget_at_30522_columns(monkeypatch):
    """At the learned-sparse width every device call's keys fit the budget.

    The budget is cut to 32 MiB so the CPU holds the calls; at the real one
    the rows per call follow from the same rule."""
    cfg = run.load_json(ROOT / "bench" / "tests" / "splade_shape.json")
    cfg["n_rows"] = 2_000
    assert gen.rows_per_call(30_522) * 30_522 * 4 <= gen.ARRAY_BYTES
    assert gen.rows_per_call(512) == gen.CHUNK_ROWS   # the paper's calls keep their size
    monkeypatch.setattr(gen, "ARRAY_BYTES", 32 << 20)
    calls, real = [], gen._pick_columns

    def spy(key, lens, table, n_cols, cap, draws):
        calls.append((lens.shape[0], n_cols if table is None else draws, cap))
        return real(key, lens, table, n_cols=n_cols, cap=cap, draws=draws)

    monkeypatch.setattr(gen, "_pick_columns", spy)
    c = gen.make_collection(cfg, SEED)
    _check_rows(c, 30_522)
    assert {cap for _, _, cap in calls} == {64, 128, 256, 512, 1024}   # caps double
    for rows, width, _ in calls:
        assert rows * width * 4 <= 32 << 20

"""The generator: the paper's row-length law, distinct sorted columns, unit rows."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import gen, run  # noqa: E402

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

"""A whole closed-batch run on the CPU under the learned-sparse laws.

As ``test_bench_check_batch.py``, with ``small_sparse.json``: 4,096
columns, lognormal row lengths, Zipf columns, half-normal values and sparse
queries.  A sound run is correct; the program's Q7 path (the control), an
altered answer and half the batch left out are not.  A traffic file's
``check_answers`` compares a sample of the answers drawn from the seed, and a
configuration's ``limits`` replace those of ``limits.json``.
"""
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402
from test_bench_check_batch import _wrap_dispatch  # noqa: E402

SEED = 2**31 + 78


def go(tmp_path, traffic=None, limits=None, **kw):
    cfg = run.load_json(ROOT / "bench" / "tests" / "small_sparse.json")
    if limits is not None:
        cfg["limits"] = limits
    e2e = [{"name": "setup_s", "unit": "s"}, {"name": "qps", "unit": "queries/s"}]
    cell = run.Cell("small_sparse.batch", 1, cfg, dict({"loop": "closed_batch", "q": 8},
                                                       **(traffic or {})), e2e, [])
    return run.run_cell(cell, SEED, 1.0, False, require_tpu=False, out_dir=tmp_path, **kw)


def test_sound_run_is_correct(tmp_path):
    res = go(tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_control_q7_is_not_correct(tmp_path):
    res = go(tmp_path, value_format="Q7")
    assert res["checks"]["score_gap"]["value"] > res["checks"]["score_gap"]["limit"]
    assert not res["correct"]


def test_altered_answer_is_not_correct(tmp_path, monkeypatch):
    def alter(v, r, xs):
        r[-1, 0] = next(i for i in range(r.shape[1] + 1) if i not in set(r[-1].tolist()))
        return v, r

    _wrap_dispatch(monkeypatch, alter)
    res = go(tmp_path)
    assert res["checks"]["row_score_gap"]["value"] > res["checks"]["row_score_gap"]["limit"]
    assert not res["correct"]


def test_half_the_batch_left_out_is_not_correct(tmp_path, monkeypatch):
    def alter(v, r, xs):
        h = v.shape[0] // 2
        v[h:], r[h:] = v[: v.shape[0] - h], r[: v.shape[0] - h]
        return v, r

    _wrap_dispatch(monkeypatch, alter)
    res = go(tmp_path)
    assert res["checks"]["score_gap"]["value"] > res["checks"]["score_gap"]["limit"]
    assert not res["correct"]


def _alter_every_answer(v, r, xs):
    for row in r:
        row[0] = next(i for i in range(row.shape[0] + 1) if i not in set(row.tolist()))
    return v, r


@pytest.mark.parametrize("fault", [None, _alter_every_answer])
def test_checked_sample_is_drawn_from_the_seed(tmp_path, monkeypatch, capsys, fault):
    if fault is not None:
        _wrap_dispatch(monkeypatch, fault)
    res = go(tmp_path, traffic={"check_answers": 3})
    compared, answered = map(int, re.search(r"compared (\d+) of (\d+) answers",
                                            capsys.readouterr().err).groups())
    assert answered == res["attempted"] >= 8 and compared == 3
    assert res["correct"] is (fault is None), res["checks"]


def test_configuration_limits_replace_the_defaults(tmp_path):
    res = go(tmp_path, limits={"score_gap": 0.25})
    assert res["checks"]["score_gap"]["limit"] == 0.25
    assert res["checks"]["row_score_gap"]["limit"] == run.limits_for({})["row_score_gap"]
    assert res["correct"], res["checks"]

"""A whole run of the closed-batch cell on the CPU, sound, as the control, and faulty.

The harness's look for a chip is skipped (``require_tpu=False``); everything
else is the run as the chip makes it, at a size the CPU holds.  The control
serves the collection through the program's own Q7 path (the precision below
the configured BF16) while the reference stays BF16; each fault breaks the
timed path where the answer is produced.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402

SEED = 2**31 + 77


def cell():
    cfg = run.load_json(ROOT / "bench" / "tests" / "small.json")
    e2e = [{"name": "setup_s", "unit": "s"}, {"name": "qps", "unit": "queries/s"}]
    return run.Cell("small.batch", 1, cfg, {"loop": "closed_batch", "q": 8}, e2e, [])


def go(tmp_path, **kw):
    return run.run_cell(cell(), SEED, 1.0, False, require_tpu=False, out_dir=tmp_path, **kw)


def test_sound_run_is_correct(tmp_path):
    res = go(tmp_path)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "qps"}


def test_control_q7_is_not_correct(tmp_path):
    res = go(tmp_path, value_format="Q7")
    assert not res["correct"]
    assert res["checks"]["score_gap"]["value"] > res["checks"]["score_gap"]["limit"]


def _wrap_dispatch(monkeypatch, alter):
    from repro.core.similarity import SparseEmbeddingIndex

    real = SparseEmbeddingIndex._dispatch_batch

    def broken(self, xs, use_kernel):
        v, r = real(self, xs, use_kernel)
        return alter(np.array(v), np.array(r), xs)

    monkeypatch.setattr(SparseEmbeddingIndex, "_dispatch_batch", broken)


def test_altered_answer_is_not_correct(tmp_path, monkeypatch):
    def alter(v, r, xs):
        r[-1, 0] = next(i for i in range(r.shape[1] + 1) if i not in set(r[-1].tolist()))
        return v, r

    _wrap_dispatch(monkeypatch, alter)
    assert not go(tmp_path)["correct"]


def test_half_the_batch_left_out_is_not_correct(tmp_path, monkeypatch):
    def alter(v, r, xs):
        h = v.shape[0] // 2
        v[h:], r[h:] = v[: v.shape[0] - h], r[: v.shape[0] - h]
        return v, r

    _wrap_dispatch(monkeypatch, alter)
    res = go(tmp_path)
    assert not res["correct"]
    assert res["checks"]["score_gap"]["value"] > res["checks"]["score_gap"]["limit"]

"""A whole run of the live open-loop cell on the CPU, sound and with updates dropped.

As in ``test_bench_check_batch.py`` the look for a chip is skipped and the
rest of the run is the chip's, at a size the CPU holds: Poisson arrivals,
half of them row replaces through ``ingest`` with a probe after each.
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402

SEED = 2**31 + 99
TRAFFIC = {"loop": "open", "rate_per_s": 8.0, "update_share": 0.5, "update_key_theta": 0.99}


def go(tmp_path):
    cfg = run.load_json(ROOT / "bench" / "tests" / "small.json")
    e2e = [{"name": n, "unit": "ms"} for n in ("setup_s", "qps", "p95_ms", "visible_p90_ms")]
    cell = run.Cell("small.churn", 1, cfg, TRAFFIC, e2e, [])
    return run.run_cell(cell, SEED, 2.0, False, require_tpu=False, out_dir=tmp_path)


def test_sound_run_is_correct(tmp_path):
    res = go(tmp_path)
    assert res["correct"], res["checks"]
    assert res["checks"]["probes_missed"]["value"] == 0
    assert res["failed"] == 0 and res["attempted"] >= 16
    assert res["metrics"]["visible_p90_ms"]["value"] > 0


def test_update_left_unapplied_is_not_correct(tmp_path, monkeypatch):
    """A replace that returns with the index unchanged."""
    from repro.core.topk_spmv import MutableTopKSpMVIndex

    monkeypatch.setattr(MutableTopKSpMVIndex, "replace_rows", lambda self, ids, rows: None)
    res = go(tmp_path)
    assert not res["correct"]
    assert res["checks"]["probes_missed"]["value"] > 0

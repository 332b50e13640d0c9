"""The roofline yardstick: the configuration's work and the peaks table."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run, work  # noqa: E402

PAPER = run.load_json(ROOT / "bench" / "configs" / "paper-10m-bf16.json")


def test_pass_work_by_hand_for_the_paper_config():
    nnz, q = 1000, 64
    # BF16 value 16 bits + ceil(log2 512) = 9 column bits + 1 row-boundary bit = 26 bits
    want_bytes = nnz * 26 / 8 + q * 512 * 4 + q * PAPER["big_k"] * 8
    assert want_bytes == 3250 + 131072 + 51200
    assert work.pass_bytes(nnz, PAPER["n_cols"], PAPER["value_format"], q,
                           PAPER["big_k"]) == want_bytes
    assert work.pass_flops(nnz, q) == 128_000
    least = work.least_seconds([(nnz, q)], 512, "BF16", 100, "TPU v5 lite")
    assert least == pytest.approx(max(want_bytes / 819e9, 128_000 / 197e12))


def test_full_size_pass_is_about_650_mb():
    assert work.pass_bytes(200_000_000, 512, "BF16", 64, 100) == pytest.approx(650.18e6, rel=1e-3)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def _reader(name):
    return run.load_reader(name)


@pytest.mark.parametrize("layout", [
    {}, {"stream_layout": "split"}, {"block_size": 128}, {"partitions": 7},
])
def test_roofline_reads_the_configuration_not_the_stream(layout):
    """Changing how the program lays out its stream leaves the share unchanged."""
    cfg = dict(PAPER, **layout)
    trace = SimpleNamespace(kernel_seconds=lambda name: 2.0 if name == "bscsr_topk_spmv_multiquery" else 0.0)
    ctx = SimpleNamespace(cfg=cfg, trace=trace, loop=SimpleNamespace(passes=lambda: [64, 64, 32]),
                          live_nnz=200_000_000, device_kind="TPU v5 lite")
    got = _reader("bscsr_topk_spmv_roofline.batch")(ctx)
    least = sum(work.pass_bytes(200_000_000, 512, "BF16", q, 100) / 819e9 for q in (64, 64, 32))
    assert got == pytest.approx(100 * least / 2.0)
    assert _reader("bscsr_topk_spmv_roofline.served")(ctx) == got


def test_roofline_is_silent_without_kernel_time():
    trace = SimpleNamespace(kernel_seconds=lambda name: 0.0)
    ctx = SimpleNamespace(cfg=PAPER, trace=trace, loop=SimpleNamespace(passes=lambda: [64]),
                          live_nnz=10, device_kind="TPU v5 lite")
    assert _reader("bscsr_topk_spmv_roofline.batch")(ctx) is None


def test_work_imports_nothing_of_the_program():
    src = (ROOT / "bench" / "work.py").read_text()
    assert "repro" not in src and "stream_bytes" not in src

"""Fused single-stream packet layout: roundtrip, kernel parity, hardening.

The fused layout packs each tile-packet's ``flags | cols | vals`` into one
contiguous int32 word row (one HBM burst per grid step); the in-kernel
shift/mask decode must be *bit-exact*, so every fused result is asserted
bit-identical to the split three-array path — across all ``ValueFormat``s,
all four ``inner_loop`` modes, single and multi-query kernels, and
delta-segmented mutable indexes.  Stage-1 gather hardening (explicit
clip+mask x-gather) gets regression coverage with poisoned padding col ids.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import jax
import repro.core as core
from repro.core import bscsr
from repro.core.topk_spmv import MutableTopKSpMVIndex, TopKSpMVConfig
from repro.kernels import ops
from repro.kernels.bscsr_topk_spmv import (
    INNER_LOOPS,
    bscsr_topk_spmv,
    bscsr_topk_spmv_multiquery,
)

FORMATS = ["F32", "BF16", "Q15", "Q7"]


def make_problem(n_rows=300, n_cols=128, mean_nnz=12, seed=0):
    csr = bscsr.synthetic_embedding_csr(n_rows, n_cols, mean_nnz, "gamma", seed)
    x = np.random.default_rng(seed + 1).standard_normal(n_cols).astype(np.float32)
    return csr, x


def assert_bit_identical(a, b):
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


class TestFuseRoundtrip:
    """encode -> fuse -> defuse must reproduce the split arrays bit-for-bit."""

    def _assert_roundtrip(self, e: bscsr.BSCSRMatrix):
        words = e.fused_words()
        assert words.dtype == np.int32
        wf, wc, wv = bscsr.fused_word_counts(
            e.block_size, e.value_format, e.cols.dtype
        )
        assert words.shape == (e.num_packets, wf + wc + wv)
        vals, cols, flags = bscsr.defuse_stream(
            words, e.block_size, e.value_format, e.cols.dtype
        )
        # Values compare as raw bytes: bf16/f32 NaN payloads must survive too.
        np.testing.assert_array_equal(
            np.ascontiguousarray(vals).view(np.uint8),
            np.ascontiguousarray(e.vals).view(np.uint8),
        )
        np.testing.assert_array_equal(cols, e.cols)
        np.testing.assert_array_equal(flags, e.flags)

    @pytest.mark.parametrize("fmt", FORMATS)
    def test_random_stream_all_formats(self, fmt):
        csr, _ = make_problem(seed=2)
        self._assert_roundtrip(bscsr.encode_bscsr(csr, 64, fmt))

    @pytest.mark.parametrize("fmt", FORMATS)
    def test_empty_rows_and_padding(self, fmt):
        lens = np.zeros(30, np.int64)
        lens[::4] = 3
        indptr = np.concatenate([[0], np.cumsum(lens)])
        rng = np.random.default_rng(3)
        idx = np.concatenate(
            [np.sort(rng.choice(64, size=l, replace=False)) for l in lens if l]
        ).astype(np.int32)
        data = rng.standard_normal(int(lens.sum())).astype(np.float32)
        csr = bscsr.CSRMatrix(indptr, idx, data, (30, 64))
        e = bscsr.encode_bscsr(csr, 32, fmt, pad_packets_to=6)
        self._assert_roundtrip(e)

    def test_multi_packet_rows(self):
        csr, _ = make_problem(n_rows=10, n_cols=256, mean_nnz=100, seed=4)
        self._assert_roundtrip(bscsr.encode_bscsr(csr, 32, "BF16"))

    @pytest.mark.parametrize("fmt", FORMATS)
    def test_delta_append_roundtrip(self, fmt):
        csr, _ = make_problem(n_rows=50, seed=5)
        base = bscsr.encode_bscsr(csr, 32, fmt)
        rng = np.random.default_rng(6)
        rows = [
            (np.sort(rng.choice(128, size=4, replace=False)).astype(np.int32),
             rng.standard_normal(4).astype(np.float32)),
            (np.zeros(0, np.int32), np.zeros(0, np.float32)),  # empty delta row
        ]
        delta = bscsr.encode_delta_rows(rows, 128, 32, fmt)
        merged = bscsr.append_packets(base, delta, pad_packets_to=20)
        self._assert_roundtrip(merged)
        # fusing segment-wise == fusing the concatenated stream
        np.testing.assert_array_equal(
            merged.fused_words()[: base.num_packets], base.fused_words()
        )

    def test_int32_cols_roundtrip(self):
        # n_cols beyond int16 forces the 1-col-per-word section
        csr = bscsr.synthetic_embedding_csr(40, 40_000, 6, "uniform", 7)
        e = bscsr.encode_bscsr(csr, 32, "F32")
        assert e.cols.dtype == np.int32
        self._assert_roundtrip(e)

    def test_width_mismatch_rejected(self):
        csr, _ = make_problem(n_rows=20, seed=8)
        e = bscsr.encode_bscsr(csr, 32, "F32")
        with pytest.raises(ValueError):
            bscsr.defuse_stream(e.fused_words()[:, :-1], 32, "F32", e.cols.dtype)


class TestFusedKernelParity:
    """Fused decode is bit-exact -> results bit-identical to split."""

    @pytest.mark.parametrize("inner_loop", INNER_LOOPS)
    @pytest.mark.parametrize("fmt", ["F32", "Q7"])
    def test_single_query_all_inner_loops(self, inner_loop, fmt):
        csr, x = make_problem(seed=10)
        split = ops.pack_partitions(csr, 4, 64, fmt)
        fused = ops.pack_partitions(csr, 4, 64, fmt, stream_layout="fused")
        a = ops.topk_spmv_blocked(jnp.asarray(x), split, 16, inner_loop=inner_loop)
        b = ops.topk_spmv_blocked(jnp.asarray(x), fused, 16, inner_loop=inner_loop)
        assert_bit_identical(a, b)

    @pytest.mark.parametrize("inner_loop", INNER_LOOPS)
    def test_multiquery_all_inner_loops(self, inner_loop):
        csr, _ = make_problem(seed=11)
        split = ops.pack_partitions(csr, 4, 64, "Q15")
        fused = ops.pack_partitions(csr, 4, 64, "Q15", stream_layout="fused")
        xs = np.random.default_rng(12).standard_normal((5, 128)).astype(np.float32)
        a = ops.topk_spmv_batched(jnp.asarray(xs), split, 16, inner_loop=inner_loop)
        b = ops.topk_spmv_batched(jnp.asarray(xs), fused, 16, inner_loop=inner_loop)
        assert_bit_identical(a, b)

    @pytest.mark.parametrize("fmt", ["BF16", "Q15"])
    def test_layout_override_derives_words(self, fmt):
        """A split snapshot queried with stream_layout="fused" fuses on the fly."""
        csr, x = make_problem(seed=13)
        split = ops.pack_partitions(csr, 4, 64, fmt)
        assert split.words is None
        a = ops.topk_spmv_blocked(jnp.asarray(x), split, 16)
        b = ops.topk_spmv_blocked(jnp.asarray(x), split, 16, stream_layout="fused")
        assert_bit_identical(a, b)

    @pytest.mark.parametrize("gather", ["take", "onehot"])
    def test_gather_modes_on_fused(self, gather):
        csr, x = make_problem(seed=14)
        fused = ops.pack_partitions(csr, 4, 64, "F32", stream_layout="fused")
        split = ops.pack_partitions(csr, 4, 64, "F32")
        a = ops.topk_spmv_blocked(jnp.asarray(x), split, 16, gather_mode=gather)
        b = ops.topk_spmv_blocked(jnp.asarray(x), fused, 16, gather_mode=gather)
        assert_bit_identical(a, b)

    def test_mutable_index_delta_segments(self):
        """Fused == split through add/replace/delete delta segments."""
        csr, x = make_problem(n_rows=200, n_cols=64, mean_nnz=8, seed=15)
        rng = np.random.default_rng(16)

        def rand_row():
            cols = np.sort(rng.choice(64, size=5, replace=False)).astype(np.int32)
            return cols, rng.standard_normal(5).astype(np.float32)

        indexes = []
        for layout in ("split", "fused"):
            rng = np.random.default_rng(16)  # identical mutation sequence
            cfg = TopKSpMVConfig(big_k=10, k=16, num_partitions=4, block_size=32,
                                 stream_layout=layout)
            idx = MutableTopKSpMVIndex(csr, cfg)
            idx.add_rows([rand_row() for _ in range(7)])
            idx.replace_rows([3, 50], [rand_row(), rand_row()])
            idx.delete_rows([10, 11])
            indexes.append(idx)
        split_idx, fused_idx = indexes
        assert fused_idx.packed.words is not None
        for use_kernel in (True, False):
            a = core.topk_spmv(split_idx, jnp.asarray(x), use_kernel=use_kernel)
            b = core.topk_spmv(fused_idx, jnp.asarray(x), use_kernel=use_kernel)
            assert_bit_identical(a, b)

    def test_distributed_one_device_fused(self):
        csr, _ = make_problem(n_rows=256, seed=17)
        xs = np.random.default_rng(18).standard_normal((3, 128)).astype(np.float32)
        mesh = jax.make_mesh((1,), ("data",))
        results = []
        for layout in ("split", "fused"):
            idx = core.build_index(csr, TopKSpMVConfig(
                big_k=12, k=8, num_partitions=4, block_size=64,
                stream_layout=layout))
            fn, arrays = core.distributed_topk_spmv_fn(idx, mesh, batched=True)
            assert len(arrays) == (1 if layout == "fused" else 3)
            results.append(fn(jnp.asarray(xs), *arrays))
        assert_bit_identical(results[0], results[1])


def poison_padding(packed: ops.PackedPartitions) -> ops.PackedPartitions:
    """Overwrite col ids of sentinel/padding stream entries with garbage."""
    cols = packed.cols.copy()
    rows_per = packed.candidate_slots
    for ci in range(packed.num_cores):
        flags = bscsr.unpack_bits(packed.flags[ci], packed.block_size).reshape(-1)
        row_ids = np.cumsum(flags) - 1
        pad = (row_ids >= rows_per[ci]).reshape(cols[ci].shape)
        c = cols[ci].copy()
        c[pad] = 30_000 if c.dtype == np.int16 else 2**30  # far out of range
        half = pad.copy()
        half[::2] = False
        c[half] = -7                                       # negative garbage too
        cols[ci] = c
    import dataclasses
    poisoned = dataclasses.replace(packed, cols=cols, words=None)
    if packed.stream_layout == "fused":
        poisoned = dataclasses.replace(poisoned, words=poisoned.fused_words())
    return poisoned


class TestGatherHardening:
    """Garbage col ids in padding must never change (or NaN) the results."""

    @pytest.mark.parametrize("layout", ["split", "fused"])
    @pytest.mark.parametrize("gather", ["take", "onehot"])
    def test_mostly_padding_partition(self, layout, gather):
        # 3 tiny rows padded to 8 packets: the stream is ~95% padding.
        csr, x = make_problem(n_rows=3, n_cols=64, mean_nnz=4, seed=20)
        plan = core.PartitionPlan.build(3, 1)
        e = bscsr.encode_bscsr(csr, 32, "F32", pad_packets_to=8)
        packed = ops.stack_streams([e], plan, 64, csr.nnz,
                                   stream_layout=layout)
        clean = ops.topk_spmv_blocked(jnp.asarray(x), packed, 3,
                                      gather_mode=gather)
        dirty = ops.topk_spmv_blocked(jnp.asarray(x), poison_padding(packed), 3,
                                      gather_mode=gather)
        assert np.isfinite(np.asarray(clean[0])[:3]).all()
        assert_bit_identical(clean, dirty)

    @pytest.mark.parametrize("layout", ["split", "fused"])
    def test_multiquery_poisoned_padding(self, layout):
        csr, _ = make_problem(n_rows=40, n_cols=64, mean_nnz=5, seed=21)
        packed = ops.pack_partitions(csr, 4, 32, "F32", stream_layout=layout)
        xs = np.random.default_rng(22).standard_normal((4, 64)).astype(np.float32)
        clean = ops.topk_spmv_batched(jnp.asarray(xs), packed, 8)
        dirty = ops.topk_spmv_batched(jnp.asarray(xs), poison_padding(packed), 8)
        assert_bit_identical(clean, dirty)


class TestAutoGatherMode:
    def test_resolves_to_supported_mode(self):
        assert ops.resolve_gather_mode("auto") == "onehot"
        assert ops.resolve_gather_mode("onehot") == "onehot"
        assert ops.resolve_gather_mode("take") == "take"
        csr, x = make_problem(n_rows=40, n_cols=64, mean_nnz=5, seed=24)
        packed = ops.pack_partitions(csr, 2, 32, "F32", stream_layout="fused")
        with pytest.raises(ValueError, match="interpret-only"):
            ops.topk_spmv_blocked(jnp.asarray(x), packed, 8,
                                  gather_mode="take", interpret=False)

    def test_auto_config_end_to_end(self):
        csr, x = make_problem(n_rows=150, seed=23)
        idx = core.build_index(csr, TopKSpMVConfig(
            big_k=10, k=8, num_partitions=2, block_size=64, gather_mode="auto"))
        a = core.topk_spmv(idx, jnp.asarray(x))
        b = ops.topk_spmv_blocked(jnp.asarray(x), idx.packed, 10,
                                  gather_mode="onehot")
        assert_bit_identical(a, b)

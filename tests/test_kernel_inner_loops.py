"""Linear-time kernel inner loops: parity vs the legacy paths + batched API.

The new stage-2 (cumsum-difference segmented sum) and stage-4
(threshold-filter-then-merge) inner loops must reproduce the legacy
(one-hot matmul / k-pass argmax) results: identical rows, values within
float-summation-order tolerance — across value formats, gather modes,
empty-row streams, and rows spanning packet boundaries.  No optional test
deps here so this coverage always runs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as core
from repro.core import bscsr
from repro.core import partition as partition_lib
from repro.kernels import ops, ref
from repro.kernels.bscsr_topk_spmv import (
    bscsr_topk_spmv,
    bscsr_topk_spmv_multiquery,
)

FORMATS = ["F32", "BF16", "Q15", "Q7"]


def make_problem(n_rows=300, n_cols=128, mean_nnz=12, dist="gamma", seed=0):
    csr = bscsr.synthetic_embedding_csr(n_rows, n_cols, mean_nnz, dist, seed)
    x = np.random.default_rng(seed + 1).standard_normal(n_cols).astype(np.float32)
    return csr, x


def csr_with_empty_rows(n_rows=120, n_cols=64, seed=0):
    """Every third row empty — exercises the placeholder-0 stream rule."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(1, 10, size=n_rows)
    lens[::3] = 0
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    idx = np.concatenate(
        [np.sort(rng.choice(n_cols, size=l, replace=False)) for l in lens if l]
    ).astype(np.int32)
    data = rng.standard_normal(int(lens.sum())).astype(np.float32)
    return bscsr.CSRMatrix(indptr, idx, data, (n_rows, n_cols))


def run_blocked(csr, x, inner_loop, fmt="F32", cores=4, block=64, big_k=16,
                k=8, t_step=2, gather_mode="take"):
    packed = ops.pack_partitions(csr, cores, block, fmt, packets_multiple=t_step)
    return ops.topk_spmv_blocked(
        jnp.asarray(x), packed, big_k, k=k, packets_per_step=t_step,
        gather_mode=gather_mode, inner_loop=inner_loop,
    )


def assert_rows_equal_vals_close(a, b, rtol=1e-5, atol=1e-5):
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]),
                               rtol=rtol, atol=atol)


class TestLinearVsLegacy:
    """The new inner loops against the old ones, stage by stage."""

    @pytest.mark.parametrize("fmt", FORMATS)
    @pytest.mark.parametrize("gather", ["take", "onehot"])
    def test_full_linear_parity(self, fmt, gather):
        csr, x = make_problem()
        new = run_blocked(csr, x, "linear", fmt=fmt, gather_mode=gather)
        old = run_blocked(csr, x, "legacy", fmt=fmt, gather_mode=gather)
        assert_rows_equal_vals_close(new, old)

    @pytest.mark.parametrize("fmt", FORMATS)
    def test_threshold_merge_bitwise_parity(self, fmt):
        """Stage 4 alone does no new arithmetic -> bit-identical to k-pass."""
        csr, x = make_problem(seed=7)
        new = run_blocked(csr, x, "linear-topk", fmt=fmt)
        old = run_blocked(csr, x, "legacy", fmt=fmt)
        np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(old[1]))
        np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(old[0]))

    @pytest.mark.parametrize("fmt", FORMATS)
    def test_cumsum_reduce_parity(self, fmt):
        """Stage 2 alone: only float summation order changes."""
        csr, x = make_problem(seed=5)
        new = run_blocked(csr, x, "linear-seg", fmt=fmt)
        old = run_blocked(csr, x, "legacy", fmt=fmt)
        assert_rows_equal_vals_close(new, old)

    @pytest.mark.parametrize("inner_loop", ["linear", "legacy"])
    def test_exact_oracle_f32(self, inner_loop):
        """k == K per core -> global top-k exact vs the numpy CSR oracle."""
        csr, x = make_problem(n_rows=333)
        kv, kr = run_blocked(csr, x, inner_loop, big_k=10, k=10)
        ev, er = core.topk_spmv_exact(csr, x, 10)
        np.testing.assert_allclose(np.asarray(kv), ev, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(kr), er)

    def test_rows_spanning_packet_boundaries(self):
        """mean row length >> block size: the carry path does the work."""
        csr, x = make_problem(n_rows=40, n_cols=128, mean_nnz=50, seed=3)
        new = run_blocked(csr, x, "linear", cores=2, block=32)
        old = run_blocked(csr, x, "legacy", cores=2, block=32)
        assert_rows_equal_vals_close(new, old)
        ev, er = core.topk_spmv_exact(csr, x, 16)
        np.testing.assert_allclose(np.asarray(new[0])[:8], ev[:8], rtol=1e-5)

    def test_empty_rows_and_placeholders(self):
        csr = csr_with_empty_rows()
        x = np.random.default_rng(9).standard_normal(64).astype(np.float32)
        new = run_blocked(csr, x, "linear", cores=3, block=32)
        old = run_blocked(csr, x, "legacy", cores=3, block=32)
        assert_rows_equal_vals_close(new, old)
        ev, er = core.topk_spmv_exact(csr, x, 16)
        np.testing.assert_allclose(np.asarray(new[0])[:8], ev[:8], rtol=1e-5)

    @pytest.mark.parametrize("t_step", [1, 2, 4])
    def test_packets_per_step(self, t_step):
        csr, x = make_problem(n_rows=200)
        new = run_blocked(csr, x, "linear", cores=2, block=32, big_k=8,
                          t_step=t_step)
        old = run_blocked(csr, x, "legacy", cores=2, block=32, big_k=8,
                          t_step=t_step)
        assert_rows_equal_vals_close(new, old)

    def test_single_packet_partition(self):
        """Whole partition in one packet: init + emit on the same step."""
        csr, x = make_problem(n_rows=20, n_cols=32, mean_nnz=3, seed=2)
        new = run_blocked(csr, x, "linear", cores=1, block=128, big_k=8,
                          t_step=1)
        old = run_blocked(csr, x, "legacy", cores=1, block=128, big_k=8,
                          t_step=1)
        assert_rows_equal_vals_close(new, old)


class TestMultiQueryParity:
    @pytest.mark.parametrize("fmt", ["F32", "Q7"])
    @pytest.mark.parametrize("inner_loop", ["linear", "legacy"])
    def test_multiquery_matches_single(self, fmt, inner_loop):
        csr, _ = make_problem(n_rows=300, seed=11)
        packed = ops.pack_partitions(csr, 4, 64, fmt)
        xs = np.random.default_rng(12).standard_normal((4, 128)).astype(np.float32)
        max_rows = int(max(packed.plan.rows_per_partition))
        args = (jnp.asarray(packed.vals), jnp.asarray(packed.cols),
                jnp.asarray(packed.flags))
        mv, mr = bscsr_topk_spmv_multiquery(
            jnp.asarray(xs), *args, k=8, n_rows=max_rows, fmt_name=fmt,
            inner_loop=inner_loop, interpret=True,
        )
        for q in range(xs.shape[0]):
            sv, sr = bscsr_topk_spmv(
                jnp.asarray(xs[q]), *args, k=8, n_rows=max_rows, fmt_name=fmt,
                inner_loop=inner_loop, interpret=True,
            )
            np.testing.assert_allclose(np.asarray(mv[:, q]), np.asarray(sv),
                                       rtol=1e-6, atol=1e-6)
            np.testing.assert_array_equal(np.asarray(mr[:, q]), np.asarray(sr))

    def test_multiquery_linear_vs_legacy(self):
        csr, _ = make_problem(n_rows=250, seed=13)
        packed = ops.pack_partitions(csr, 4, 64, "F32")
        xs = np.random.default_rng(14).standard_normal((6, 128)).astype(np.float32)
        new = ops.topk_spmv_batched(jnp.asarray(xs), packed, 16, k=8,
                                    inner_loop="linear")
        old = ops.topk_spmv_batched(jnp.asarray(xs), packed, 16, k=8,
                                    inner_loop="legacy")
        assert_rows_equal_vals_close(new, old)


class TestBatchedAPI:
    def test_ops_batched_matches_blocked(self):
        csr, _ = make_problem(n_rows=300, seed=21)
        packed = ops.pack_partitions(csr, 4, 64, "F32")
        xs = np.random.default_rng(22).standard_normal((5, 128)).astype(np.float32)
        bv, br = ops.topk_spmv_batched(jnp.asarray(xs), packed, 16, k=8)
        for q in range(xs.shape[0]):
            sv, sr = ops.topk_spmv_blocked(jnp.asarray(xs[q]), packed, 16, k=8)
            np.testing.assert_allclose(np.asarray(bv[q]), np.asarray(sv),
                                       rtol=1e-6, atol=1e-6)
            np.testing.assert_array_equal(np.asarray(br[q]), np.asarray(sr))

    def test_batched_reference_matches_kernel(self):
        csr, _ = make_problem(n_rows=300, seed=23)
        packed = ops.pack_partitions(csr, 4, 64, "BF16")
        xs = np.random.default_rng(24).standard_normal((3, 128)).astype(np.float32)
        kv, kr = ops.topk_spmv_batched(jnp.asarray(xs), packed, 16, k=8)
        rv, rr = ops.topk_spmv_reference_batched(jnp.asarray(xs), packed, 16, k=8)
        assert_rows_equal_vals_close((kv, kr), (rv, rr))

    def test_core_batched_api(self):
        csr, _ = make_problem(n_rows=256, seed=25)
        idx = core.build_index(csr, core.TopKSpMVConfig(
            big_k=16, k=8, num_partitions=4, block_size=64))
        xs = np.random.default_rng(26).standard_normal((4, 128)).astype(np.float32)
        bv, br = core.topk_spmv_batched(idx, jnp.asarray(xs))
        rv, rr = core.topk_spmv_batched(idx, jnp.asarray(xs), use_kernel=False)
        assert_rows_equal_vals_close((bv, br), (rv, rr))
        for q in range(4):
            sv, sr = core.topk_spmv(idx, jnp.asarray(xs[q]))
            np.testing.assert_array_equal(np.asarray(br[q]), np.asarray(sr))

    def test_distributed_batched_one_device(self):
        csr, _ = make_problem(n_rows=256, seed=27)
        idx = core.build_index(csr, core.TopKSpMVConfig(
            big_k=12, k=8, num_partitions=4, block_size=64))
        xs = np.random.default_rng(28).standard_normal((3, 128)).astype(np.float32)
        mesh = jax.make_mesh((1,), ("data",))
        fn, arrays = core.distributed_topk_spmv_fn(idx, mesh, batched=True)
        dv, dr = fn(jnp.asarray(xs), *arrays)
        bv, br = core.topk_spmv_batched(idx, jnp.asarray(xs))
        np.testing.assert_allclose(np.asarray(dv), np.asarray(bv),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(dr), np.asarray(br))

    def test_head_batch_matches_single(self):
        from repro.serve.topk_head import ApproxTopKHead, TopKHeadConfig

        emb = np.random.default_rng(30).standard_normal((256, 32)).astype(np.float32)
        head = ApproxTopKHead(emb, TopKHeadConfig(
            big_k=16, k=8, num_partitions=4, nnz_per_row=16, block_size=32,
            value_format="F32"))
        hs = np.random.default_rng(31).standard_normal((4, 32)).astype(np.float32)
        bv, br = head.topk_logits_batch(hs)
        assert bv.shape == (4, 16) and br.shape == (4, 16)
        for i, h in enumerate(hs):
            sv, sr = head.topk_logits(h)
            np.testing.assert_array_equal(br[i], sr)
            np.testing.assert_allclose(bv[i], sv, rtol=1e-5, atol=1e-5)


class TestHostPacking:
    def test_pad_packets_matches_encoder_padding(self):
        """In-place padding == re-encoding with pad_packets_to (all formats)."""
        csr, _ = make_problem(n_rows=150, seed=41)
        plan = partition_lib.PartitionPlan.build(csr.shape[0], 3)
        for fmt in FORMATS:
            for part in partition_lib.partition_csr(csr, plan):
                e = bscsr.encode_bscsr(part, 64, fmt)
                padded = bscsr.pad_packets(e, e.num_packets + 3)
                ref_enc = bscsr.encode_bscsr(part, 64, fmt,
                                             pad_packets_to=e.num_packets + 3)
                np.testing.assert_array_equal(
                    np.asarray(padded.vals, np.float32),
                    np.asarray(ref_enc.vals, np.float32))
                np.testing.assert_array_equal(padded.cols, ref_enc.cols)
                np.testing.assert_array_equal(padded.flags, ref_enc.flags)
                assert padded.nnz == e.nnz and padded.n_rows == e.n_rows

    def test_pad_packets_rejects_shrink(self):
        csr, _ = make_problem(n_rows=50, seed=42)
        e = bscsr.encode_bscsr(csr, 32)
        with pytest.raises(ValueError):
            bscsr.pad_packets(e, e.num_packets - 1)

    def test_pack_partitions_step_aligned(self):
        csr, _ = make_problem(n_rows=333, seed=43)
        packed = ops.pack_partitions(csr, 4, 64, "F32", packets_multiple=4)
        assert packed.vals.shape[1] % 4 == 0
        assert packed.vals.shape == packed.cols.shape

    def test_vectorized_reference_matches_exact(self):
        """The vmapped per-core oracle on ragged partitions (masked padding
        rows must never displace real candidates)."""
        csr, x = make_problem(n_rows=333, seed=44)
        packed = ops.pack_partitions(csr, 5, 64, "F32")  # ragged: 67/67/67/66/66
        rv, rr = ops.topk_spmv_reference(jnp.asarray(x), packed, big_k=10, k=10)
        ev, er = core.topk_spmv_exact(csr, x, 10)
        np.testing.assert_allclose(np.asarray(rv), ev, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(rr), er)

"""Device-resident snapshot plane: parity, pinning, invalidation, zero copies.

The executor must be a pure dispatch optimization: every answer bit-identical
to the per-call-upload helpers in ``kernels/ops.py`` across inner loops,
stream layouts and value formats (including Q-bucket padding).  Device pins
must follow snapshot identity — version bumps and ``compact()`` invalidate,
garbage collection evicts — and the steady-state dispatch must perform ZERO
host->device transfers (asserted under ``jax.transfer_guard``).
"""
import gc

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import bscsr
from repro.core.topk_spmv import (
    MutableTopKSpMVIndex,
    TopKSpMVConfig,
    query_executor,
    topk_spmv,
    topk_spmv_batched,
)
from repro.kernels import executor as executor_lib
from repro.kernels import ops
from repro.kernels.bscsr_topk_spmv import INNER_LOOPS

FORMATS = ["F32", "BF16", "Q15", "Q7"]
LAYOUTS = ["split", "fused"]
BIG_K = 10


def make_problem(n_rows=150, n_cols=64, mean_nnz=8, seed=0):
    csr = bscsr.synthetic_embedding_csr(n_rows, n_cols, mean_nnz, "gamma", seed)
    x = np.random.default_rng(seed + 1).standard_normal(n_cols).astype(np.float32)
    return csr, x


def assert_bit_identical(a, b):
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


class TestExecutorParity:
    """Executor answers == per-call-upload dispatch, bit for bit."""

    @pytest.mark.parametrize("fmt", FORMATS)
    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_single_query_all_inner_loops(self, fmt, layout):
        csr, x = make_problem(seed=2)
        packed = ops.pack_partitions(csr, 2, 32, fmt, stream_layout=layout)
        xd = jnp.asarray(x)
        for loop in INNER_LOOPS:
            ex = executor_lib.QueryExecutor(big_k=BIG_K, k=8, inner_loop=loop)
            got = ex.query(xd, packed)
            want = ops.topk_spmv_blocked(xd, packed, BIG_K, k=8, inner_loop=loop)
            assert_bit_identical(got, want)

    @pytest.mark.parametrize("fmt", FORMATS)
    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_batched_query_with_bucket_padding(self, fmt, layout):
        csr, _ = make_problem(seed=3)
        packed = ops.pack_partitions(csr, 2, 32, fmt, stream_layout=layout)
        xs = np.random.default_rng(4).standard_normal((5, 64)).astype(np.float32)
        ex = executor_lib.QueryExecutor(big_k=BIG_K, k=8)
        got = ex.query_batched(jnp.asarray(xs), packed)  # Q=5 pads to bucket 8
        assert got[0].shape == (5, BIG_K)
        want = ops.topk_spmv_batched(jnp.asarray(xs), packed, BIG_K, k=8)
        assert_bit_identical(got, want)

    @pytest.mark.parametrize("loop", INNER_LOOPS)
    def test_batched_inner_loops(self, loop):
        csr, _ = make_problem(seed=5)
        packed = ops.pack_partitions(csr, 2, 32, "F32", stream_layout="fused")
        xs = np.random.default_rng(6).standard_normal((4, 64)).astype(np.float32)
        ex = executor_lib.QueryExecutor(big_k=BIG_K, k=8, inner_loop=loop)
        got = ex.query_batched(jnp.asarray(xs), packed)
        want = ops.topk_spmv_batched(
            jnp.asarray(xs), packed, BIG_K, k=8, inner_loop=loop
        )
        assert_bit_identical(got, want)

    def test_reference_path(self):
        csr, x = make_problem(seed=7)
        packed = ops.pack_partitions(csr, 2, 32, "F32", stream_layout="fused")
        ex = executor_lib.QueryExecutor(big_k=BIG_K, k=8)
        got = ex.query(jnp.asarray(x), packed, path="reference")
        want = ops.topk_spmv_reference(jnp.asarray(x), packed, BIG_K, k=8)
        assert_bit_identical(got, want)
        xs = np.random.default_rng(8).standard_normal((3, 64)).astype(np.float32)
        got = ex.query_batched(jnp.asarray(xs), packed, path="reference")
        want = ops.topk_spmv_reference_batched(jnp.asarray(xs), packed, BIG_K, k=8)
        assert_bit_identical(got, want)

    def test_segmented_snapshot_parity(self):
        """Delta segments + tombstones flow through the executor unchanged."""
        csr, x = make_problem(seed=9)
        cfg = TopKSpMVConfig(big_k=BIG_K, k=16, num_partitions=2, block_size=32)
        index = MutableTopKSpMVIndex(csr, cfg)
        rng = np.random.default_rng(10)
        index.add_rows([(np.arange(6, dtype=np.int32),
                         rng.standard_normal(6).astype(np.float32))])
        index.delete_rows([3, 7])
        assert index.packed.has_tombstones
        xd = jnp.asarray(x)
        got = query_executor(cfg).query(xd, index.packed)
        want = ops.topk_spmv_blocked(
            xd, index.packed, BIG_K, k=16,
            gather_mode=ops.resolve_gather_mode("auto"),
        )
        assert_bit_identical(got, want)


class TestDevicePinning:
    def test_snapshot_pinned_once_and_fns_cached(self):
        csr, x = make_problem(seed=11)
        packed = ops.pack_partitions(csr, 2, 32, "F32", stream_layout="fused")
        ex = executor_lib.QueryExecutor(big_k=BIG_K, k=8)
        xd = jnp.asarray(x)
        a = ex.query(xd, packed)
        builds = ex.fn_builds
        b = ex.query(xd, packed)
        assert ex.fn_builds == builds  # cache hit: no rebuild
        assert ex.dispatches == 2
        assert_bit_identical(a, b)
        # one device pin for this uid; repeated lookups return the same object
        snap1 = executor_lib.device_snapshot(packed)
        snap2 = executor_lib.device_snapshot(packed)
        assert snap1 is snap2

    def test_gc_evicts_device_pin(self):
        csr, x = make_problem(seed=12)
        packed = ops.pack_partitions(csr, 2, 32, "F32", stream_layout="fused")
        ex = executor_lib.QueryExecutor(big_k=BIG_K, k=8)
        ex.query(jnp.asarray(x), packed)
        # cache key: (uid, layout, row_map_key, device, packets_per_step) —
        # no row map and no explicit device pin on this plain dispatch
        key = (packed.uid, "fused", None, None, ex.packets_per_step)
        assert key in executor_lib._DEVICE_CACHE
        del packed
        gc.collect()
        assert key not in executor_lib._DEVICE_CACHE

    def test_stale_fns_evicted_under_churn(self):
        """Without churn-stable bucketing every refresh changes the shape
        signature; dead signatures' fns must be evicted or a long-lived
        service leaks compiled executables.  (With ``churn_stable`` — the
        default — signatures are reused instead; see TestChurnStable.)"""
        csr, x = make_problem(seed=18)
        cfg = TopKSpMVConfig(big_k=BIG_K, k=16, num_partitions=2, block_size=32,
                             churn_stable=False)
        index = MutableTopKSpMVIndex(csr, cfg)
        ex = executor_lib.QueryExecutor(big_k=BIG_K, k=16)
        xd = jnp.asarray(x)
        rng = np.random.default_rng(19)
        for _ in range(4):
            ex.query(xd, index.packed)
            index.add_rows([(np.arange(5, dtype=np.int32),
                             rng.standard_normal(5).astype(np.float32))])
            gc.collect()
        assert ex.fn_builds >= 4          # churn really did retrace
        assert len(ex._fns) <= 2          # but only live signatures survive

    def test_version_bump_invalidates(self):
        """A mutable-index refresh pins the NEW snapshot; answers track it."""
        csr, x = make_problem(seed=13)
        cfg = TopKSpMVConfig(big_k=BIG_K, k=16, num_partitions=2, block_size=32)
        index = MutableTopKSpMVIndex(csr, cfg)
        xd = jnp.asarray(x)
        topk_spmv(index, xd)
        uid0 = index.packed.uid
        # upsert a row that must become the top hit for query x
        gid = index.add_rows([self._aligned_row(x)])[0]
        assert index.packed.uid != uid0
        _, rows = topk_spmv(index, xd)
        assert int(np.asarray(rows)[0]) == gid
        want = ops.topk_spmv_blocked(
            xd, index.packed, BIG_K, k=16,
            gather_mode=ops.resolve_gather_mode("auto"),
        )
        assert_bit_identical(topk_spmv(index, xd), want)

    def test_compact_invalidates(self):
        csr, x = make_problem(seed=14)
        cfg = TopKSpMVConfig(big_k=BIG_K, k=16, num_partitions=2, block_size=32)
        index = MutableTopKSpMVIndex(csr, cfg)
        xd = jnp.asarray(x)
        gid = index.add_rows([self._aligned_row(x)])[0]
        index.delete_rows([1])
        topk_spmv(index, xd)
        index.compact()
        _, rows = topk_spmv(index, xd)
        assert int(np.asarray(rows)[0]) == gid
        assert 1 not in set(np.asarray(rows).tolist())
        want = ops.topk_spmv_blocked(
            xd, index.packed, BIG_K, k=16,
            gather_mode=ops.resolve_gather_mode("auto"),
        )
        assert_bit_identical(topk_spmv(index, xd), want)

    @staticmethod
    def _aligned_row(x, nnz=8):
        cols = np.argsort(-np.abs(x))[:nnz].astype(np.int32)
        cols.sort()
        return cols, (10.0 * np.sign(x[cols]) * np.ones(nnz)).astype(np.float32)


class TestZeroTransfer:
    """Steady-state dispatch must move NOTHING host->device."""

    def test_steady_state_zero_transfers(self):
        csr, x = make_problem(seed=15)
        cfg = TopKSpMVConfig(big_k=BIG_K, k=16, num_partitions=2, block_size=32)
        index = MutableTopKSpMVIndex(csr, cfg)
        xd = jnp.asarray(x)
        xs = jnp.asarray(
            np.random.default_rng(16).standard_normal((3, 64)).astype(np.float32)
        )
        # warm: pins the snapshot, compiles the fns (incl. the Q=3->4 padder)
        warm = [
            topk_spmv(index, xd),
            topk_spmv(index, xd, use_kernel=False),
            topk_spmv_batched(index, xs),
            topk_spmv_batched(index, xs, use_kernel=False),
        ]
        with jax.transfer_guard_host_to_device("disallow"):
            cold = [
                topk_spmv(index, xd),
                topk_spmv(index, xd, use_kernel=False),
                topk_spmv_batched(index, xs),
                topk_spmv_batched(index, xs, use_kernel=False),
            ]
            for (_, r) in cold:
                r.block_until_ready()
        for a, b in zip(warm, cold):
            assert_bit_identical(a, b)

    def test_legacy_dispatch_does_transfer(self):
        """The baseline per-call upload path trips the guard — the contrast
        that proves the executor actually removed the transfers."""
        csr, x = make_problem(seed=17)
        packed = ops.pack_partitions(csr, 2, 32, "F32", stream_layout="fused")
        xd = jnp.asarray(x)
        ops.topk_spmv_blocked(xd, packed, BIG_K, k=8)  # warm compile caches
        with pytest.raises(Exception):
            with jax.transfer_guard_host_to_device("disallow"):
                ops.topk_spmv_blocked(xd, packed, BIG_K, k=8)[0].block_until_ready()


class TestChurnStable:
    """Churn-stable signatures: zero retraces under ingest, padded parity.

    The hazard being guarded (see the scratch-shape analysis in
    ``bscsr_topk_spmv.py``): a padded per-core slot budget must never let a
    phantom zero-score slot displace a real negative-score candidate in the
    k-sized scratchpad.  Parity is therefore asserted bit-identically
    against the unpadded (``churn_stable=False``) path on matrices whose
    true top-k scores are ALL negative.
    """

    @staticmethod
    def _negative_problem(n_rows=60, n_cols=32, mean_nnz=6, seed=21):
        """A collection whose every live score is strictly negative."""
        base = bscsr.synthetic_embedding_csr(
            n_rows, n_cols, mean_nnz, "gamma", seed, normalize=False
        )
        csr = bscsr.CSRMatrix(
            indptr=base.indptr,
            indices=base.indices,
            data=(-np.abs(base.data) - 0.01).astype(np.float32),
            shape=base.shape,
        )
        x = np.abs(
            np.random.default_rng(seed + 1).standard_normal(n_cols)
        ).astype(np.float32) + 0.1
        return csr, x

    @staticmethod
    def _mutate(index, rng):
        """Identical churn for both arms: appends, a replace and a delete."""
        index.add_rows([
            (np.arange(5, dtype=np.int32),
             -np.abs(rng.standard_normal(5)).astype(np.float32) - 0.01)
            for _ in range(2)
        ])
        index.replace_rows([4], [(
            np.arange(4, dtype=np.int32),
            -np.abs(rng.standard_normal(4)).astype(np.float32) - 0.01,
        )])
        index.delete_rows([9])

    def _arms(self):
        csr, x = self._negative_problem()
        arms = []
        for stable in (True, False):
            cfg = TopKSpMVConfig(
                big_k=BIG_K, k=8, num_partitions=2, block_size=32,
                churn_stable=stable,
            )
            index = MutableTopKSpMVIndex(csr, cfg)
            self._mutate(index, np.random.default_rng(22))
            arms.append(index)
        padded, exact = arms
        # The premise: the stable arm really is padded past the live counts.
        info = padded.packed.signature_info()
        assert info["slot_bucket"] > info["slots_live"]
        assert info["tombstone_bucket"] > info["rows_live"]
        assert padded.packed.max_slots > exact.packed.max_slots
        return padded, exact, x

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_negative_score_padded_parity_all_loops(self, layout):
        padded, exact, x = self._arms()
        xd = jnp.asarray(x)
        for loop in INNER_LOOPS:
            got = ops.topk_spmv_blocked(
                xd, padded.packed, BIG_K, k=8, inner_loop=loop,
                stream_layout=layout,
            )
            want = ops.topk_spmv_blocked(
                xd, exact.packed, BIG_K, k=8, inner_loop=loop,
                stream_layout=layout,
            )
            # the premise again: the true top-k really is negative
            assert float(np.asarray(want[0])[0]) < 0
            assert_bit_identical(got, want)

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_negative_score_padded_parity_batched(self, layout):
        padded, exact, x = self._arms()
        xs = jnp.asarray(np.stack([x, 2.0 * x, 0.5 * x]))
        for loop in INNER_LOOPS:
            got = ops.topk_spmv_batched(
                xs, padded.packed, BIG_K, k=8, inner_loop=loop,
                stream_layout=layout,
            )
            want = ops.topk_spmv_batched(
                xs, exact.packed, BIG_K, k=8, inner_loop=loop,
                stream_layout=layout,
            )
            assert float(np.asarray(want[0])[0, 0]) < 0
            assert_bit_identical(got, want)

    def test_negative_score_padded_parity_reference_and_executor(self):
        padded, exact, x = self._arms()
        xd = jnp.asarray(x)
        ex = executor_lib.QueryExecutor(big_k=BIG_K, k=8)
        for path in ("kernel", "reference"):
            assert_bit_identical(
                ex.query(xd, padded.packed, path=path),
                ex.query(xd, exact.packed, path=path),
            )

    def test_zero_retrace_across_upsert_query_cycles(self):
        """3 consecutive upsert->query cycles: the refresh re-pins arrays but
        never rebuilds a compiled fn (trace counter), and repeated queries
        between mutations still move zero bytes host->device."""
        csr, x = make_problem(seed=23)
        cfg = TopKSpMVConfig(big_k=BIG_K, k=16, num_partitions=2, block_size=32)
        index = MutableTopKSpMVIndex(csr, cfg)
        ex = executor_lib.QueryExecutor(big_k=BIG_K, k=16)
        xd = jnp.asarray(x)
        xs = jnp.asarray(
            np.random.default_rng(24).standard_normal((3, 64)).astype(np.float32)
        )
        rng = np.random.default_rng(25)
        # Warm into the steady state: the FIRST append past the build-time
        # packet cap is a one-time cold event (the cap jumps to its pow2
        # bucket); everything after serves from stable signatures.
        ex.query(xd, index.packed)
        ex.query_batched(xs, index.packed)
        index.add_rows([(np.arange(5, dtype=np.int32),
                         rng.standard_normal(5).astype(np.float32))])
        ex.query(xd, index.packed)
        ex.query_batched(xs, index.packed)
        builds = ex.fn_builds
        retraces = ex.retraces
        for _ in range(3):
            index.add_rows([(np.arange(5, dtype=np.int32),
                             rng.standard_normal(5).astype(np.float32))])
            # first post-upsert query pins the new snapshot (one upload)...
            first = ex.query(xd, index.packed)
            firstb = ex.query_batched(xs, index.packed)
            # ...but compiles nothing, and steady queries transfer nothing.
            with jax.transfer_guard_host_to_device("disallow"):
                again = ex.query(xd, index.packed)
                againb = ex.query_batched(xs, index.packed)
                again[1].block_until_ready()
                againb[1].block_until_ready()
            assert_bit_identical(first, again)
            assert_bit_identical(firstb, againb)
        assert ex.fn_builds == builds
        assert ex.retraces == retraces

    def test_delete_keeps_signature_stable(self):
        """The first delete flips tombstone VALUES, not the signature — the
        bitmap rides along (bucket-padded) from the very first snapshot."""
        csr, x = make_problem(seed=26)
        cfg = TopKSpMVConfig(big_k=BIG_K, k=16, num_partitions=2, block_size=32)
        index = MutableTopKSpMVIndex(csr, cfg)
        ex = executor_lib.QueryExecutor(big_k=BIG_K, k=16)
        xd = jnp.asarray(x)
        # warm past the one-time packet-cap jump of the first-ever mutation
        bottom = int(np.asarray(ex.query(xd, index.packed)[1])[-1])
        index.delete_rows([bottom])
        before = ex.query(xd, index.packed)
        builds = ex.fn_builds
        retraces = ex.retraces
        target = int(np.asarray(before[1])[0])  # the current top hit
        index.delete_rows([target])
        _, rows = ex.query(xd, index.packed)
        assert target not in set(np.asarray(rows).tolist())
        assert ex.fn_builds == builds and ex.retraces == retraces

    def test_unstable_config_still_retraces(self):
        """The knob works both ways: churn_stable=False restores the exact
        dims, so the same churn really does change signatures."""
        csr, x = make_problem(seed=27)
        cfg = TopKSpMVConfig(big_k=BIG_K, k=16, num_partitions=2, block_size=32,
                             churn_stable=False)
        index = MutableTopKSpMVIndex(csr, cfg)
        ex = executor_lib.QueryExecutor(big_k=BIG_K, k=16)
        xd = jnp.asarray(x)
        ex.query(xd, index.packed)
        rng = np.random.default_rng(28)
        index.add_rows([(np.arange(5, dtype=np.int32),
                         rng.standard_normal(5).astype(np.float32))])
        gc.collect()  # the replaced snapshot must be dead to count as churn
        ex.query(xd, index.packed)
        assert ex.retraces == 1

    def test_second_collection_is_first_touch_not_retrace(self):
        """Two collections with different shapes sharing one executor: each
        first query is a first-touch build, and alternating between the
        LIVE collections afterwards is pure cache hits — `retraces` must
        stay 0 (it is the churn health signal, docs/SERVING.md)."""
        csr_a, x = make_problem(seed=29)
        csr_b, _ = make_problem(n_rows=77, seed=30)
        cfg = TopKSpMVConfig(big_k=BIG_K, k=16, num_partitions=2, block_size=32)
        a = MutableTopKSpMVIndex(csr_a, cfg)
        b = MutableTopKSpMVIndex(csr_b, cfg)
        assert a.packed.signature_info() != b.packed.signature_info()
        ex = executor_lib.QueryExecutor(big_k=BIG_K, k=16)
        xd = jnp.asarray(x)
        for _ in range(2):
            ex.query(xd, a.packed)
            ex.query(xd, b.packed)
        assert ex.fn_builds == 2
        assert ex.retraces == 0

    @staticmethod
    def _seg_index():
        """A churn-stable index at E = 256 entries per step (cap 384 slots),
        warmed past the first mutation's packet-cap jump; rows of ~8
        entries bound its segment space at 128.  Its slot, tombstone and
        packet buckets have room for the churn below, so only the segment
        space can move a signature."""
        csr, x = make_problem(n_rows=600, seed=31)
        cfg = TopKSpMVConfig(big_k=BIG_K, k=8, num_partitions=2, block_size=128)
        index = MutableTopKSpMVIndex(csr, cfg)
        ex = executor_lib.QueryExecutor(big_k=BIG_K, k=8)
        xs = jnp.asarray(np.stack([x, -x]))
        index.add_rows([(np.arange(5, dtype=np.int32),
                         np.ones(5, np.float32))])
        ex.query_batched(xs, index.packed)
        assert ex.cache_info()["seg_slots"] == {128: 1}
        return index, ex, xs

    def test_seg_slots_zero_retrace_inside_bucket(self):
        index, ex, xs = self._seg_index()
        retraces, builds = ex.retraces, ex.fn_builds
        rng = np.random.default_rng(32)
        for _ in range(3):
            index.add_rows([(np.sort(rng.choice(64, 6, replace=False)).astype(np.int32),
                             rng.standard_normal(6).astype(np.float32))
                            for _ in range(4)])
            gc.collect()
            ex.query_batched(xs, index.packed)
            assert ex.cache_info()["seg_slots"] == {128: 1}
        assert (ex.retraces, ex.fn_builds) == (retraces, builds)

    def test_seg_slots_bucket_crossing_retraces_once(self):
        """Many one-entry rows put 128+ row starts in one step: the bound
        moves to the next bucket (256), one counted retrace, and the
        answers are still the reference's."""
        index, ex, xs = self._seg_index()
        retraces = ex.retraces
        buckets = {k: v for k, v in index.packed.signature_info().items()
                   if k.endswith("_bucket")}
        rng = np.random.default_rng(33)
        index.add_rows([(np.array([j % 64], np.int32),
                         rng.standard_normal(1).astype(np.float32))
                        for j in range(260)])
        assert buckets == {k: v for k, v in index.packed.signature_info().items()
                           if k.endswith("_bucket")}
        gc.collect()   # the replaced snapshot must be dead to count as churn
        got = ex.query_batched(xs, index.packed)
        assert ex.cache_info()["seg_slots"] == {256: 1}
        assert ex.retraces == retraces + 1
        want = ex.query_batched(xs, index.packed, path="reference")
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=1e-5, atol=1e-5)
        # the bound holds: a small upsert after the crossing keeps 256
        index.add_rows([(np.arange(5, dtype=np.int32), np.ones(5, np.float32))])
        gc.collect()
        ex.query_batched(xs, index.packed)
        assert ex.cache_info()["seg_slots"] == {256: 1}
        assert ex.retraces == retraces + 1

    def test_pow2_buckets(self):
        assert [ops.pow2_bucket(n) for n in (1, 2, 3, 4, 5, 130)] == [
            1, 2, 4, 4, 8, 256,
        ]
        assert ops.pow2_bucket(0, minimum=1) == 1
        assert ops.bucket_packets(5, 2) == 8
        assert ops.bucket_packets(9, 3) == 18  # pow2 rounded up to the step

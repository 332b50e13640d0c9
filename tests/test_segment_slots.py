"""Stage 2's segment space, sized by the stream's own row-start bound.

A grid step's segment space ``seg_slots`` is the stream's most row starts
in one step, plus the carried row, in whole 128-lane tiles, at most one
slot per entry (``seg_slot_cap``).  Cutting it removes only slots no row
fills, so the kernels must answer as they do at the cap: bit for bit where
each segment's sum is a pick with one nonzero term (the prefix-difference
loops, and the accumulate kernel's placement), within the f32
summation-order tolerance where the one-hot matmul adds a segment up.  A
bound that some step overflows would drop entries, so it is refused on the
host before anything is served.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bscsr
from repro.core.similarity import SparseEmbeddingIndex
from repro.core.topk_spmv import TopKSpMVConfig, topk_spmv_exact
from repro.kernels import executor as executor_lib
from repro.kernels import ops
from repro.kernels.bscsr_topk_spmv import (
    INNER_LOOPS,
    bscsr_spmv,
    bscsr_topk_spmv_multiquery,
    seg_slot_cap,
)

T, BLOCK, K = 2, 128, 8             # E = 256 entries per step: cap 384
E = T * BLOCK
PREFIX_LOOPS = ("linear", "linear-seg")  # the sums the ends pick makes exact


def gamma_csr(n_rows=160, n_cols=96, mean_nnz=12, seed=0, negative=False):
    csr = bscsr.synthetic_embedding_csr(n_rows, n_cols, mean_nnz, "gamma", seed)
    if negative:   # every score strictly negative against a positive query
        csr = bscsr.CSRMatrix(csr.indptr, csr.indices,
                              (-np.abs(csr.data) - 0.01).astype(np.float32), csr.shape)
    return csr


def csr_from_lengths(lens, n_cols=96, seed=0):
    rng = np.random.default_rng(seed)
    lens = np.asarray(lens, np.int64)
    idx = np.concatenate(
        [np.sort(rng.choice(n_cols, size=int(n), replace=False)) for n in lens]
    ).astype(np.int32)
    data = rng.standard_normal(int(lens.sum())).astype(np.float32)
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    return bscsr.CSRMatrix(indptr, idx, data, (len(lens), n_cols))


def queries(n_cols=96, q=3, seed=1, positive=False):
    xs = np.random.default_rng(seed).standard_normal((q, n_cols)).astype(np.float32)
    return np.abs(xs) + 0.1 if positive else xs


def stream_args(packed, layout):
    if layout == "fused":
        return (jnp.asarray(packed.fused_words()),)
    return tuple(jnp.asarray(a) for a in (packed.vals, packed.cols, packed.flags))


def topk_at(packed, xs, seg_slots, *, loop, layout, fmt_name=None, streams=None):
    """Per-core (C, Q, k) kernel answers at one segment space."""
    return bscsr_topk_spmv_multiquery(
        jnp.asarray(xs), *(streams or stream_args(packed, layout)), k=K,
        n_rows=packed.max_slots, packets_per_step=T,
        fmt_name=fmt_name or packed.value_format.name, inner_loop=loop,
        stream_layout=layout, block_size=packed.block_size,
        seg_slots=seg_slots, interpret=True,
    )


def assert_same_answers(got, want, exact):
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    if exact:
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    else:
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=1e-5, atol=1e-5)


def bounded(packed, expect=128):
    """The premise: the stream bounds below the cap, at ``expect``."""
    s = packed.seg_slots(T)
    assert s == expect < seg_slot_cap(E), (s, seg_slot_cap(E))
    return s


class TestBoundedMatchesCap:
    @pytest.mark.parametrize("fmt", ["F32", "BF16", "Q15"])
    @pytest.mark.parametrize("layout", ["split", "fused"])
    @pytest.mark.parametrize("loop", INNER_LOOPS)
    def test_topk(self, loop, layout, fmt):
        packed = ops.pack_partitions(gamma_csr(), 2, BLOCK, fmt,
                                     packets_multiple=T, stream_layout=layout)
        xs = queries()
        got = topk_at(packed, xs, bounded(packed), loop=loop, layout=layout)
        want = topk_at(packed, xs, None, loop=loop, layout=layout)
        assert_same_answers(got, want, exact=loop in PREFIX_LOOPS)

    @pytest.mark.parametrize("loop", INNER_LOOPS)
    def test_tagged_class(self, loop):
        """A mixed-precision group: BF16 and Q15 share the 2-byte class."""
        packed = ops.pack_partitions(
            gamma_csr(seed=3), 2, BLOCK, packets_multiple=T,
            stream_layout="fused", value_formats=["BF16", "Q15"],
        )
        (group,) = packed.groups
        xs = queries(seed=4)
        streams = (jnp.asarray(group.words),)
        kw = dict(loop=loop, layout="fused", fmt_name=group.class_name,
                  streams=streams)
        got = topk_at(packed, xs, bounded(packed), **kw)
        want = topk_at(packed, xs, None, **kw)
        assert_same_answers(got, want, exact=loop in PREFIX_LOOPS)

    @pytest.mark.parametrize("layout", ["split", "fused"])
    @pytest.mark.parametrize("loop", INNER_LOOPS)
    def test_all_negative_scores(self, loop, layout):
        packed = ops.pack_partitions(gamma_csr(seed=5, negative=True), 2, BLOCK,
                                     "F32", packets_multiple=T, stream_layout=layout)
        xs = queries(seed=6, positive=True)
        got = topk_at(packed, xs, bounded(packed), loop=loop, layout=layout)
        want = topk_at(packed, xs, None, loop=loop, layout=layout)
        assert float(np.asarray(want[0]).max()) < 0     # the premise
        assert_same_answers(got, want, exact=loop in PREFIX_LOOPS)

    @pytest.mark.parametrize("layout", ["split", "fused"])
    @pytest.mark.parametrize("loop", INNER_LOOPS)
    def test_accumulate(self, loop, layout):
        packed = ops.pack_partitions(gamma_csr(seed=7), 2, BLOCK, "BF16",
                                     packets_multiple=T, stream_layout=layout)
        x = jnp.asarray(queries(seed=8, q=1)[0])

        def sums(seg_slots):
            return bscsr_spmv(
                x, *stream_args(packed, layout), n_rows=packed.max_slots,
                packets_per_step=T, fmt_name="BF16", inner_loop=loop,
                stream_layout=layout, block_size=BLOCK, seg_slots=seg_slots,
                interpret=True,
            )

        got, want = sums(bounded(packed)), sums(None)
        if loop in PREFIX_LOOPS:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        else:
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-5)


class TestStreamsAtTheEdges:
    @pytest.mark.parametrize("head, starts, seg_slots", [(4, 127, 128), (3, 128, 256)])
    def test_step_at_the_bound(self, head, starts, seg_slots):
        """Step 0 holds 127 row starts, the most 128 slots hold, or one more,
        which takes the next tile: a ``head``-entry row, then rows of 2 to
        the step's end, then longer rows."""
        csr = csr_from_lengths([head] + [2] * (starts - 1) + [10] * 30, seed=9)
        packed = ops.pack_partitions(csr, 1, BLOCK, "F32", packets_multiple=T)
        assert packed.max_step_row_starts(T) == starts
        seg = bounded(packed, expect=seg_slots)
        xs = queries(seed=10)
        for loop in PREFIX_LOOPS:
            got = topk_at(packed, xs, seg, loop=loop, layout="split")
            want = topk_at(packed, xs, None, loop=loop, layout="split")
            assert_same_answers(got, want, exact=True)
        # one core, k = K: its candidates are the exact top-K
        for q in range(xs.shape[0]):
            ev, er = topk_spmv_exact(csr, xs[q], K)
            np.testing.assert_array_equal(np.asarray(got[1])[0, q], er)
            np.testing.assert_allclose(np.asarray(got[0])[0, q], ev, rtol=1e-5)
        x = jnp.asarray(xs[0])
        dense = np.zeros(csr.shape, np.float32)
        rows = np.repeat(np.arange(csr.shape[0]), np.diff(csr.indptr))
        dense[rows, csr.indices] = csr.data
        y = bscsr_spmv(x, *stream_args(packed, "split"), n_rows=packed.max_slots,
                       packets_per_step=T, seg_slots=seg, interpret=True)
        np.testing.assert_allclose(np.asarray(y)[0, : csr.shape[0]],
                                   dense @ xs[0], rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("rows, expect", [("gamma20", 128), ("one_entry", 640)])
    def test_dispatch_info_reports_the_bound(self, rows, expect):
        """At the deployment step (block 256, 2 packets: E = 512), rows of ~20
        entries bound at 128 slots; one-entry rows keep the cap, 640."""
        n_cols = 64
        if rows == "gamma20":
            csr = bscsr.synthetic_embedding_csr(1200, n_cols, 20, "gamma", 11)
        else:
            csr = csr_from_lengths([1] * 1500, n_cols=n_cols, seed=11)
        cfg = TopKSpMVConfig(big_k=10, k=8, num_partitions=2, block_size=256,
                             packets_per_step=2, value_format="BF16")
        index = SparseEmbeddingIndex(csr, cfg)
        xs = queries(n_cols=n_cols, q=4, seed=12)
        v, r = index.query_batch(xs)
        assert index.dispatch_info()["seg_slots"] == {expect: 1}
        want_v, want_r = index.query_batch(xs, use_kernel=False)
        np.testing.assert_array_equal(r, want_r)
        np.testing.assert_allclose(v, want_v, rtol=1e-5, atol=1e-5)


class TestOverflowRefused:
    def _one_entry_rows(self):
        csr = csr_from_lengths([1] * 400, seed=13)
        packed = ops.pack_partitions(csr, 1, BLOCK, "F32", packets_multiple=T)
        assert packed.seg_slots(T) == seg_slot_cap(E)     # 256 starts: 384
        return packed

    def test_check_refuses_a_forced_bound(self):
        packed = self._one_entry_rows()
        with pytest.raises(ValueError, match="row starts"):
            ops.check_segment_slots(packed, T, 128)
        ops.check_segment_slots(packed, T, seg_slot_cap(E))

    def test_pin_refuses_rather_than_answers(self, monkeypatch):
        packed = self._one_entry_rows()
        monkeypatch.setattr(ops.PackedPartitions, "seg_slots", lambda self, t: 128)
        ex = executor_lib.QueryExecutor(big_k=K, k=K, packets_per_step=T)
        before = executor_lib.device_cache_size()
        with pytest.raises(ValueError, match="row starts"):
            ex.query_batched(jnp.asarray(queries(seed=14)), packed)
        assert executor_lib.device_cache_size() == before   # nothing pinned

    @pytest.mark.parametrize("seg_slots", [0, 100, 512])
    def test_kernel_refuses_an_unaligned_or_oversized_space(self, seg_slots):
        packed = self._one_entry_rows()
        with pytest.raises(ValueError, match="seg_slots"):
            topk_at(packed, queries(seed=15), seg_slots, loop="linear",
                    layout="split")

"""Stage 1 gathers over the query block's set columns, bit for bit.

The top-k kernels compact the split query to the columns some query of the
block sets (``set_columns``) and gather over those chunks alone.  Each case
checks the gathered values against the full-width one-hot (``_gather_x``)
and against the gather of ``kernels/ref.py``'s oracle (``jnp.take``), bit
for bit, and the kernel's answers with the block's set columns against its
answers over every column.  Then one compiled query fn serves blocks of
different unions without a retrace.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import repro.core as core
from repro.core import bscsr
from repro.kernels import bscsr_topk_spmv as K
from repro.kernels import ops
from repro.kernels.bscsr_topk_spmv import CHUNK, bscsr_topk_spmv_multiquery, set_columns

E = 512      # entries per grid step at the defaults (block 256 x 2 packets)


def _block(rng, q, m, case):
    """A (q, m) float32 query block of the named shape."""
    if case == "dense":
        return rng.standard_normal((q, m)).astype(np.float32)
    xs = np.zeros((q, m), np.float32)
    n_set = {"one-chunk": 300, "three-chunks": 2500, "zero-query": 1400, "edge-cols": 40}[case]
    n_set = min(n_set, m)
    cols = rng.choice(m, n_set, replace=False)
    if case == "edge-cols":
        cols[:2] = (0, m - 1)
    xs[np.arange(n_set) % q, cols] = np.abs(rng.standard_normal(n_set)).astype(np.float32)
    if case == "zero-query" and q > 1:
        xs[q // 2] = 0.0
    return xs


def _gather(xs, query_cols, c):
    """The kernel's set-column gather alone, in one interpreted call."""
    q = xs.shape[0]
    n_chunks, ids, x3 = K._compact_query(jnp.asarray(xs), query_cols)

    def kern(nch_ref, id_cols, x_ref, c_ref, o_ref):
        o_ref[...] = K._gather_set_columns(x_ref, id_cols, nch_ref[0], c_ref[...], q, "onehot")

    return pl.pallas_call(
        kern,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [pl.BlockSpec()] * 3,
        out_shape=jax.ShapeDtypeStruct((q, c.shape[1]), jnp.float32),
        interpret=True,
    )(n_chunks, ids[..., None], x3, c)


def _bits(a):
    return np.asarray(a).view(np.uint32)


def _device(query_cols):
    """``set_columns``'s ids on the device; None (one chunk) stays None."""
    return None if query_cols is None else jnp.asarray(query_cols)


@pytest.mark.parametrize("q", [1, 8, 64])
@pytest.mark.parametrize("m, case", [
    (512, "dense"),
    (1100, "one-chunk"),
    (2100, "dense"),
    (4100, "one-chunk"),
    (4100, "three-chunks"),
    (4100, "zero-query"),
    (4100, "edge-cols"),
])
def test_gather_matches_full_width_and_the_oracle(q, m, case):
    rng = np.random.default_rng(q * 7 + m)
    xs = _block(rng, q, m, case)
    query_cols, n_set = set_columns(xs)
    c = rng.integers(0, m, (1, E)).astype(np.int32)
    if case == "edge-cols":
        c[0, :2] = (0, m - 1)
    want_chunks = max(1, -(-n_set // CHUNK))
    if case in ("one-chunk", "three-chunks"):
        assert want_chunks == {"one-chunk": 1, "three-chunks": 3}[case]
    got = _gather(xs, _device(query_cols), jnp.asarray(c))
    # the oracle's gather: jnp.take at the entry columns, all in range here
    oracle = jnp.take(jnp.asarray(xs), jnp.asarray(c[0]), axis=1)
    np.testing.assert_array_equal(_bits(got), _bits(oracle + 0.0))
    # out-of-range ids gather 0, as the full-width one-hot's all-zero row
    c_oob = c.copy()
    c_oob[0, 5:9] = (-1, m, 32767, -32768)
    full = K._gather_x(K._split3(jnp.asarray(xs)), jnp.asarray(c_oob), "onehot")
    np.testing.assert_array_equal(
        _bits(_gather(xs, _device(query_cols), jnp.asarray(c_oob))), _bits(full))


@functools.lru_cache(maxsize=None)
def _index(m):
    rng = np.random.default_rng(m)
    dense = np.abs(rng.standard_normal((300, m))).astype(np.float32)
    cfg = core.TopKSpMVConfig(big_k=6, k=4, num_partitions=2, block_size=256,
                              value_format="BF16", stream_layout="fused")
    packed = core.MutableTopKSpMVIndex(bscsr.sparsify_topm(dense, 24), cfg).packed
    return packed, jnp.asarray(packed.fused_words())


@pytest.mark.parametrize("q", [1, 8, 64])
@pytest.mark.parametrize("m, case", [(2100, "dense"), (4100, "three-chunks"),
                                     (4100, "zero-query"), (4100, "edge-cols")])
def test_kernel_answers_match_the_full_width_gather(q, m, case):
    packed, words = _index(m)
    xs = _block(np.random.default_rng(q + m), q, m, case)
    query_cols, _ = set_columns(xs)
    kw = dict(k=4, n_rows=packed.max_slots, fmt_name="BF16", stream_layout="fused",
              block_size=packed.block_size, interpret=True)
    x = jnp.asarray(xs)
    v_set, r_set = bscsr_topk_spmv_multiquery(x, words, query_cols=_device(query_cols), **kw)
    v_all, r_all = bscsr_topk_spmv_multiquery(x, words, **kw)
    np.testing.assert_array_equal(_bits(v_set), _bits(v_all))
    np.testing.assert_array_equal(np.asarray(r_set), np.asarray(r_all))


def test_one_built_fn_serves_blocks_of_different_unions():
    m = 4100
    rng = np.random.default_rng(3)
    dense = np.abs(rng.standard_normal((300, m))).astype(np.float32)
    # a big_k no other test uses: the interned executor starts afresh
    cfg = core.TopKSpMVConfig(big_k=17, k=4, num_partitions=2, block_size=256,
                              value_format="BF16", stream_layout="fused")
    index = core.SparseEmbeddingIndex.from_dense(dense, nnz_per_row=24, config=cfg)
    narrow, wide = _block(rng, 8, m, "one-chunk"), _block(rng, 8, m, "three-chunks")
    index.query_batch(narrow)
    builds = index.dispatch_info()["fn_builds"]
    v, r = index.query_batch(wide)
    info = index.dispatch_info()
    assert info["fn_builds"] == builds and info["retraces"] == 0
    assert info["gather_widths"] == {1024: 1, 3072: 1}
    # the answers are those of the full-width gather through the same plane
    packed = index.index.packed
    kw = dict(k=4, n_rows=packed.max_slots, fmt_name="BF16", stream_layout="fused",
              block_size=packed.block_size, interpret=True)
    lv, lr = bscsr_topk_spmv_multiquery(jnp.asarray(wide), jnp.asarray(packed.fused_words()), **kw)
    want_v, want_r = ops.finalize_candidates_batched(
        lv, lr, big_k=17, **ops._finalize_kwargs(packed))
    np.testing.assert_array_equal(_bits(v), _bits(want_v))
    np.testing.assert_array_equal(np.asarray(r), np.asarray(want_r))

"""The kernels' one-hot MXU matmuls: one bf16 pass on an exact three-way split.

``_split3`` cuts each f32 into three bf16 pieces that add back to it exactly,
so a dot of the pieces against a 0/1 matrix returns, wherever an output has
one nonzero term, the f32 operand's own bits.  Under the Pallas interpreter
the ``onehot`` gather therefore gives the same top-k rows and values, bit for
bit, as the ``take`` reference gather: for every value format, both stage-2
sums and one, eight and 64 queries, with query values from 1e-30 to 1e30.
The guard walks the kernels' jaxprs (traced with ``interpret=False``, never
lowered) and finds no dot inside a ``pallas_call`` body at fp32 contract
precision or on f32 operands.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bscsr
from repro.kernels import ops
from repro.kernels.bscsr_topk_spmv import (
    INNER_LOOPS,
    _split3,
    _sum3,
    bscsr_spmv,
    bscsr_topk_spmv,
    bscsr_topk_spmv_multiquery,
)

FORMATS = ["F32", "BF16", "Q15", "Q7"]
N_ROWS, N_COLS, CORES, BLOCK, K = 200, 128, 2, 64, 8


def mixed_magnitudes(shape, seed):
    """Signed values from 1e-30 to 1e30, a tenth of them exactly zero."""
    rng = np.random.default_rng(seed)
    x = rng.choice([-1.0, 1.0], shape) * rng.uniform(1, 10, shape) * 10.0 ** rng.integers(-30, 30, shape)
    x[rng.random(shape) < 0.1] = 0.0
    return x.astype(np.float32)


def split_input(kind, seed=0):
    rng = np.random.default_rng(seed)
    if kind == "normal":
        return rng.standard_normal((64, 512)).astype(np.float32)
    if kind == "mixed":
        return mixed_magnitudes((64, 512), seed)
    if kind == "bits":       # random f32 bit patterns, biased exponents 27-227
        bits = rng.integers(27 << 23, 228 << 23, (64, 512), dtype=np.int64)
        signs = rng.integers(0, 2, (64, 512)) << 31
        return (bits | signs).astype(np.uint32).view(np.float32)
    edges = np.float32([0.0, -0.0, 1.0, -1.0, np.finfo(np.float32).max,
                        -np.finfo(np.float32).max, 1e-30, 3.0, 1 / 3, 2.0 ** 100])
    return np.resize(edges, (16, 128))


@pytest.mark.parametrize("kind", ["normal", "mixed", "bits", "edges"])
def test_split3_reconstructs_f32_exactly(kind):
    a = split_input(kind)
    pieces = jax.jit(_split3)(jnp.asarray(a))
    assert pieces.dtype == jnp.bfloat16 and pieces.shape == (3 * a.shape[0], a.shape[1])
    back = np.asarray(_sum3(pieces.astype(jnp.float32)))
    np.testing.assert_array_equal(back, a)     # exact; -0.0 comes back as 0.0


@pytest.fixture(scope="module", params=FORMATS)
def packed(request):
    csr = bscsr.synthetic_embedding_csr(N_ROWS, N_COLS, 12, "gamma", 3)
    return ops.pack_partitions(csr, CORES, BLOCK, request.param, stream_layout="fused")


def _topk(packed, x, gather_mode, inner_loop):
    kwargs = dict(
        k=K, n_rows=int(max(packed.plan.rows_per_partition)), interpret=True,
        fmt_name=packed.value_format.name, gather_mode=gather_mode,
        inner_loop=inner_loop, stream_layout="fused", block_size=BLOCK,
    )
    words = jnp.asarray(packed.fused_words())
    if x.shape[0] == 1:
        return bscsr_topk_spmv(jnp.asarray(x[0]), words, **kwargs)
    return bscsr_topk_spmv_multiquery(jnp.asarray(x), words, **kwargs)


@pytest.mark.parametrize("q", [1, 8, 64])
@pytest.mark.parametrize("inner_loop", ["legacy", "linear"])
def test_onehot_gather_is_bit_identical_to_take(packed, q, inner_loop):
    x = mixed_magnitudes((q, N_COLS), q)
    ov, orow = _topk(packed, x, "onehot", inner_loop)
    tv, trow = _topk(packed, x, "take", inner_loop)
    np.testing.assert_array_equal(np.asarray(orow), np.asarray(trow))
    np.testing.assert_array_equal(np.asarray(ov).view(np.uint32), np.asarray(tv).view(np.uint32))
    assert np.isfinite(np.asarray(ov)).all()


@pytest.mark.parametrize("inner_loop", ["legacy", "linear"])
def test_accumulate_onehot_is_bit_identical_to_take(packed, inner_loop):
    x = mixed_magnitudes((1, N_COLS), 5)[0]
    kwargs = dict(
        n_rows=int(max(packed.plan.rows_per_partition)), interpret=True,
        fmt_name=packed.value_format.name, inner_loop=inner_loop,
        stream_layout="fused", block_size=BLOCK,
    )
    words = jnp.asarray(packed.fused_words())
    one = bscsr_spmv(jnp.asarray(x), words, gather_mode="onehot", **kwargs)
    take = bscsr_spmv(jnp.asarray(x), words, gather_mode="take", **kwargs)
    np.testing.assert_array_equal(np.asarray(one).view(np.uint32), np.asarray(take).view(np.uint32))


def _kernel_dots(jaxpr, inside=False):
    """Yield every dot_general eqn in pallas_call bodies, recursively."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and inside:
            yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _kernel_dots(sub, inside or eqn.primitive.name == "pallas_call")


def _traced(fn, layout, inner_loop):
    csr = bscsr.synthetic_embedding_csr(N_ROWS, N_COLS, 12, "gamma", 3)
    packed = ops.pack_partitions(csr, CORES, BLOCK, "BF16", stream_layout=layout)
    streams = ((packed.fused_words(),) if layout == "fused"
               else (packed.vals, packed.cols, packed.flags))
    kwargs = dict(n_rows=int(max(packed.plan.rows_per_partition)), interpret=False,
                  fmt_name="BF16", inner_loop=inner_loop, stream_layout=layout,
                  block_size=BLOCK)
    x = np.ones((N_COLS,), np.float32)
    if fn is bscsr_topk_spmv_multiquery:
        x = np.ones((64, N_COLS), np.float32)
    if fn is not bscsr_spmv:
        kwargs["k"] = K
    return jax.make_jaxpr(lambda x, *s: fn(x, *s, **kwargs))(x, *streams)


@pytest.mark.parametrize("layout", ["fused", "split"])
@pytest.mark.parametrize("inner_loop", INNER_LOOPS)
@pytest.mark.parametrize("fn", [bscsr_topk_spmv_multiquery, bscsr_topk_spmv, bscsr_spmv],
                         ids=["multiquery", "single", "accumulate"])
def test_no_fp32_contract_dot_in_kernel_bodies(fn, inner_loop, layout):
    dots = list(_kernel_dots(_traced(fn, layout, inner_loop).jaxpr))
    assert len(dots) >= 3          # gather, segment ids, segment sums (+ placement)
    for eqn in dots:
        assert [v.aval.dtype for v in eqn.invars] == [jnp.bfloat16] * 2, eqn
        precision = eqn.params["precision"]
        assert jax.lax.Precision.HIGHEST not in (
            precision if isinstance(precision, tuple) else (precision,)), eqn

"""The BS-CSR kernels compile with Mosaic for a described TPU v5e.

Nothing runs: each test lowers a kernel with ``interpret=False`` at the
deployment widths of ``configs/topk_spmv.CONFIG`` (M=512, block 256, BF16,
fused stream, k=8), and the top-k kernel also at the learned-sparse width
(M=30,522), and compiles it for one chip of a ``v5e:2x2`` topology
that is described, not attached.  The topology is described inside a
fixture, so collecting this file never loads the TPU library; compiles stay
in this process, and JAX's persistent compilation cache is off around them
(a compile for a described chip cannot be read back without one).
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import bscsr
from repro.core.quantization import FORMATS, WIDTH_CLASSES
from repro.kernels.bscsr_topk_spmv import (
    CHUNK,
    INNER_LOOPS,
    bscsr_spmv,
    bscsr_topk_spmv,
    bscsr_topk_spmv_multiquery,
    chunk_capacity,
)

M, BLOCK, K = 512, 256, 8
CORES, PACKETS, SLOTS = 8, 64, 1000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fused_width(fmt_name: str) -> int:
    wf, wc, wv = bscsr.fused_word_counts(BLOCK, FORMATS.get(fmt_name, "F32"), "int16")
    if fmt_name in WIDTH_CLASSES:
        wv = BLOCK * WIDTH_CLASSES[fmt_name].bytes_per_value // 4
        return 1 + wf + wc + wv
    return wf + wc + wv


def _fused_stream(fmt_name: str, sharding):
    return _sds((CORES, PACKETS, _fused_width(fmt_name)), jnp.int32, sharding)


def _split_streams(fmt_name: str, sharding):
    dtype = FORMATS[fmt_name].np_dtype
    return (
        _sds((CORES, PACKETS, BLOCK), dtype, sharding),
        _sds((CORES, PACKETS, BLOCK), jnp.int16, sharding),
        _sds((CORES, PACKETS, BLOCK // 32), jnp.int32, sharding),
    )


def _compiles(fn, *args, **kwargs):
    text = fn.lower(*args, interpret=False, **kwargs).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fmt_name", ["F32", "BF16", "Q15", "Q7", "TAG4", "TAG2", "TAG1"])
def test_topk_kernel_compiles_fused(one_chip, fmt_name):
    _compiles(
        bscsr_topk_spmv, _sds((M,), jnp.float32, one_chip),
        _fused_stream(fmt_name, one_chip), k=K, n_rows=SLOTS,
        fmt_name=fmt_name, stream_layout="fused", block_size=BLOCK,
    )


@pytest.mark.parametrize("inner_loop", INNER_LOOPS)
@pytest.mark.parametrize("layout", ["fused", "split"])
def test_topk_kernel_compiles_every_inner_loop(one_chip, inner_loop, layout):
    streams = (
        (_fused_stream("BF16", one_chip),) if layout == "fused"
        else _split_streams("BF16", one_chip)
    )
    _compiles(
        bscsr_topk_spmv, _sds((M,), jnp.float32, one_chip), *streams,
        k=K, n_rows=SLOTS, fmt_name="BF16", inner_loop=inner_loop,
        stream_layout=layout, block_size=BLOCK,
    )


@pytest.mark.parametrize("fmt_name", ["BF16", "TAG2"])
def test_multiquery_kernel_compiles_q64(one_chip, fmt_name):
    _compiles(
        bscsr_topk_spmv_multiquery, _sds((64, M), jnp.float32, one_chip),
        _fused_stream(fmt_name, one_chip), k=K, n_rows=SLOTS,
        fmt_name=fmt_name, stream_layout="fused", block_size=BLOCK,
    )


@pytest.mark.parametrize("m, q", [(M, 64), (30522, 64), (30522, 1)])
def test_multiquery_kernel_compiles_with_set_columns(one_chip, m, q):
    # The learned-sparse width (30,522 columns): the compacted query block
    # is held in VMEM whole, its set-column ids are data.  A one-hot over
    # all 30,522 columns would need 26.4 MB of scoped VMEM against 16 MB.
    _compiles(
        bscsr_topk_spmv_multiquery, _sds((q, m), jnp.float32, one_chip),
        _fused_stream("BF16", one_chip), None, None,
        _sds((chunk_capacity(m) * CHUNK,), jnp.int32, one_chip), k=K,
        n_rows=SLOTS, fmt_name="BF16", stream_layout="fused", block_size=BLOCK,
    )


@pytest.mark.parametrize("inner_loop", ["linear", "legacy"])
@pytest.mark.parametrize("q", [2, 8, 16])
def test_multiquery_kernel_compiles_small_q_buckets(one_chip, q, inner_loop):
    # The split query block has 3q rows, off the f32 sublane tiling for q=2.
    _compiles(
        bscsr_topk_spmv_multiquery, _sds((q, M), jnp.float32, one_chip),
        _fused_stream("BF16", one_chip), k=K, n_rows=SLOTS, fmt_name="BF16",
        inner_loop=inner_loop, stream_layout="fused", block_size=BLOCK,
    )


@pytest.mark.parametrize("fmt_name", ["BF16", "F32", "TAG1"])
def test_accumulate_kernel_compiles(one_chip, fmt_name):
    _compiles(
        bscsr_spmv, _sds((M,), jnp.float32, one_chip),
        _fused_stream(fmt_name, one_chip), n_rows=SLOTS, fmt_name=fmt_name,
        stream_layout="fused", block_size=BLOCK,
    )


@pytest.mark.parametrize("fn, name", [
    (bscsr_topk_spmv, "bscsr_topk_spmv_multiquery"),
    (bscsr_spmv, "bscsr_spmv"),
])
def test_kernels_carry_their_trace_names(one_chip, fn, name):
    # The device trace names an op by its HLO instruction; the pallas_call's
    # name= fixes it, whatever jitted function encloses the call.
    kwargs = dict(n_rows=SLOTS, fmt_name="BF16", stream_layout="fused",
                  block_size=BLOCK, interpret=False)
    if fn is bscsr_topk_spmv:
        kwargs["k"] = K
    text = fn.lower(_sds((M,), jnp.float32, one_chip),
                    _fused_stream("BF16", one_chip), **kwargs).compile().as_text()
    calls = re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = .*custom_call_target=\"tpu_custom_call\"",
                       text, re.M)
    assert calls and all(re.fullmatch(rf"{name}(\.\d+)?", c) for c in calls), calls


def test_take_gather_refuses_to_compile(one_chip):
    with pytest.raises(ValueError, match="interpret-only"):
        bscsr_topk_spmv.lower(
            _sds((M,), jnp.float32, one_chip), _fused_stream("BF16", one_chip),
            k=K, n_rows=SLOTS, fmt_name="BF16", stream_layout="fused",
            block_size=BLOCK, gather_mode="take", interpret=False,
        )


@pytest.mark.parametrize("m", [M, 30522])
def test_multiquery_kernel_compiles_with_bounded_segments(one_chip, m):
    # The segment space the benchmark collections bound at (128 of 640).
    query_cols = (
        _sds((chunk_capacity(m) * CHUNK,), jnp.int32, one_chip)
        if chunk_capacity(m) > 1 else None
    )
    _compiles(
        bscsr_topk_spmv_multiquery, _sds((64, m), jnp.float32, one_chip),
        _fused_stream("BF16", one_chip), None, None, query_cols, k=K,
        n_rows=SLOTS, fmt_name="BF16", stream_layout="fused", block_size=BLOCK,
        seg_slots=128,
    )


@pytest.mark.parametrize("inner_loop", ["linear", "legacy"])
def test_accumulate_kernel_compiles_with_bounded_segments(one_chip, inner_loop):
    _compiles(
        bscsr_spmv, _sds((M,), jnp.float32, one_chip),
        _fused_stream("BF16", one_chip), n_rows=SLOTS, fmt_name="BF16",
        inner_loop=inner_loop, stream_layout="fused", block_size=BLOCK,
        seg_slots=128,
    )

"""Kernel vs oracle sweeps + partitioned approximation behaviour."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # property tests only; the class-based sweeps run without hypothesis
    from hypothesis import given, settings, strategies as st
except ImportError:
    def given(**kwargs):
        return lambda fn: pytest.mark.skip(reason="hypothesis not installed")(fn)

    def settings(**kwargs):
        return lambda fn: fn

    class st:  # stand-in: strategies are built at decoration time
        integers = staticmethod(lambda *a, **k: None)
        sampled_from = staticmethod(lambda *a, **k: None)

import repro.core as core
from repro.core import bscsr
from repro.kernels import ops, ref


def make_problem(n_rows=400, n_cols=128, mean_nnz=12, dist="gamma", seed=0):
    csr = bscsr.synthetic_embedding_csr(n_rows, n_cols, mean_nnz, dist, seed)
    x = np.random.default_rng(seed + 1).standard_normal(n_cols).astype(np.float32)
    return csr, x


class TestKernelVsOracle:
    """pl.pallas_call (interpret=True) against the pure-jnp oracle."""

    @pytest.mark.parametrize("fmt", ["F32", "BF16", "Q15", "Q7"])
    @pytest.mark.parametrize("block", [32, 128])
    def test_formats_and_blocks(self, fmt, block):
        csr, x = make_problem()
        packed = ops.pack_partitions(csr, 4, block, fmt)
        kv, kr = ops.topk_spmv_blocked(jnp.asarray(x), packed, big_k=16, k=8)
        rv, rr = ops.topk_spmv_reference(jnp.asarray(x), packed, big_k=16, k=8)
        np.testing.assert_allclose(np.asarray(kv), np.asarray(rv),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(kr), np.asarray(rr))

    @pytest.mark.parametrize("cores", [1, 2, 8])
    def test_core_counts(self, cores):
        csr, x = make_problem(n_rows=333)  # ragged partition sizes
        packed = ops.pack_partitions(csr, cores, 64, "F32")
        kv, kr = ops.topk_spmv_blocked(jnp.asarray(x), packed, big_k=10, k=10)
        ev, er = core.topk_spmv_exact(csr, x, 10)
        # k == K with c cores: top-k per core guarantees exact top-10 overall
        np.testing.assert_allclose(np.asarray(kv), ev, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("t_step", [1, 2, 4])
    def test_packets_per_step(self, t_step):
        csr, x = make_problem(n_rows=200)
        packed = ops.pack_partitions(csr, 2, 32, "F32", packets_multiple=t_step)
        kv, _ = ops.topk_spmv_blocked(
            jnp.asarray(x), packed, big_k=8, k=8, packets_per_step=t_step
        )
        rv, _ = ops.topk_spmv_reference(jnp.asarray(x), packed, big_k=8, k=8)
        np.testing.assert_allclose(np.asarray(kv), np.asarray(rv), rtol=1e-5)

    def test_gather_modes_agree(self):
        csr, x = make_problem(n_rows=150, n_cols=64)
        packed = ops.pack_partitions(csr, 2, 32, "F32")
        a, _ = ops.topk_spmv_blocked(jnp.asarray(x), packed, 8, gather_mode="take")
        b, _ = ops.topk_spmv_blocked(jnp.asarray(x), packed, 8, gather_mode="onehot")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)

    def test_uniform_vs_gamma_distribution_oblivious(self):
        """BS-CSR is oblivious to row-density skew: same packets/nnz ratio."""
        for dist in ("uniform", "gamma"):
            csr, x = make_problem(dist=dist, seed=3)
            packed = ops.pack_partitions(csr, 4, 64, "F32")
            kv, kr = ops.topk_spmv_blocked(jnp.asarray(x), packed, 16, k=8)
            ev, er = core.topk_spmv_exact(csr, x, 16)
            # top-8 must match exactly (k=8 guarantee on best-ranked rows)
            np.testing.assert_allclose(np.asarray(kv)[:8], ev[:8], rtol=1e-5)


@settings(max_examples=12, deadline=None)
@given(
    n_rows=st.integers(20, 300),
    cores=st.sampled_from([1, 2, 4]),
    block=st.sampled_from([32, 64]),
    k=st.sampled_from([4, 8]),
    seed=st.integers(0, 500),
)
def test_property_kernel_matches_oracle(n_rows, cores, block, k, seed):
    """Property: for any (matrix, partitioning, block size, k), the Pallas
    kernel and the jnp oracle produce identical candidates."""
    csr, x = make_problem(n_rows=n_rows, seed=seed)
    packed = ops.pack_partitions(csr, cores, block, "F32")
    big_k = min(k * cores, n_rows)
    kv, kr = ops.topk_spmv_blocked(jnp.asarray(x), packed, big_k, k=k)
    rv, rr = ops.topk_spmv_reference(jnp.asarray(x), packed, big_k, k=k)
    np.testing.assert_allclose(np.asarray(kv), np.asarray(rv), rtol=1e-5,
                               atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100), big_k=st.sampled_from([8, 16, 32]))
def test_property_approximation_never_misses_top_k_of_each_partition(seed, big_k):
    """§III-A invariant: 'the approximation does not affect the best-ranked
    rows' — the top-k of every partition always survives the merge, so the
    global top-min(k, K) is exact."""
    csr, x = make_problem(n_rows=256, seed=seed)
    idx = core.build_index(csr, core.TopKSpMVConfig(
        big_k=big_k, k=8, num_partitions=4, block_size=32))
    av, ar = core.topk_spmv(idx, jnp.asarray(x))
    ev, er = core.topk_spmv_exact(csr, x, big_k)
    kk = min(8, big_k)
    np.testing.assert_allclose(np.asarray(av)[:kk], ev[:kk], rtol=1e-5)


class TestDistributed:
    def test_one_device_mesh_matches_exact(self):
        csr, x = make_problem(n_rows=300)
        mesh = jax.make_mesh((1,), ("data",))
        idx = core.build_index(csr, core.TopKSpMVConfig(
            big_k=12, k=8, num_partitions=4, block_size=64))
        fn, arrays = core.distributed_topk_spmv_fn(idx, mesh)
        v, r = fn(jnp.asarray(x), *arrays)
        rv, rr = core.topk_spmv(idx, jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(v), np.asarray(rv), rtol=1e-5)

    def test_multi_device_subprocess(self):
        """Real 8-device run: numerics must match the single-device path."""
        import subprocess, sys, os
        code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
import repro.core as core
csr = core.synthetic_embedding_csr(400, 128, 12, 'gamma', 0)
x = np.random.default_rng(1).standard_normal(128).astype(np.float32)
mesh = jax.make_mesh((8,), ('data',))
idx = core.build_index(csr, core.TopKSpMVConfig(big_k=16, k=8,
    num_partitions=8, block_size=64))
fn, arrays = core.distributed_topk_spmv_fn(idx, mesh)
v, r = fn(jnp.asarray(x), *arrays)
ev, er = core.topk_spmv_exact(csr, x, 16)
np.testing.assert_allclose(np.asarray(v)[:8], ev[:8], rtol=1e-5)
print("MULTIDEV_OK")
"""
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300)
        assert "MULTIDEV_OK" in out.stdout, out.stderr[-2000:]


class TestMultiQuery:
    """Beyond-paper multi-query kernel == Q independent single-query runs."""

    @pytest.mark.parametrize("fmt", ["F32", "Q7"])
    def test_matches_single_query(self, fmt):
        from repro.kernels.bscsr_topk_spmv import bscsr_topk_spmv_multiquery

        csr, _ = make_problem(n_rows=300, seed=11)
        packed = ops.pack_partitions(csr, 4, 64, fmt)
        xs = np.random.default_rng(12).standard_normal((4, 128)).astype(np.float32)
        max_rows = int(max(packed.plan.rows_per_partition))
        lv, lr = bscsr_topk_spmv_multiquery(
            jnp.asarray(xs), jnp.asarray(packed.vals), jnp.asarray(packed.cols),
            jnp.asarray(packed.flags), k=8, n_rows=max_rows,
            fmt_name=fmt, interpret=True,
        )
        for q in range(xs.shape[0]):
            fv, fr = ops.finalize_candidates(
                lv[:, q], lr[:, q], jnp.asarray(packed.row_starts),
                jnp.asarray(packed.rows_per_partition), 16, csr.shape[0])
            sv, sr = ops.topk_spmv_blocked(jnp.asarray(xs[q]), packed, 16, k=8)
            np.testing.assert_allclose(np.asarray(fv), np.asarray(sv),
                                       rtol=1e-5, atol=1e-5)

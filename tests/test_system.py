"""End-to-end behaviour tests for the paper's system.

The paper's claim chain, in miniature: sparse embedding collection ->
partitioned BS-CSR index -> approximate Top-K queries that (a) match the
exact CPU baseline on the best-ranked results, (b) hit the Eq. (1) precision
model, and (c) move ~3x fewer bytes than naive COO.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as core
from repro.core import bscsr


@pytest.fixture(scope="module")
def service():
    csr = core.synthetic_embedding_csr(5000, 256, 20, "gamma", seed=7)
    cfg = core.TopKSpMVConfig(big_k=50, k=8, num_partitions=16,
                              block_size=128, value_format="BF16")
    return core.SparseEmbeddingIndex(csr, cfg)


class TestSimilarityService:
    def test_query_matches_exact_top8(self, service, rng):
        for _ in range(3):
            x = rng.standard_normal(256).astype(np.float32)
            av, ar = service.query(x)
            ev, er = service.query_exact(x)
            # best-ranked results are exact (k=8 per partition, §III-A);
            # BF16 values perturb scores ~1e-2 and may swap near-ties, but
            # the sorted top-8 score vectors must agree to bf16 tolerance
            np.testing.assert_allclose(av[:8], ev[:8], rtol=0.02, atol=0.03)

    def test_precision_at_50_meets_model(self, service, rng):
        precs = []
        for _ in range(5):
            x = rng.standard_normal(256).astype(np.float32)
            _, ar = service.query(x, use_kernel=False)
            _, er = service.query_exact(x)
            precs.append(len(set(ar.tolist()) & set(er.tolist())) / 50)
        model = service.index.expected_precision
        assert np.mean(precs) >= model - 0.08

    def test_batch_queries(self, service, rng):
        xs = rng.standard_normal((3, 256)).astype(np.float32)
        vals, ids = service.query_batch(xs)
        assert vals.shape == (3, 50) and ids.shape == (3, 50)

    def test_stats_report_bandwidth_story(self, service):
        st = service.stats()
        # BF16 BS-CSR must beat naive COO by ~3x in bytes/nnz (Fig. 6 claim)
        assert bscsr.coo_bytes_per_nnz() / st.bytes_per_nnz > 2.5
        assert st.expected_precision > 0.99


class TestFromDense:
    def test_sparsify_and_search(self, rng):
        dense = rng.standard_normal((2000, 128)).astype(np.float32)
        idx = core.SparseEmbeddingIndex.from_dense(
            dense, nnz_per_row=24,
            config=core.TopKSpMVConfig(big_k=10, k=8, num_partitions=4,
                                       block_size=64),
        )
        # query WITH one of the collection's own (sparsified) rows: its row
        # must be the top hit (cosine similarity 1 with itself)
        row0 = idx.csr.row_slice(17, 18).to_dense()[0]
        _, ids = idx.query(row0)
        assert ids[0] == 17


def test_query_batch_kernel_matches_reference(service, rng):
    """query_batch(use_kernel=True) — the one-pass multi-query kernel —
    returns the same results as the per-query reference path."""
    xs = rng.standard_normal((3, 256)).astype(np.float32)
    kv, kr = service.query_batch(xs, use_kernel=True)
    rv, rr = service.query_batch(xs, use_kernel=False)
    np.testing.assert_allclose(kv, rv, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(kr, rr)


def test_served_defaults_dispatch_the_kernel(service, rng):
    """query_batch, the streaming service and the top-k head all run the
    Pallas kernel unless the reference oracle is asked for by name."""
    from repro.serve import StreamingSimilarityService
    from repro.serve.topk_head import ApproxTopKHead, TopKHeadConfig

    xs = rng.standard_normal((2, 256)).astype(np.float32)
    kv, kr = service.query_batch(xs)
    np.testing.assert_array_equal(kr, service.query_batch(xs, use_kernel=True)[1])
    info = service.dispatch_info()
    assert "kernel" in info["paths"] and info["interpret"] is True
    assert info["gather_mode"] == "onehot"
    svc = StreamingSimilarityService(service)
    assert svc.use_kernel is True
    np.testing.assert_array_equal(svc.search(xs)[1], kr)
    head = ApproxTopKHead(
        rng.standard_normal((300, 32)).astype(np.float32),
        TopKHeadConfig(big_k=8, k=8, num_partitions=2, nnz_per_row=8, block_size=64),
    )
    h = rng.standard_normal(32).astype(np.float32)
    np.testing.assert_array_equal(head.topk_logits(h)[1],
                                  head.topk_logits(h, use_kernel=True)[1])


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False)])
def test_interpret_resolves_from_the_backend(monkeypatch, backend, want):
    """interpret=None: interpreted on the CPU, compiled on a TPU."""
    from repro.kernels import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    assert ops.default_interpret() is want
    assert core.TopKSpMVConfig().resolve_interpret() is want
    assert core.TopKSpMVConfig(interpret=not want).resolve_interpret() is (not want)


def test_interpret_refuses_other_backends(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="not on 'gpu'"):
        core.TopKSpMVConfig().resolve_interpret()

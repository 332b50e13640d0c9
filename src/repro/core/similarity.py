"""Embedding-similarity service — the paper's end application (§I, Fig. 1).

Matches a dense query embedding against a collection of sparse embeddings and
returns the K most cosine-similar rows.  Wraps index building (sparsify ->
partition -> BS-CSR encode -> quantize) and batched querying behind one class.

The backing index is a ``MutableTopKSpMVIndex``: rows can be ``upsert``-ed
and ``delete``-d while serving (delta tile-packets + tombstones, no
re-encode), and ``compact()`` periodically reclaims the churn.  Queries
dispatch through the device-resident snapshot plane (``kernels/executor``):
each snapshot version's streams are pinned on device once, so steady-state
queries perform zero host->device transfers (``dispatch_info()`` exposes the
executor caches).

With ``mesh=`` (a ``launch.mesh.make_serving_mesh`` mesh) or ``n_shards=``
the backing index is a :class:`~repro.core.sharded.ShardedTopKSpMVIndex`
instead: the collection row-shards across the mesh's "shard" axis (each
shard device-pinned on its mesh column, per-shard candidates tree-merged
under global ids) and query batches fan out across the "replica" axis —
same mutation surface, bit-identical results, docs/SERVING.md §"Sharded
serving".
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import bscsr as bscsr_lib
from repro.core import topk_spmv as topk_lib
from repro.core import sharded as sharded_lib
from repro.utils.tracing import span


@dataclasses.dataclass
class SimilaritySearchStats:
    n_rows: int
    n_cols: int
    nnz: int
    num_partitions: int
    bytes_per_nnz: float          # effective: stream bytes / live nnz
    stream_bytes: int
    expected_precision: float
    delta_fraction: float = 0.0   # live nnz held in delta segments / live nnz
    tombstone_count: int = 0      # retired (tombstoned) candidate slots
    deleted_rows: int = 0         # globally tombstoned row ids
    version: int = 0              # snapshot version counter
    stream_layout: str = "split"  # fused (one burst/step) | split (3 arrays)
    last_refresh_repadded: int = 0  # partitions re-padded by the last snapshot
    last_refresh_copied: int = 0  # partitions copied into the COW stack buffers
    snapshot_buffers: int = 0     # COW stacked buffers pooled (leased + free)
    # -- mixed precision (config.recall_target) ------------------------------
    value_format_histogram: dict = dataclasses.field(default_factory=dict)
    value_bytes_per_nnz: float = 0.0  # streamed value bytes / live nnz
    recall_target: Optional[float] = None
    predicted_recall: Optional[float] = None  # calibration's recall@k estimate


class SparseEmbeddingIndex:
    """Approximate Top-K cosine-similarity over a sparse embedding collection."""

    def __init__(
        self,
        csr: bscsr_lib.CSRMatrix,
        config: Optional[topk_lib.TopKSpMVConfig] = None,
        nnz_per_row: int = 32,
        recall_target: Optional[float] = None,
        mesh=None,
        n_shards: Optional[int] = None,
        native_groups: bool = True,
    ):
        self.csr = csr  # the collection the index was built from (base segment)
        config = config or topk_lib.TopKSpMVConfig()
        if recall_target is not None:
            # Convenience knob: per-partition mixed-precision streams tuned
            # so predicted recall@k vs exact stays >= the target.
            config = dataclasses.replace(config, recall_target=recall_target)
        self.config = config
        self.nnz_per_row = nnz_per_row  # sparsification level for dense upserts
        if mesh is not None or (n_shards is not None and n_shards > 1):
            # Sharded serving plane: row shards pinned per mesh column,
            # tree-merged under global ids — bit-identical to the
            # single-device index (core/sharded.py).
            self.index = sharded_lib.ShardedTopKSpMVIndex(
                csr, self.config, mesh=mesh, n_shards=n_shards,
                native_groups=native_groups,
            )
        else:
            self.index = topk_lib.MutableTopKSpMVIndex(csr, self.config)

    @property
    def is_sharded(self) -> bool:
        return isinstance(self.index, sharded_lib.ShardedTopKSpMVIndex)

    @property
    def replica_factor(self) -> int:
        """Query fan-out width of one kernel pass (mesh "replica" axis).

        A sharded index spreads a coalesced batch across R replica groups,
        so one pass carries R x the per-device Q bucket — the micro-batching
        frontend multiplies its target/capacity by this factor
        (docs/SERVING.md §"Request frontend").  1 for a single-device index.
        """
        return self.index.n_replicas if self.is_sharded else 1

    @property
    def n_cols(self) -> int:
        """Feature dimension served by the backing index."""
        return self.index.n_cols

    @classmethod
    def from_index(
        cls,
        index,
        nnz_per_row: int = 32,
    ) -> "SparseEmbeddingIndex":
        """Wrap an already-built backing index — the recovery constructor.

        ``persistence.DurableIndexStore.recover()`` returns a bare
        ``MutableTopKSpMVIndex``; this re-attaches the service facade to it
        without re-encoding anything (the restored snapshot keeps serving
        bit-identically).
        """
        obj = cls.__new__(cls)
        obj.config = index.config
        obj.nnz_per_row = nnz_per_row
        obj.index = index
        csr, _ = index.live_csr()
        obj.csr = csr
        return obj

    def _validate_query(self, x: np.ndarray, batched: bool) -> None:
        x = np.asarray(x)
        want = 2 if batched else 1
        shape_name = "(Q, M) batch" if batched else "(M,) vector"
        if x.ndim != want:
            raise ValueError(
                f"query must be a {want}-D {shape_name}, got shape {x.shape}"
            )
        if x.shape[-1] != self.n_cols:
            raise ValueError(
                f"query width {x.shape[-1]} != index feature dim "
                f"{self.n_cols}"
            )
        if not np.all(np.isfinite(np.asarray(x, np.float32))):
            raise ValueError(
                "query contains non-finite values (NaN/Inf) — scores would "
                "be meaningless; sanitize upstream"
            )

    @classmethod
    def from_dense(
        cls,
        embeddings: np.ndarray,
        nnz_per_row: int = 32,
        config: Optional[topk_lib.TopKSpMVConfig] = None,
        recall_target: Optional[float] = None,
        mesh=None,
        n_shards: Optional[int] = None,
        native_groups: bool = True,
    ) -> "SparseEmbeddingIndex":
        """Sparsify dense embeddings (magnitude top-m) and index them."""
        csr = bscsr_lib.sparsify_topm(embeddings, nnz_per_row)
        return cls(csr, config, nnz_per_row=nnz_per_row,
                   recall_target=recall_target, mesh=mesh, n_shards=n_shards,
                   native_groups=native_groups)

    def query(
        self, x: np.ndarray, use_kernel: bool = True
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Top-K (scores, row ids) for one dense query embedding.

        Routed through the same batched dispatch entry as ``query_batch``
        (as a Q=1 batch): the convenience path and the micro-batching
        frontend share ONE compiled-fn/pin plane, so ``dispatch_info()``
        counters agree no matter which door a query came through, and a
        Q=1 dispatch warms the same Q-bucket cache the frontend drifts
        across.  Answers are bit-identical to the dedicated single-query
        path (the batched kernel at Q=1 evaluates the same partitioned
        approximation).
        """
        self._validate_query(x, batched=False)
        v, r = self._dispatch_batch(
            np.asarray(x)[None, :], use_kernel=use_kernel
        )
        return v[0], r[0]

    def query_batch(
        self, xs: np.ndarray, use_kernel: bool = True
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Batched queries.

        By default the multi-query Pallas kernel answers all Q queries in
        ONE pass over the stream (per-query bytes/nnz divided by Q — the
        beyond-paper optimization, EXPERIMENTS.md §Perf C4).  The kernel is
        compiled on a TPU and interpreted on the CPU (``config.interpret``).
        ``use_kernel=False`` asks for the vmapped jnp reference oracle by
        name: it evaluates the identical partitioned approximation.
        """
        self._validate_query(xs, batched=True)
        return self._dispatch_batch(xs, use_kernel=use_kernel)

    def _dispatch_batch(
        self, xs: np.ndarray, use_kernel: bool
    ) -> Tuple[np.ndarray, np.ndarray]:
        """The one dispatch entry every query path funnels through.

        ``query`` (Q=1), ``query_batch`` and the frontend's coalesced
        passes all land here — one place that derives the executor from
        the config and routes sharded vs single-device, so the executor's
        cache/bucket counters count every path the same way.  On the
        single-device kernel path the executor first finds the block's set
        columns on the host (``executor.columns``), which the kernel's
        gather covers alone.  The query block's upload and the answers'
        fetch are the ``index.upload`` and ``index.fetch`` spans; the fetch
        waits for the device.
        """
        q = int(np.shape(xs)[0])
        cols = None
        if use_kernel and self.config.use_executor and not self.is_sharded:
            cols = topk_lib.query_executor(self.config).set_columns(np.asarray(xs))
        with span("index.upload", q=q):
            xs = jnp.asarray(xs, jnp.float32)
            cols = None if cols is None else jnp.asarray(cols)
        if self.is_sharded:
            v, r = self.index.query_batched(xs, use_kernel=use_kernel)
        else:
            v, r = topk_lib.topk_spmv_batched(
                self.index, xs, use_kernel=use_kernel, query_cols=cols
            )
        with span("index.fetch", q=q):
            return np.asarray(v), np.asarray(r)

    def query_exact(self, x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Exact Top-K over the *live* rows — ground truth for accuracy checks.

        Casts the query exactly like ``query`` does, so int/float64 inputs
        cannot silently change the comparison baseline.
        """
        x = np.asarray(jnp.asarray(x, jnp.float32))
        csr, gids = self.index.live_csr()
        v, local = topk_lib.topk_spmv_exact(csr, x, self.config.big_k)
        return v, gids[local].astype(np.int64)

    # -- live updates (serve-while-ingest) ----------------------------------

    def upsert(
        self,
        embeddings: np.ndarray,
        ids: Optional[Sequence[int]] = None,
        nnz_per_row: Optional[int] = None,
    ) -> np.ndarray:
        """Add or replace dense embedding rows; returns their global row ids.

        Rows are magnitude-top-m sparsified like ``from_dense``.  With
        ``ids=None`` the rows are appended under fresh ids; otherwise each
        row replaces (or resurrects) the given id.  Updates land as delta
        tile-packets — no re-encode of the existing stream.
        """
        embeddings = np.atleast_2d(np.asarray(embeddings, np.float32))
        if embeddings.shape[1] != self.n_cols:
            raise ValueError(
                f"embedding width {embeddings.shape[1]} != index width "
                f"{self.n_cols}"
            )
        if not np.all(np.isfinite(embeddings)):
            raise ValueError(
                "upsert embeddings contain non-finite values (NaN/Inf) — "
                "they would poison the quantization calibration and every "
                "score they touch; sanitize upstream"
            )
        m_keep = min(nnz_per_row or self.nnz_per_row, embeddings.shape[1])
        sparse = bscsr_lib.sparsify_topm(embeddings, m_keep)
        rows = [
            (
                sparse.indices[sparse.indptr[i] : sparse.indptr[i + 1]],
                sparse.data[sparse.indptr[i] : sparse.indptr[i + 1]],
            )
            for i in range(sparse.shape[0])
        ]
        if ids is None:
            return np.asarray(self.index.add_rows(rows), dtype=np.int64)
        self.index.replace_rows(list(ids), rows)
        return np.asarray(list(ids), dtype=np.int64)

    def delete(self, ids: Sequence[int]) -> None:
        """Tombstone rows: never returned again, reclaimed at ``compact()``."""
        self.index.delete_rows(list(ids))

    def compact(self) -> None:
        """Re-encode live rows, restoring base-only bytes/nnz."""
        self.index.compact()

    # -- iterative graph workloads (accumulate-mode SpMV) -------------------

    def personalized_pagerank(self, seeds, **kwargs):
        """Personalized PageRank over this index's rows as a graph operator.

        Requires a square index (rows indexed by the same id space as
        columns — e.g. built from ``graph.synthetic_graph_csr`` or any
        adjacency-shaped collection).  Damped power iteration on the
        accumulate-mode kernel: one fused ``y = alpha*A@x + beta*y``
        dispatch per step, device-resident between steps, warm-startable
        for incremental re-solves after ``upsert``/``delete``.  See
        :func:`repro.core.graph.personalized_pagerank` for the keyword
        surface (``alpha``, ``tol``, ``warm_start``, ...).
        """
        from repro.core import graph as graph_lib

        return graph_lib.personalized_pagerank(self.index, seeds, **kwargs)

    def topk_eigen(self, k: int, **kwargs):
        """Top-k eigenpairs of this (symmetric, square) index's operator.

        Deflated power iteration on the accumulate-mode kernel; see
        :func:`repro.core.graph.topk_eigen`.
        """
        from repro.core import graph as graph_lib

        return graph_lib.topk_eigen(self.index, k, **kwargs)

    def stats(self) -> SimilaritySearchStats:
        if self.is_sharded:
            agg = self.index.aggregate_stats()
            return SimilaritySearchStats(
                n_rows=self.index.n_rows,
                n_cols=agg["n_cols"],
                nnz=agg["nnz"],
                num_partitions=self.index.num_cores,
                bytes_per_nnz=agg["bytes_per_nnz"],
                stream_bytes=agg["stream_bytes"],
                expected_precision=self.index.expected_precision,
                delta_fraction=agg["delta_fraction"],
                tombstone_count=agg["tombstone_count"],
                deleted_rows=self.index.deleted_rows,
                version=self.index.version,
                stream_layout=agg["stream_layout"],
                last_refresh_repadded=self.index.last_refresh_repadded,
                last_refresh_copied=self.index.last_refresh_copied,
                snapshot_buffers=self.index.snapshot_buffers,
                value_format_histogram=agg["format_histogram"],
                value_bytes_per_nnz=agg["value_bytes_per_nnz"],
                recall_target=self.config.recall_target,
                predicted_recall=self.index.predicted_recall,
            )
        packed = self.index.packed
        return SimilaritySearchStats(
            n_rows=self.index.n_rows,
            n_cols=packed.n_cols,
            nnz=packed.nnz,
            num_partitions=packed.num_cores,
            bytes_per_nnz=packed.bytes_per_nnz,
            stream_bytes=packed.stream_bytes,
            expected_precision=self.index.expected_precision,
            delta_fraction=packed.delta_fraction,
            tombstone_count=packed.tombstone_count,
            deleted_rows=self.index.deleted_rows,
            version=self.index.version,
            stream_layout=packed.stream_layout,
            last_refresh_repadded=self.index.last_refresh_repadded,
            last_refresh_copied=self.index.last_refresh_copied,
            snapshot_buffers=self.index.snapshot_buffers,
            value_format_histogram=packed.format_histogram(),
            value_bytes_per_nnz=packed.value_bytes_per_nnz,
            recall_target=self.config.recall_target,
            predicted_recall=self.index.predicted_recall,
        )

    def dispatch_info(self) -> dict:
        """Cache + signature stats of the executor serving this config.

        Executor counters (``compiled_fns``, ``fn_builds``, ``retraces``,
        ``dispatches``, device-pin counts, ``gather_widths``, and
        ``seg_slots``: the kernels' segment space per pinned signature)
        merged with the current
        snapshot's ``signature_info()`` — the bucketed dims that key
        compiled query fns vs the live counts inside them.  Steady-state
        serve-while-ingest shows ``retraces`` flat while versions climb;
        see docs/SERVING.md for the field-by-field reference.

        A sharded index reports its topology (shard/replica counts),
        per-shard versions + signatures, and — on the SPMD path — the
        bundle's per-shard upload/byte counters instead.
        """
        if self.is_sharded:
            return self.index.dispatch_info()
        info = topk_lib.query_executor(self.config).cache_info()
        info["signature"] = self.index.packed.signature_info()
        info["churn_stable"] = self.config.churn_stable
        return info

"""Sharded multi-replica top-k serving plane (paper §V-C scaled past one device).

The paper's scale-out story is one FPGA per HBM stack, each streaming its
slice of the BS-CSR matrix; this module is the TPU-serving analogue.  A
:class:`ShardedTopKSpMVIndex` row-shards the collection across the "shard"
axis of a ``("replica", "shard")`` mesh (``launch.mesh.make_serving_mesh``):

* **Row sharding at partition granularity.**  The global partition plan is
  cut into ``S`` contiguous runs of ``C/S`` partitions; each run's rows back
  one shard-local :class:`~repro.core.topk_spmv.MutableTopKSpMVIndex`.  The
  partition plan slices exactly (the +1-sized partitions of ``C = q*S + r``
  form a prefix), so every shard's base encode is bit-identical to the
  corresponding slice of the single-device encode.
* **Global ids via per-shard row maps.**  Each shard merges candidates under
  the *global* id space: a device-pinned ``l2g`` map rides the shard's
  snapshot (``finalize_candidates(..., row_map=)``) so tie-breaks and the
  sentinel id are identical to the single-device merge — which makes the
  merge associative and any merge tree bit-identical to the flat one
  (see ``partition.merge_topk``).
* **Tree top-k merge.**  Per-shard ``big_k`` pools reduce over the shard
  axis in ``log2(S)`` pairwise ``merge_topk`` rounds (XOR-partner
  ``ppermute``; non-power-of-two shard counts fall back to one
  ``all_gather`` + flat merge, bit-identical by the same normalisation).
* **Device-pinned shards, dirty-partition refresh.**  The SPMD dispatcher
  pins each shard's streams on its mesh column through
  ``kernels.executor.ShardedDeviceBundle``; a mutable-index refresh ships
  only the partitions whose COW stamps moved, to the owning shard's devices
  only.  Steady-state queries dispatch with zero host->device transfers and
  zero retraces (churn-stable per-shard buckets stack into churn-stable
  global shapes).
* **Replica fan-out.**  Query batches shard over the "replica" axis
  (``sharding.rules``: logical axes ``topk_shards`` / ``topk_queries``),
  so QPS scales with replicas while each replica group holds a full copy
  of every shard.

Mutations (``add_rows`` / ``replace_rows`` / ``delete_rows``) route through
a *global* least-loaded-core simulation that replicates the single-device
greedy placement exactly — per-core slot structure, delta packets and
sentinels match the single-device index batch for batch, which is what the
bit-identity guarantee under churn rests on.  ``compact()`` re-slices the
live collection across shards at partition boundaries.

Heterogeneous (``recall_target``) indexes shard-locally regroup their
width classes: each shard's local index builds tagged fused groups from its
own partitions and serves them natively through the per-shard executor
path; ``native_groups=False`` forces the exactly-dequantized f32-twin split
streams instead (bit-identical scores — the twins are
``dequantize(native)``).

Dispatch paths:

==============================  ==========================================
configuration                   path
==============================  ==========================================
``mesh=None`` (``n_shards=S``)  per-shard executor dispatch on the default
                                device (testing / 1-device bit-identity)
mesh + uniform format           SPMD shard_map: one compiled fn, tree merge
mesh + hetero, native groups    per-shard executor dispatch, one column
                                device per shard, host-side tree merge
mesh + hetero, f32 twins        SPMD shard_map over the split twin streams
``use_kernel=False``            per-shard reference oracle (same plane)
==============================  ==========================================

See docs/ARCHITECTURE.md ("Sharded serving") and docs/SERVING.md for the
mesh knob, the refresh byte-shipping table and the ``dispatch_info()``
fields.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import bscsr as bscsr_lib
from repro.core import faults as faults_lib
from repro.core import partition as partition_lib
# Direct-from imports: the package __init__ re-binds the ``topk_spmv``
# attribute to the function of the same name, so the module object is not
# reachable as ``repro.core.topk_spmv`` once the package is initialised.
from repro.core.topk_spmv import (
    MutableTopKSpMVIndex,
    TopKSpMVConfig,
    expected_precision,
    query_executor,
)
from repro.kernels import executor as executor_lib
from repro.kernels import ops as kernel_ops
from repro.kernels.bscsr_topk_spmv import (
    bscsr_spmv,
    bscsr_topk_spmv,
    bscsr_topk_spmv_multiquery,
)
from repro.sharding import rules as rules_lib

_INVALID = int(bscsr_lib.INVALID_ROW)


@functools.lru_cache(maxsize=None)
def _combine_partials_fn(n_pools: int):
    """Jitted ``alpha * sum(partials) + beta * y`` for the per-shard
    accumulate path.  Each global row lives on exactly one shard, so the
    off-owner partials contribute literal zeros and the sum is bit-identical
    to the single-device scatter (adding 0.0 never perturbs an f32)."""

    def run(alpha, beta, y, *parts):
        acc = parts[0]
        for p in parts[1:]:
            acc = acc + p
        return alpha * acc + beta * y

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _pinned_zeros(n: int, device=None):
    """A cached device-resident zero vector (per-shard accumulate partials
    pass it as the fn's ``y`` arg with beta pinned to 0)."""
    if device is None:
        return jnp.zeros((n,), jnp.float32)
    return jax.device_put(np.zeros((n,), np.float32), device)


@functools.lru_cache(maxsize=None)
def _pinned_unit_scalars(device=None):
    """Cached (1.0, 0.0) f32 device scalars for partial-product dispatches."""
    if device is None:
        return jnp.asarray(1.0, jnp.float32), jnp.asarray(0.0, jnp.float32)
    return (
        jax.device_put(np.float32(1.0), device),
        jax.device_put(np.float32(0.0), device),
    )


@functools.lru_cache(maxsize=None)
def _host_merge_fn(n_pools: int, big_k: int, batched: bool):
    """Jitted host-side tree merge of per-shard pools (per-shard path).

    The global row-id sentinel arrives as a traced arg, so the compiled fn
    (keyed only by pool count and shapes) survives id-space growth with
    zero retraces and zero transfers.
    """

    def run(gsent, *pools):
        vs = list(pools[:n_pools])
        rs = list(pools[n_pools:])
        if batched:
            return partition_lib.tree_merge_topk_batched(vs, rs, big_k, gsent)
        return partition_lib.tree_merge_topk(vs, rs, big_k, gsent)

    return jax.jit(run)


class ShardedTopKSpMVIndex:
    """A row-sharded, multi-replica, serve-while-ingest top-k index.

    Duck-types the mutation and query surface of
    :class:`~repro.core.topk_spmv.MutableTopKSpMVIndex` (global row ids,
    ``add_rows`` / ``replace_rows`` / ``delete_rows`` / ``compact`` /
    ``live_csr``) while holding ``n_shards`` shard-local mutable indexes,
    each pinned to its mesh column.  Queries return results bit-identical
    to the single-device index built from the same collection with the
    same (frozen) partition count.

    The partition count is resolved once at construction and FROZEN: it
    must divide by the shard count, and ``compact()`` keeps it (a sharded
    plan cannot re-resolve per live-row count without re-negotiating the
    shard split).
    """

    def __init__(
        self,
        csr: bscsr_lib.CSRMatrix,
        config: Optional[TopKSpMVConfig] = None,
        *,
        mesh=None,
        n_shards: Optional[int] = None,
        native_groups: bool = True,
    ):
        config = config or TopKSpMVConfig()
        self.config = config
        self.mesh = mesh
        self.native_groups = native_groups
        if mesh is not None:
            if "shard" not in mesh.axis_names:
                raise ValueError(
                    "serving mesh needs a 'shard' axis — build it with "
                    "launch.mesh.make_serving_mesh(n_shards, n_replicas)"
                )
            s = int(mesh.shape["shard"])
            r = (
                int(mesh.shape["replica"])
                if "replica" in mesh.axis_names else 1
            )
            if n_shards is not None and int(n_shards) != s:
                raise ValueError(
                    f"n_shards={n_shards} contradicts the mesh's shard axis "
                    f"({s})"
                )
        else:
            s = int(n_shards) if n_shards is not None else 1
            r = 1
        if s < 1:
            raise ValueError(f"n_shards must be >= 1, got {s}")
        self.n_shards = s
        self.n_replicas = r
        c_total = config.resolve_partitions(csr.shape[0])
        if c_total % s:
            raise ValueError(
                f"num_partitions ({c_total}) must divide by the shard count "
                f"({s}) so every shard owns whole partitions"
            )
        self._c_total = c_total
        self._cps = c_total // s
        self._local_config = dataclasses.replace(
            config, num_partitions=self._cps
        )
        self._hetero = config.recall_target is not None

        plan = partition_lib.PartitionPlan.build(csr.shape[0], c_total)
        bounds = [0]
        for i in range(s):
            bounds.append(bounds[-1] + int(sum(
                plan.rows_per_partition[i * self._cps:(i + 1) * self._cps]
            )))
        self._shards = []
        self._l2g: list = []     # per shard: local id -> global id, append-only
        self._live: dict = {}    # global id -> (shard, local id)
        for i in range(s):
            sub = csr.row_slice(bounds[i], bounds[i + 1])
            self._shards.append(
                MutableTopKSpMVIndex(sub, self._local_config)
            )
            ids = list(range(bounds[i], bounds[i + 1]))
            self._l2g.append(ids)
            for lid, gid in enumerate(ids):
                self._live[gid] = (i, lid)
        self._next_gid = csr.shape[0]
        self._deleted: set = set()
        self._dead_shards: set = set()  # failed dispatch -> degraded serving
        self.failovers = 0              # shards ever marked dead
        self.last_query_degraded = False
        self._version = 0
        self._generation = 0          # bumped by compact(): shard-version
                                      # counters restart, caches must not alias
        self._row_maps: dict = {}     # shard -> ((generation, version), map)
        self._gsent: dict = {}        # device|None -> (next_gid, pinned scalar)
        self._live_csr_cache = None
        # SPMD shard_map dispatch needs one uniform stream format across the
        # mesh: uniform configs ship their native streams, hetero configs
        # ship the exactly-dequantized f32 twins unless native per-shard
        # width-class groups were requested (those ride the per-shard path).
        self._spmd = None
        if mesh is not None and (not self._hetero or not native_groups):
            self._spmd = _SpmdDispatcher(self)

    # -- bookkeeping ---------------------------------------------------------

    @property
    def version(self) -> int:
        return self._version

    @property
    def n_rows(self) -> int:
        """Live (queryable) rows across all shards."""
        return len(self._live)

    @property
    def n_rows_total(self) -> int:
        """Size of the global row-id space (live + deleted ids)."""
        return self._next_gid

    @property
    def num_cores(self) -> int:
        return self._c_total

    @property
    def deleted_rows(self) -> int:
        return len(self._deleted)

    @property
    def expected_precision(self) -> float:
        return expected_precision(
            max(self.n_rows, 1), self._c_total, self.config.k,
            self.config.big_k,
        )

    @property
    def predicted_recall(self) -> Optional[float]:
        """Worst shard-local calibration estimate (None when homogeneous)."""
        vals = [sh.predicted_recall for sh in self._shards]
        if any(v is None for v in vals):
            return None
        return min(vals)

    @property
    def partition_formats(self) -> Optional[Tuple[str, ...]]:
        """Global-partition-order format names (None when homogeneous)."""
        if not self._hetero:
            return None
        out = []
        for sh in self._shards:
            out.extend(sh.partition_formats)
        return tuple(out)

    @property
    def n_cols(self) -> int:
        """Feature dimension (embedding width) of the collection."""
        return self._shards[0].n_cols

    @property
    def live_shard_fraction(self) -> float:
        """Fraction of shards currently serving (1.0 = full coverage)."""
        return (self.n_shards - len(self._dead_shards)) / self.n_shards

    @property
    def dead_shards(self) -> tuple:
        return tuple(sorted(self._dead_shards))

    @property
    def snapshot_buffers(self) -> int:
        return sum(sh.snapshot_buffers for sh in self._shards)

    @property
    def last_refresh_repadded(self) -> int:
        return sum(sh.last_refresh_repadded for sh in self._shards)

    @property
    def last_refresh_copied(self) -> int:
        return sum(sh.last_refresh_copied for sh in self._shards)

    @property
    def last_refresh_group_copied(self) -> int:
        return sum(sh.last_refresh_group_copied for sh in self._shards)

    @property
    def shards(self) -> tuple:
        """The shard-local mutable indexes (read-only introspection)."""
        return tuple(self._shards)

    def aggregate_stats(self) -> dict:
        """Collection-wide stream statistics summed over the shard packeds."""
        packs = [sh.packed for sh in self._shards]
        nnz = sum(p.nnz for p in packs)
        stream_bytes = sum(p.stream_bytes for p in packs)
        value_bytes = sum(p.value_stream_bytes for p in packs)
        delta = sum(p.delta_nnz for p in packs)
        hist: dict = {}
        for p in packs:
            for name, count in p.format_histogram().items():
                hist[name] = hist.get(name, 0) + count
        return {
            "n_cols": packs[0].n_cols,
            "nnz": nnz,
            "stream_bytes": stream_bytes,
            "bytes_per_nnz": stream_bytes / max(nnz, 1),
            "value_bytes_per_nnz": value_bytes / max(nnz, 1),
            "delta_fraction": delta / max(nnz, 1),
            "tombstone_count": sum(p.tombstone_count for p in packs),
            "stream_layout": self.config.stream_layout,
            "format_histogram": hist,
        }

    # -- mutation routing ----------------------------------------------------
    #
    # The single-device index places each appended row on the globally
    # least-loaded core (lowest index wins ties), computing the per-core
    # slot counts ONCE per batch and simulating the increments.  Routing
    # replays that simulation over the concatenated shard-major core list:
    # every item lands on the same core as it would single-device, and each
    # shard receives its items as ONE local append batch (preserving
    # relative order), so per-core groups — and therefore delta packets,
    # sentinels and slot structure — match the single-device index exactly.

    def _route(self, count: int) -> list:
        sizes = []
        for sh in self._shards:
            sizes.extend(len(slots) for slots in sh._slots)
        sizes = np.asarray(sizes, np.int64)
        dest = []
        for _ in range(count):
            ci = int(np.argmin(sizes))
            sizes[ci] += 1
            dest.append(ci // self._cps)
        return dest

    def _append_routed(self, items: Sequence[tuple]) -> None:
        """Append (gid, normalized row) items, one local batch per shard."""
        dest = self._route(len(items))
        per_shard: dict = {}
        for (gid, row), s in zip(items, dest):
            per_shard.setdefault(s, []).append((gid, row))
        for s in sorted(per_shard):
            sh = self._shards[s]
            batch = per_shard[s]
            base = len(self._l2g[s])
            lids = sh.add_rows([row for _, row in batch])
            assert lids[0] == base, "shard-local id space out of sync"
            for (gid, _), lid in zip(batch, lids):
                self._l2g[s].append(gid)
                self._live[gid] = (s, lid)

    def add_rows(self, rows: Sequence[tuple]) -> list:
        """Append new rows; returns their freshly assigned global row ids."""
        if not rows:
            return []
        normalized = [
            MutableTopKSpMVIndex._normalize_row(c, v)
            for c, v in rows
        ]
        gids = list(range(self._next_gid, self._next_gid + len(rows)))
        self._next_gid += len(rows)
        self._append_routed(list(zip(gids, normalized)))
        self._bump()
        return gids

    def replace_rows(self, row_ids: Sequence[int], rows: Sequence[tuple]):
        """Replace rows in place of their global ids (resurrects deleted ids).

        The old copy's slot is tombstoned on its current shard; the new copy
        appends wherever the global greedy placement sends it — a replace
        may MOVE a row between shards, which is why merges run on global
        ids (the shard-local maps need not stay monotone).
        """
        if len(row_ids) != len(rows):
            raise ValueError("row_ids and rows must be the same length")
        ids = self._validate_ids(row_ids)
        normalized = [
            MutableTopKSpMVIndex._normalize_row(c, v)
            for c, v in rows
        ]
        per_del: dict = {}
        for gid in ids:
            cur = self._live.pop(gid, None)
            if cur is not None:
                per_del.setdefault(cur[0], []).append(cur[1])
            self._deleted.discard(gid)
        for s in sorted(per_del):
            self._shards[s].delete_rows(per_del[s])
        self._append_routed(list(zip(ids, normalized)))
        self._bump()

    def delete_rows(self, row_ids: Sequence[int]) -> None:
        """Tombstone rows: never returned again, reclaimed at ``compact()``."""
        ids = self._validate_ids(row_ids, allow_duplicates=True)
        per: dict = {}
        for gid in ids:
            cur = self._live.pop(gid, None)
            if cur is not None:
                per.setdefault(cur[0], []).append(cur[1])
            self._deleted.add(gid)
        for s in sorted(per):
            self._shards[s].delete_rows(per[s])
        self._bump()

    def _validate_ids(self, row_ids, allow_duplicates=False) -> list:
        out = [int(g) for g in row_ids]
        for gid in out:
            if gid < 0 or gid >= self._next_gid:
                raise KeyError(f"row id {gid} was never assigned")
        if not allow_duplicates and len(set(out)) != len(out):
            raise ValueError("duplicate row ids in one replace batch")
        return out

    def _bump(self) -> None:
        self._version += 1
        self._live_csr_cache = None

    def live_csr(self) -> Tuple[bscsr_lib.CSRMatrix, np.ndarray]:
        """Live rows (gid-ascending) as one host CSR plus their global ids."""
        if self._live_csr_cache is not None and (
            self._live_csr_cache[0] == self._version
        ):
            return self._live_csr_cache[1]
        gids = np.asarray(sorted(self._live), dtype=np.int64)
        rows = []
        for gid in gids:
            s, lid = self._live[int(gid)]
            rows.append(self._shards[s]._rows[lid])
        lens = np.asarray([len(c) for c, _ in rows], dtype=np.int64)
        indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
        if rows:
            indices = np.concatenate([c for c, _ in rows])
            data = np.concatenate([v for _, v in rows])
        else:
            indices = np.zeros(0, np.int32)
            data = np.zeros(0, np.float32)
        n_cols = self._shards[0]._n_cols
        csr = bscsr_lib.CSRMatrix(
            indptr=indptr, indices=indices, data=data,
            shape=(int(gids.size), n_cols),
        )
        self._live_csr_cache = (self._version, (csr, gids))
        return csr, gids

    def compact(self) -> None:
        """Re-slice the live collection across shards at partition bounds.

        Each shard re-encodes its fresh contiguous run of the (gid-sorted)
        live rows — the sharded analogue of the single-device ``compact()``
        under the frozen partition count.  Global ids survive; shard-local
        id spaces restart (the generation counter keeps device caches from
        aliasing the restarted shard version counters).
        """
        csr, gids = self.live_csr()
        plan = partition_lib.PartitionPlan.build(csr.shape[0], self._c_total)
        bounds = [0]
        for i in range(self.n_shards):
            bounds.append(bounds[-1] + int(sum(
                plan.rows_per_partition[i * self._cps:(i + 1) * self._cps]
            )))
        self._live = {}
        for i in range(self.n_shards):
            sub = csr.row_slice(bounds[i], bounds[i + 1])
            self._shards[i] = MutableTopKSpMVIndex(
                sub, self._local_config
            )
            ids = [int(g) for g in gids[bounds[i]:bounds[i + 1]]]
            self._l2g[i] = ids
            for lid, gid in enumerate(ids):
                self._live[gid] = (i, lid)
        self._generation += 1
        self._row_maps = {}
        self._bump()

    # -- query dispatch ------------------------------------------------------

    def _row_map(self, s: int) -> np.ndarray:
        """Shard ``s``'s local->global id map, padded to its churn bucket.

        Entries past the shard's local id space are INVALID_ROW — the
        finalize mask turns them into the global sentinel, so padded-slot
        output matches the single-device index bit for bit.  The bucket
        shares the tombstone-bitmap discipline: power-of-two under
        ``churn_stable`` so the compiled signature survives local growth.
        """
        sh = self._shards[s]
        key = (self._generation, sh.version)
        cached = self._row_maps.get(s)
        if cached is not None and cached[0] == key:
            return cached[1]
        n = sh.n_rows_total
        assert len(self._l2g[s]) == n, "l2g out of sync with shard id space"
        ln = (
            kernel_ops.pow2_bucket(max(n, 1))
            if self.config.churn_stable else max(n, 1)
        )
        m = np.full(ln, _INVALID, np.int32)
        if n:
            m[:n] = np.asarray(self._l2g[s], np.int32)
        self._row_maps[s] = (key, m)
        return m

    def _gsent_scalar(self, device):
        """The current global row-id sentinel, pinned on ``device``."""
        cur = self._gsent.get(device)
        if cur is None or cur[0] != self._next_gid:
            val = np.int32(self._next_gid)
            arr = (
                jnp.asarray(val) if device is None
                else jax.device_put(val, device)
            )
            self._gsent[device] = (self._next_gid, arr)
        return self._gsent[device][1]

    def _shard_device(self, s: int):
        """Replica-0 device of shard ``s``'s mesh column (None off-mesh)."""
        if self.mesh is None:
            return None
        ax = self.mesh.axis_names.index("shard")
        return np.take(self.mesh.devices, s, axis=ax).flat[0]

    def _merge_device(self):
        return None if self.mesh is None else self.mesh.devices.flat[0]

    def query(self, x, use_kernel: bool = True):
        """Top-``big_k`` (values, global row ids) for one (M,) query."""
        if self._spmd is not None and use_kernel:
            return self._spmd.query(x)
        return self._per_shard_query(x, use_kernel, batched=False)

    def query_batched(self, xs, use_kernel: bool = True):
        """(Q, big_k) answers for a (Q, M) batch."""
        if self._spmd is not None and use_kernel:
            return self._spmd.query_batched(xs)
        return self._per_shard_query(xs, use_kernel, batched=True)

    def _per_shard_query(self, x, use_kernel, batched):
        """One executor dispatch per shard + jitted host-side tree merge.

        Every shard snapshot (streams + its l2g map + the override sentinel)
        is device-pinned, so the steady-state loop is S compiled calls and
        one compiled merge: zero host->device transfers, zero retraces
        until a shard's bucket doubles.

        **Failover:** a shard whose dispatch raises is marked dead and its
        pool dropped from the merge — ``merge_topk``'s sentinel
        normalisation makes an absent pool merge-safe, so the survivors'
        answer is exactly the full answer restricted to their rows.
        Queries then serve **degraded** (``last_query_degraded`` /
        ``live_shard_fraction``) until :meth:`recover_shard` re-pins the
        shard from its intact host copy.
        """
        ex = query_executor(self._local_config)
        path = "kernel" if use_kernel else "reference"
        layout = None
        if use_kernel and self._hetero and not self.native_groups:
            layout = "split"    # f32-twin fallback: exactly-dequantized
        merge_dev = self._merge_device()
        pools_v, pools_r = [], []
        for s, sh in enumerate(self._shards):
            if s in self._dead_shards:
                continue
            dev = self._shard_device(s)
            kw = dict(
                path=path, stream_layout=layout,
                row_map=self._row_map(s),
                row_map_key=("l2g", self._generation),
                device=dev, n_rows=self._gsent_scalar(dev),
            )
            try:
                faults_lib.fault_point("dispatch.shard")
                if batched:
                    v, r = ex.query_batched(x, sh.packed, **kw)
                else:
                    v, r = ex.query(x, sh.packed, **kw)
            except Exception:
                self._dead_shards.add(s)
                self.failovers += 1
                continue
            if dev is not None and dev != merge_dev:
                v = jax.device_put(v, merge_dev)   # device-to-device, big_k
                r = jax.device_put(r, merge_dev)   # floats/int32 per shard
            pools_v.append(v)
            pools_r.append(r)
        self.last_query_degraded = bool(self._dead_shards)
        if not pools_v:
            raise RuntimeError(
                "all shards failed dispatch — no pools to merge (recover "
                "with recover_shard() or rebuild from a checkpoint)"
            )
        merge = _host_merge_fn(len(pools_v), self.config.big_k, batched)
        return merge(self._gsent_scalar(merge_dev), *pools_v, *pools_r)

    def spmv(self, x, alpha, beta, y, use_kernel: bool = True):
        """``alpha * A @ x + beta * y`` over the sharded collection.

        The accumulate-mode (``select_topk=False``) sharded dispatch: each
        shard computes its rows' partial products in the *global* row space
        (``y``'s length fixes it), and the partials reduce with a dense
        ``psum`` over the shard axis instead of the top-k tree merge —
        bit-identical to the single-device scatter because every global row
        is owned by exactly one shard (the off-owner lanes are literal
        zeros).  Iterative graph solvers (``core.graph``) drive this with
        device-pinned ``alpha``/``beta``/``y`` for zero-transfer steps.
        """
        n_out = int(y.shape[0])
        if n_out < self._next_gid:
            raise ValueError(
                f"y has {n_out} rows but the global id space holds "
                f"{self._next_gid} — accumulate output must cover every id"
            )
        if self._dead_shards:
            raise RuntimeError(
                "accumulate-mode SpMV needs every shard (a degraded partial "
                f"product is silently wrong); recover shards "
                f"{sorted(self._dead_shards)} first"
            )
        if self._spmd is not None and use_kernel:
            return self._spmd.spmv(x, alpha, beta, y)
        return self._per_shard_spmv(x, alpha, beta, y, use_kernel)

    def _per_shard_spmv(self, x, alpha, beta, y, use_kernel):
        """One accumulate dispatch per shard + jitted partial-sum combine."""
        ex = query_executor(self._local_config)
        path = "accumulate" if use_kernel else "accumulate_ref"
        layout = None
        if use_kernel and self._hetero and not self.native_groups:
            layout = "split"    # f32-twin fallback: exactly-dequantized
        merge_dev = self._merge_device()
        n_out = int(y.shape[0])
        parts = []
        for s, sh in enumerate(self._shards):
            dev = self._shard_device(s)
            one, zero = _pinned_unit_scalars(dev)
            p = ex.spmv(
                x, sh.packed, alpha=one, beta=zero,
                y=_pinned_zeros(n_out, dev), path=path, stream_layout=layout,
                row_map=self._row_map(s),
                row_map_key=("l2g", self._generation), device=dev,
            )
            if dev is not None and dev != merge_dev:
                p = jax.device_put(p, merge_dev)   # device-to-device
            parts.append(p)
        return _combine_partials_fn(len(parts))(alpha, beta, y, *parts)

    def recover_shard(self, s: int) -> None:
        """Return a dead shard to serving, re-pinned from its host copy.

        The shard-local index (host arrays) survives a device/dispatch
        failure untouched — mutations keep applying to it while the shard
        is dead.  Recovery evicts the shard's device-cache pins (so the
        next dispatch re-places fresh copies of the CURRENT snapshot) and
        clears the dead mark.  If the host copy were lost too, rebuild the
        whole index from a ``DurableIndexStore`` checkpoint instead.
        """
        if not (0 <= s < self.n_shards):
            raise ValueError(f"shard {s} out of range (0..{self.n_shards - 1})")
        executor_lib.evict_snapshot(self._shards[s].packed.uid)
        self._dead_shards.discard(s)
        self.last_query_degraded = bool(self._dead_shards)

    def dispatch_info(self) -> dict:
        """Topology + per-shard serving counters (docs/SERVING.md)."""
        info = {
            "path": "spmd" if self._spmd is not None else "per_shard",
            "topology": {
                "n_shards": self.n_shards,
                "n_replicas": self.n_replicas,
                "partitions_per_shard": self._cps,
                "mesh_axes": (
                    dict(zip(self.mesh.axis_names,
                             (int(n) for n in self.mesh.devices.shape)))
                    if self.mesh is not None else None
                ),
            },
            "churn_stable": self.config.churn_stable,
            "health": {
                "dead_shards": list(self.dead_shards),
                "live_shard_fraction": self.live_shard_fraction,
                "failovers": self.failovers,
                "last_query_degraded": self.last_query_degraded,
            },
            "per_shard": [
                {
                    "version": sh.version,
                    "row_map_bucket": int(self._row_map(s).shape[0]),
                    "signature": sh.packed.signature_info(),
                }
                for s, sh in enumerate(self._shards)
            ],
        }
        if self._spmd is not None:
            info.update(self._spmd.info())
        else:
            info.update(query_executor(self._local_config).cache_info())
        return info


class _SpmdDispatcher:
    """shard_map dispatch: one compiled fn runs kernel + finalize + tree
    merge across the whole mesh, against bundle-assembled sharded arrays."""

    def __init__(self, owner: ShardedTopKSpMVIndex):
        self.owner = owner
        self.mesh = owner.mesh
        self.s_count = owner.n_shards
        self.bundle = executor_lib.ShardedDeviceBundle(self.mesh, "shard")
        self.layout = (
            "split" if owner._hetero else owner.config.stream_layout
        )
        cfg = owner.config
        self._interpret = cfg.resolve_interpret()
        self._gather = kernel_ops.resolve_gather_mode(cfg.gather_mode)
        # Queries fan out over the replica axis when the mesh has one (the
        # logical axes live in sharding.rules so serving and model planes
        # share one rules table).
        self._rep_axis = rules_lib._present(
            self.mesh, rules_lib.DEFAULT_RULES.lookup("topk_queries")
        )
        self.r_count = (
            int(self.mesh.shape[self._rep_axis]) if self._rep_axis else 1
        )
        self._fns: dict = {}       # (q bucket | None, signature) -> jitted fn
        self._last_sig: dict = {}  # q bucket -> signature it last compiled
        self.fn_builds = 0
        self.retraces = 0
        self.dispatches = 0
        # Batched fn reuse split by padded-bucket vs exact-bucket hits —
        # mirrors QueryExecutor.cache_info (docs/SERVING.md).
        self.q_bucket_hits = 0
        self.q_exact_hits = 0
        self.seg_slots = None      # the last synced signature's segment space

    # -- device sync ---------------------------------------------------------

    def _sync(self):
        """Assemble the global sharded arrays, shipping only changed bytes.

        Per-shard blocks pad to COMMON buckets (max over shards per dim) so
        one compiled fn serves every shard; a single shard outgrowing its
        bucket re-buckets the family (O(log growth) rebuilds, like the
        single-device churn-stable discipline).  Stream families ship at
        partition granularity via the COW mutation stamps.
        """
        o = self.owner
        shards = o._shards
        packs = [sh.packed for sh in shards]
        versions = [(o._generation, sh.version) for sh in shards]
        cps = o._cps
        fused = self.layout == "fused"

        def pad_dim1(a, width, fill=0):
            if a.shape[1] == width:
                return a
            out = np.full(a.shape[:1] + (width,) + a.shape[2:], fill, a.dtype)
            out[:, :a.shape[1]] = a
            return out

        def pad_dim0(a, width, fill=0):
            if a.shape[0] == width:
                return a
            out = np.full((width,) + a.shape[1:], fill, a.dtype)
            out[:a.shape[0]] = a
            return out

        arrs = []
        # Offset stamps by the generation: compact() rebuilds shard-local
        # indexes whose stamp counters RESTART, and a coincidental stamp
        # match must not suppress shipping the re-encoded partitions.
        gen_off = np.int64(o._generation) << np.int64(33)
        stamps = [sh._part_stamps + gen_off for sh in shards]
        if fused:
            p_common = max(p.fused_words().shape[1] for p in packs)
            w_words = packs[0].fused_words().shape[2]

            def words_fn(s):
                return pad_dim1(np.asarray(packs[s].fused_words()), p_common)

            arrs.append(self.bundle.sync(
                "words", (cps, p_common, w_words), np.int32, words_fn,
                versions, stamps=stamps,
            ))
        else:
            p_common = max(p.vals.shape[1] for p in packs)
            for name in ("vals", "cols", "flags"):
                ref = getattr(packs[0], name)

                def block_fn(s, _name=name):
                    return pad_dim1(
                        np.asarray(getattr(packs[s], _name)), p_common
                    )

                arrs.append(self.bundle.sync(
                    name, (cps, p_common, ref.shape[2]), ref.dtype,
                    block_fn, versions, stamps=stamps,
                ))
        l_common = max(p.slot_to_row.shape[1] for p in packs)
        arrs.append(self.bundle.sync(
            "slot", (cps, l_common), np.int32,
            lambda s: pad_dim1(packs[s].slot_to_row, l_common, _INVALID),
            versions,
        ))
        arrs.append(self.bundle.sync(
            "nslots", (cps,), np.int32,
            lambda s: np.asarray(packs[s].candidate_slots, np.int32),
            versions,
        ))
        tl_common = max(p.tombstones.shape[0] for p in packs)
        arrs.append(self.bundle.sync(
            "tombs", (tl_common,), bool,
            lambda s: pad_dim0(packs[s].tombstones, tl_common),
            versions,
        ))
        maps = [o._row_map(s) for s in range(self.s_count)]
        lg_common = max(m.shape[0] for m in maps)
        arrs.append(self.bundle.sync(
            "l2g", (lg_common,), np.int32,
            lambda s: pad_dim0(maps[s], lg_common, _INVALID),
            versions,
        ))
        gsent = self.bundle.sync_replicated(
            "gsent", np.asarray(o._next_gid, np.int32), o._next_gid
        )
        args = tuple(arrs) + (gsent,)
        # One compiled fn serves every shard: the segment space is the
        # largest shard's, checked against each before it is served.
        t = o.config.packets_per_step
        seg_slots = max(p.seg_slots(t) for p in packs)
        for p in packs:
            kernel_ops.check_segment_slots(p, t, seg_slots)
        self.seg_slots = seg_slots
        sig = (
            self.layout,
            tuple((a.shape, str(a.dtype)) for a in args),
            seg_slots,
        )
        return args, sig

    # -- compiled fn ---------------------------------------------------------

    def _build_spmv(self, n_out: int, args, seg_slots: int):
        """One compiled accumulate fn: per-shard kernel + global-row scatter,
        reduced with a dense ``psum`` over the shard axis (no top-k merge).

        Replicas each hold a full copy of every shard, so the psum over
        "shard" alone already yields the complete ``A @ x`` on every device —
        the replica axis needs no reduction (all replica groups compute the
        same value), and every in/out other than the matrix streams is
        replicated.
        """
        o = self.owner
        cfg = o.config
        mesh = self.mesh
        cps = o._cps
        layout = self.layout
        n_streams = 1 if layout == "fused" else 3
        max_slots = int(args[n_streams].shape[2])  # common slot bucket
        pack0 = o._shards[0].packed
        kwargs = dict(
            n_rows=max_slots,
            packets_per_step=cfg.packets_per_step,
            fmt_name=pack0.value_format.name,
            gather_mode=self._gather,
            inner_loop=cfg.inner_loop,
            stream_layout=layout, block_size=pack0.block_size,
            seg_slots=seg_slots, interpret=self._interpret,
        )

        def body(x, alpha, beta, y, *arrs):
            streams = [a[0] for a in arrs[:n_streams]]
            slot = arrs[n_streams][0]
            nslots = arrs[n_streams + 1][0]
            tombs = arrs[n_streams + 2][0]
            l2g = arrs[n_streams + 3][0]
            sums = bscsr_spmv(jnp.asarray(x, jnp.float32), *streams, **kwargs)
            partial = kernel_ops.scatter_slot_sums(
                sums, jnp.zeros((cps,), jnp.int32), nslots, n_out,
                slot_to_row=slot, tombstones=tombs, row_map=l2g,
            )
            ax = jax.lax.psum(partial, "shard")
            return alpha * ax + beta * y

        rep = PartitionSpec()
        shard_spec = rules_lib.logical_to_spec(
            ("topk_shards",), (self.s_count,), mesh
        )
        in_specs = (
            (rep, rep, rep, rep)
            + (shard_spec,) * (len(args) - 1) + (rep,)
        )
        out_specs = rep
        fn = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,  # pallas_call outputs carry no vma info
        )
        return jax.jit(
            fn,
            in_shardings=tuple(NamedSharding(mesh, sp) for sp in in_specs),
            out_shardings=NamedSharding(mesh, out_specs),
        )

    def _build(self, q: Optional[int], args, seg_slots: int):
        if isinstance(q, tuple) and q[0] == "spmv":
            return self._build_spmv(q[1], args, seg_slots)
        o = self.owner
        cfg = o.config
        mesh = self.mesh
        s_count = self.s_count
        cps = o._cps
        big_k, k = cfg.big_k, cfg.k
        layout = self.layout
        n_streams = 1 if layout == "fused" else 3
        # args: streams..., slot, nslots, tombs, l2g, gsent
        max_slots = int(args[n_streams].shape[2])  # common slot bucket
        pack0 = o._shards[0].packed
        kernel = bscsr_topk_spmv if q is None else bscsr_topk_spmv_multiquery
        kwargs = dict(
            k=k, n_rows=max_slots,
            packets_per_step=cfg.packets_per_step,
            fmt_name=pack0.value_format.name,
            gather_mode=self._gather,
            inner_loop=cfg.inner_loop,
            stream_layout=layout, block_size=pack0.block_size,
            seg_slots=seg_slots, interpret=self._interpret,
        )

        def merge_pair(v1, r1, v2, r2, gsent):
            def m(a, b, c, d):
                return partition_lib.merge_topk(
                    jnp.concatenate([a, c]), jnp.concatenate([b, d]),
                    big_k, gsent,
                )

            if q is None:
                return m(v1, r1, v2, r2)
            return jax.vmap(m)(v1, r1, v2, r2)

        def tree_merge(fv, fr, gsent):
            if s_count & (s_count - 1) == 0:
                # Power-of-two shard counts: log2(S) XOR-partner rounds.
                step = 1
                while step < s_count:
                    perm = [(i, i ^ step) for i in range(s_count)]
                    pv = jax.lax.ppermute(fv, "shard", perm)
                    pr = jax.lax.ppermute(fr, "shard", perm)
                    fv, fr = merge_pair(fv, fr, pv, pr, gsent)
                    step <<= 1
                return fv, fr
            # Non-power-of-two: one all_gather + flat merge (bit-identical —
            # merge_topk normalises masked entries, so tree == flat).
            av = jax.lax.all_gather(fv, "shard")
            ar = jax.lax.all_gather(fr, "shard")
            if q is None:
                return partition_lib.merge_topk(av, ar, big_k, gsent)
            return jax.vmap(
                lambda a, b: partition_lib.merge_topk(a, b, big_k, gsent),
                in_axes=(1, 1),
            )(av, ar)

        def body(x, *arrs):
            streams = [a[0] for a in arrs[:n_streams]]
            slot = arrs[n_streams][0]
            nslots = arrs[n_streams + 1][0]
            tombs = arrs[n_streams + 2][0]
            l2g = arrs[n_streams + 3][0]
            gsent = arrs[n_streams + 4]
            lv, lr = kernel(jnp.asarray(x, jnp.float32), *streams, **kwargs)
            finalize = (
                kernel_ops.finalize_candidates if q is None
                else kernel_ops.finalize_candidates_batched
            )
            fv, fr = finalize(
                lv, lr, jnp.zeros((cps,), jnp.int32), nslots, big_k, gsent,
                slot_to_row=slot, tombstones=tombs, row_map=l2g,
            )
            if s_count > 1:
                fv, fr = tree_merge(fv, fr, gsent)
            return fv, fr

        if q is not None and self._rep_axis:
            xspec = rules_lib.logical_to_spec(("topk_queries",), (q,), mesh)
        else:
            xspec = PartitionSpec()
        shard_spec = rules_lib.logical_to_spec(
            ("topk_shards",), (self.s_count,), mesh
        )
        in_specs = (
            (xspec,) + (shard_spec,) * (len(args) - 1) + (PartitionSpec(),)
        )
        out_specs = (xspec, xspec)
        fn = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,  # pallas_call outputs carry no vma info
        )
        return jax.jit(
            fn,
            in_shardings=tuple(NamedSharding(mesh, sp) for sp in in_specs),
            out_shardings=tuple(NamedSharding(mesh, sp) for sp in out_specs),
        )

    def _fn(self, q: Optional[int], args, sig):
        key = (q, sig)
        fn = self._fns.get(key)
        if fn is None:
            # A signature change means a common bucket moved: every cached
            # fn of the old signature is stale, drop them all.
            self._fns = {kk: f for kk, f in self._fns.items() if kk[1] == sig}
            fn = self._build(q, args, sig[2])
            self._fns[key] = fn
            self.fn_builds += 1
            prev = self._last_sig.get(q)
            if prev is not None and prev != sig:
                self.retraces += 1
            self._last_sig[q] = sig
        return fn

    # -- dispatch ------------------------------------------------------------

    def _place_x(self, x, spec):
        sharding = NamedSharding(self.mesh, spec)
        if isinstance(x, jax.Array) and x.sharding == sharding:
            return x   # pre-placed by the caller: zero transfers
        return jax.device_put(np.asarray(x, np.float32), sharding)

    def query(self, x):
        args, sig = self._sync()
        fn = self._fn(None, args, sig)
        self.dispatches += 1
        return fn(self._place_x(x, PartitionSpec()), *args)

    def _place_rep(self, v):
        """Replicate a scalar/vector across the mesh (no-op if pre-placed)."""
        sharding = NamedSharding(self.mesh, PartitionSpec())
        if isinstance(v, jax.Array) and v.sharding == sharding:
            return v   # already replicated: zero transfers
        return jax.device_put(jnp.asarray(v, jnp.float32), sharding)

    def spmv(self, x, alpha, beta, y):
        args, sig = self._sync()
        fn = self._fn(("spmv", int(y.shape[0])), args, sig)
        self.dispatches += 1
        return fn(
            self._place_x(x, PartitionSpec()), self._place_rep(alpha),
            self._place_rep(beta), self._place_rep(y), *args,
        )

    def query_batched(self, xs):
        args, sig = self._sync()
        q = int(np.asarray(xs).shape[0] if not isinstance(xs, jax.Array)
                else xs.shape[0])
        if q == 0:
            raise ValueError("xs must be a non-empty (Q, M) batch")
        r = self.r_count
        bucket = r * executor_lib._q_bucket(-(-q // r))
        if isinstance(xs, jax.Array) and xs.shape[0] == bucket:
            q = bucket     # caller pre-padded and pre-placed
        elif bucket != q:
            xs = np.asarray(xs, np.float32)
            xs = np.concatenate(
                [xs, np.zeros((bucket - q, xs.shape[1]), np.float32)]
            )
        builds_before = self.fn_builds
        fn = self._fn(bucket, args, sig)
        if self.fn_builds == builds_before:  # reused a compiled fn
            if bucket != q:
                self.q_bucket_hits += 1      # padded into a shared bucket
            else:
                self.q_exact_hits += 1
        self.dispatches += 1
        xspec = (
            rules_lib.logical_to_spec(
                ("topk_queries",), (bucket,), self.mesh
            ) if self._rep_axis else PartitionSpec()
        )
        vals, rows = fn(self._place_x(xs, xspec), *args)
        if bucket != q:
            vals, rows = executor_lib._query_unpadder(q)(vals, rows)
        return vals, rows

    def info(self) -> dict:
        return {
            "compiled_fns": len(self._fns),
            "fn_builds": self.fn_builds,
            "retraces": self.retraces,
            "dispatches": self.dispatches,
            "q_bucket_hits": self.q_bucket_hits,
            "q_exact_hits": self.q_exact_hits,
            "interpret": self._interpret,
            "gather_mode": self._gather,
            "seg_slots": {} if self.seg_slots is None else {self.seg_slots: 1},
            "bundle": self.bundle.counters(),
        }

"""High-level Top-K SpMV API: exact / approximate / mesh-distributed.

Distribution model (DESIGN.md §2): the paper's c cores = (devices on the mesh
"data" axis) x (sub-partitions per device).  Each device streams its local
BS-CSR partitions through the Pallas kernel; only the c*k candidate (value,
row) pairs cross ICI in one small all-gather before the final merge — the
paper's "no output write-back" argument, restated as "no large collective".
"""
from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import adaptive as adaptive_lib
from repro.core import bscsr as bscsr_lib
from repro.core import faults as faults_lib
from repro.core import partition as partition_lib
from repro.core.precision_model import expected_precision, min_partitions_for_precision
from repro.core.quantization import F32, FORMATS, width_class_of
from repro.kernels import executor as executor_lib
from repro.kernels import ops as kernel_ops
from repro.kernels import ref as ref_lib
from repro.utils.tracing import span

@dataclasses.dataclass(frozen=True)
class TopKSpMVConfig:
    """User-facing knobs; mirrors the paper's design space (Table II)."""

    big_k: int = 100               # K
    k: int = 8                     # per-core scratchpad size (paper: 8)
    num_partitions: Optional[int] = None   # c; None -> auto from precision target
    precision_target: float = 0.99
    block_size: int = 256          # B (nnz per tile-packet)
    value_format: str = "F32"      # F32 | BF16 | Q15 | Q7 (uniform)
    recall_target: Optional[float] = None  # per-partition mixed precision:
                                   # autotune one ValueFormat per partition so
                                   # predicted quantization-induced recall@k
                                   # vs exact stays >= this target (overrides
                                   # value_format; see core/adaptive.py)
    calibration_queries: int = 16  # query sample size for the autotuner
    calibration_seed: int = 0      # deterministic per (seed, collection)
    packets_per_step: int = 2      # T
    gather_mode: str = "auto"      # onehot (= auto, the compiled MXU gather)
                                   # | take (interpret-only reference gather)
    inner_loop: str = "linear"     # linear | legacy (+ mixed, for parity tests)
    stream_layout: str = "fused"   # fused (one burst/step) | split (legacy 3-array)
    incremental_snapshots: bool = True  # mutable index: re-pad only mutated parts
    use_executor: bool = True      # device-resident snapshot plane + compiled
                                   # query fns (False: per-call upload dispatch)
    cow_snapshots: bool = True     # mutable index: copy-on-write stacked buffers
                                   # (False: legacy O(bytes) np.stack per refresh)
    parallel_compaction: bool = True  # compact(): re-encode partitions in a pool
    parallel_compaction_min_nnz: int = 100_000  # per-partition nnz below which
                                   # compact() stays serial (pool dispatch and
                                   # GIL-bound numpy beat tiny encodes)
    churn_stable: bool = True      # mutable index: pad the churn-varying
                                   # snapshot dims (tombstone length, slot-map
                                   # width, packet count) to power-of-two
                                   # buckets so serve-while-ingest reuses ONE
                                   # compiled signature per bucket — zero
                                   # retraces between bucket doublings.
                                   # False: exact dims (retrace per refresh).
    interpret: Optional[bool] = None  # None -> compiled on a TPU, interpreted
                                      # on the CPU, refused elsewhere

    def resolve_partitions(self, n_rows: int) -> int:
        if self.num_partitions is not None:
            return self.num_partitions
        c = min_partitions_for_precision(
            n_rows, self.k, self.big_k, self.precision_target
        )
        return max(c, -(-self.big_k // self.k))

    def resolve_interpret(self) -> bool:
        return kernel_ops.resolve_interpret(self.interpret)


@dataclasses.dataclass(frozen=True)
class TopKSpMVIndex:
    """An immutable, queryable packed index over one embedding collection."""

    packed: kernel_ops.PackedPartitions
    config: TopKSpMVConfig
    format_plan: Optional[adaptive_lib.PartitionFormatPlan] = None

    @property
    def n_rows(self) -> int:
        return self.packed.plan.n_rows

    @property
    def expected_precision(self) -> float:
        return expected_precision(
            self.n_rows, self.packed.num_cores, self.config.k, self.config.big_k
        )


def build_index(csr: bscsr_lib.CSRMatrix, config: TopKSpMVConfig) -> TopKSpMVIndex:
    c = config.resolve_partitions(csr.shape[0])
    fmt_plan = None
    value_formats = None
    if config.recall_target is not None:
        fmt_plan, _ = adaptive_lib.assign_partition_formats(
            csr, c, config.recall_target, k=config.k,
            n_queries=config.calibration_queries, seed=config.calibration_seed,
        )
        value_formats = fmt_plan.formats
    packed = kernel_ops.pack_partitions(
        csr,
        num_partitions=c,
        block_size=config.block_size,
        value_format=config.value_format,
        packets_multiple=config.packets_per_step,
        stream_layout=config.stream_layout,
        value_formats=value_formats,
    )
    return TopKSpMVIndex(packed=packed, config=config, format_plan=fmt_plan)


class MutableTopKSpMVIndex:
    """A live, serve-while-ingest index: base + per-partition delta segments.

    Rows can be appended (``add_rows``), replaced (``replace_rows`` =
    tombstone the old copy + append the new one) and deleted
    (``delete_rows``) without re-encoding the stream: updates are encoded as
    delta tile-packets (``bscsr.encode_delta_rows``) and concatenated after
    the owning partition's stream (``bscsr.append_packets``), while retired
    slots and deleted row ids are masked host-side in
    ``finalize_candidates``.  The kernel body is untouched.

    Every update batch swaps in a fresh immutable ``PackedPartitions``
    snapshot under a ``version`` counter — queries holding the previous
    snapshot (e.g. an in-flight batch, or ``compact()`` re-encoding one
    partition at a time) keep answering consistently from it.

    Duck-types ``TopKSpMVIndex`` (``.packed`` / ``.config``), so
    ``topk_spmv`` / ``topk_spmv_batched`` / ``distributed_topk_spmv_fn``
    work unchanged on the current snapshot.

    Note on precision: tombstoned slots still flow through the kernel's
    per-core top-k scratchpad until ``compact()`` reclaims them, so heavy
    churn transiently costs candidate slots (delta fraction and tombstone
    count are exposed for compaction policies).

    Cost model: mutations never *re-encode* existing packets, and with
    ``config.incremental_snapshots`` (the default) a refresh re-pads (and,
    for the fused layout, re-fuses) ONLY the partitions whose stream mutated
    since the last snapshot — unmutated partitions reuse their cached padded
    arrays (``last_refresh_repadded`` counts re-padded partitions; a growth
    of the common step-aligned packet count forces an all-partition re-pad).
    With ``config.cow_snapshots`` (the default) the final stacking is
    copy-on-write too: snapshots lease read-only views of preallocated
    stacked buffers (``kernel_ops.SnapshotBufferPool``) and only mutated
    partitions' rows are rewritten, so a steady-state refresh is O(mutated
    partitions) end to end (``last_refresh_copied`` counts buffer copies).
    ``cow_snapshots=False`` restores the legacy O(index bytes) ``np.stack``
    per refresh; ``incremental_snapshots=False`` additionally restores the
    re-pad-everything behavior.  Frozen snapshots stay bit-identical either
    way — a buffer is recycled only after every snapshot leasing it has been
    garbage collected.
    """

    def __init__(self, csr: bscsr_lib.CSRMatrix, config: TopKSpMVConfig):
        with span("index.build", kind="init", rows=int(csr.shape[0]),
                  nnz=int(csr.nnz)):
            self._build(csr, config)

    def _build(self, csr: bscsr_lib.CSRMatrix, config: TopKSpMVConfig) -> None:
        """The body of ``__init__``, one child span per build stage."""
        self.config = config
        self._n_cols = csr.shape[1]
        self._fmt = FORMATS[config.value_format]
        c = config.resolve_partitions(csr.shape[0])
        self._plan = partition_lib.PartitionPlan.build(csr.shape[0], c)
        with span("index.partition", partitions=c):
            parts = partition_lib.partition_csr(csr, self._plan)
        # Mixed-precision plane (config.recall_target): three aligned stream
        # copies per partition — ``_exact`` (F32, the structural + numeric
        # source of truth), ``_native`` (the partition's assigned format,
        # what the tagged fused groups actually stream) and ``_streams``
        # (= dequantize(_native), the f32 twins the split/reference plane and
        # the existing pad/stack machinery consume).  All three share one
        # flags/cols structure, so slot bookkeeping is format-oblivious.
        self._part_fmts: Optional[list] = None
        self._calib: Optional[adaptive_lib.PrecisionCalibration] = None
        self._exact: Optional[list] = None
        self._native: Optional[list] = None
        self.last_refresh_promoted = 0
        if config.recall_target is not None:
            fmt_plan, calib = adaptive_lib.assign_partition_formats(
                csr, c, config.recall_target, k=config.k,
                n_queries=config.calibration_queries,
                seed=config.calibration_seed,
            )
            self._part_fmts = list(fmt_plan.formats)
            self._calib = calib
            self._fmt = F32  # the split twin plane is uniformly f32
            self._exact = [
                self._encode(ci, p, F32) for ci, p in enumerate(parts)
            ]
            self._native = [
                bscsr_lib.requantize_stream(e, FORMATS[f])
                for e, f in zip(self._exact, self._part_fmts)
            ]
            self._streams = [
                bscsr_lib.dequantize_stream(n) for n in self._native
            ]
        else:
            self._streams = [
                self._encode(ci, p, self._fmt) for ci, p in enumerate(parts)
            ]
        self._base_packets = max(e.num_packets for e in self._streams)
        with span("index.row_maps", rows=int(csr.shape[0])):
            self._slots = [
                list(range(start, start + size))
                for start, size in zip(
                    self._plan.row_starts, self._plan.rows_per_partition
                )
            ]
            self._loc = {
                gid: (ci, si)
                for ci, slots in enumerate(self._slots)
                for si, gid in enumerate(slots)
            }
            cols_split = np.split(csr.indices, csr.indptr[1:-1])
            data_split = np.split(csr.data, csr.indptr[1:-1])
            self._rows = {
                gid: (cols_split[gid].astype(np.int32), data_split[gid])
                for gid in range(csr.shape[0])
            }
        self._deleted = bscsr_lib.TombstoneBitmap.empty(csr.shape[0])
        self._next_gid = csr.shape[0]
        self._live_nnz = csr.nnz
        self._delta_nnz = 0
        self._dead_nnz = 0
        self._tombstone_slots = 0
        self._version = -1
        self._packed: Optional[kernel_ops.PackedPartitions] = None
        self._live_csr_cache = None  # (version, (csr, gids))
        self._buffer_pool = kernel_ops.SnapshotBufferPool()
        self._stamp_counter = 0
        self._reset_padded_cache()
        self.last_refresh_repadded = 0   # partitions re-padded by the last refresh
        self.total_repadded = 0
        self.last_refresh_copied = 0     # partitions copied into the COW stack
        self.total_copied = 0
        self.last_refresh_group_copied = 0  # member streams copied into the
        self.total_group_copied = 0         # COW width-class group stacks
        self.last_compact_parallel = False
        self._refresh()

    def _encode(self, ci: int, part: bscsr_lib.CSRMatrix, fmt):
        """BS-CSR encode of partition ``ci`` (an ``index.encode`` span)."""
        with span("index.encode", partition=ci, nnz=int(part.nnz)):
            return bscsr_lib.encode_bscsr(part, self.config.block_size, fmt)

    def _reset_padded_cache(self) -> None:
        """Invalidate the per-partition padded-stream (+ fused words) cache."""
        c = len(self._streams)
        self._dirty = set(range(c))
        self._mutated = set()  # content-mutated since the last refresh
        self._padded_streams = [None] * c
        self._padded_words = [None] * c
        self._padded_max_p = -1
        # Churn-stable packet cap: re-anchored at the exact (step-aligned)
        # count on build/compact, bumped to pow2 buckets by growth.
        self._packet_cap = -1
        # Mixed-precision plane: per-width-class packet caps (same
        # anchor-then-bucket discipline, one cap per TAG class) and the
        # per-partition padded tagged-word cache: ci -> (cap, fmt, words).
        self._class_caps: Optional[dict] = None
        self._padded_tagged = [None] * c
        # Churn-stable floor of the kernels' segment space (same anchor-
        # then-grow discipline), from each partition's most row starts in
        # one grid step, kept per partition so a refresh rescans only the
        # mutated ones.
        self._seg_cap = -1
        self._part_starts = [0] * c
        # All partitions' content is new: stamp them past every COW buffer.
        self._stamp_counter += 1
        self._part_stamps = np.full(c, self._stamp_counter, np.int64)

    def _mark_dirty(self, ci: int) -> None:
        """Record that partition ``ci``'s stream content changed."""
        self._dirty.add(ci)
        self._mutated.add(ci)
        self._stamp_counter += 1
        self._part_stamps[ci] = self._stamp_counter

    # -- snapshot bookkeeping ------------------------------------------------

    def _refresh(self, preserve_caps: bool = False) -> None:
        """Swap in a fresh immutable snapshot (bumps the version counter).

        ``preserve_caps`` is the checkpoint-restore mode: the churn-stable
        packet / width-class caps were restored verbatim from the manifest
        and must be used as-is (neither re-anchored nor re-bucketed), so a
        recovered index reproduces the crashed process's padded shapes —
        and therefore its executor signature — exactly.

        Crash atomicity: everything below builds into locals; the served
        ``self._packed`` is replaced by ONE assignment at the very end.  A
        failure anywhere before the swap (see the ``faults.fault_point``
        hooks) leaves the previous snapshot serving bit-identically, and a
        retry of :meth:`refresh` converges — the padded-stream cache and
        COW leases are idempotent given unchanged stream state.

        Incremental by default: padded per-partition streams (and, for the
        fused layout, their fused word forms) are cached, so only partitions
        whose stream mutated since the last snapshot pay a re-pad/re-fuse —
        unless the common step-aligned packet count changed, which re-pads
        everyone.  With ``cow_snapshots`` the stacked snapshot arrays are
        copy-on-write buffer leases (only mutated partitions' rows written);
        otherwise they are freshly ``np.stack``-ed every time.  Frozen older
        snapshots are never aliased by later updates in either mode.

        With ``config.churn_stable`` (the default) every churn-varying dim
        of the snapshot — padded packet count, slot-map width, tombstone
        bitmap length — is padded to a power-of-two bucket, so consecutive
        refreshes produce shape-identical snapshots and the executor's
        compiled query fns are reused with ZERO retraces until a bucket
        doubles (docs/ARCHITECTURE.md, "where does a query retrace?").
        """
        with span("index.refresh") as s:
            self._swap_snapshot(preserve_caps)
            s.set_metadata(version=self._version,
                           partitions_copied=self.last_refresh_copied)

    def _swap_snapshot(self, preserve_caps: bool) -> None:
        """The body of :meth:`_refresh`."""
        hetero = self._part_fmts is not None
        # Mixed-precision snapshots never carry uniform fused words — their
        # fused dispatch plane is the per-width-class tagged groups below.
        fused = self.config.stream_layout == "fused" and not hetero
        mult = self.config.packets_per_step
        # Promote-only format hysteresis: re-score mutated partitions against
        # the stored calibration; promote the worst offenders up the byte
        # ladder only if the recall budget is breached.  Benign upserts keep
        # the format vector — and the executor signature — bit-stable;
        # demotions wait for the full re-assignment at compact().
        self.last_refresh_promoted = 0
        if hetero and self._mutated and self._calib is not None:
            mutated = {
                ci: self._partition_live_csr(ci) for ci in sorted(self._mutated)
            }
            new_fmts, promoted = adaptive_lib.refresh_partition_formats(
                self._part_fmts, self._calib, mutated
            )
            for ci, (old, new) in enumerate(zip(self._part_fmts, new_fmts)):
                if old != new:
                    # Structure-preserving re-quantization from the exact
                    # plane: slots, deltas and flags stay untouched.
                    self._native[ci] = bscsr_lib.requantize_stream(
                        self._exact[ci], FORMATS[new]
                    )
                    self._streams[ci] = bscsr_lib.dequantize_stream(
                        self._native[ci]
                    )
            self._part_fmts = list(new_fmts)
            self.last_refresh_promoted = promoted
        self._mutated = set()
        max_p = max(e.num_packets for e in self._streams)
        max_p = max(-(-max_p // mult) * mult, mult)
        if self.config.churn_stable:
            # Churn-anchored packet cap: at build/compact the cap is the
            # exact step-aligned count (ZERO padding overhead for a static
            # index — streamed bytes are the paper's whole metric); the
            # FIRST mutation refresh jumps it to the power-of-two bucket,
            # and from then on delta appends change the padded stream SHAPE
            # — i.e. the compiled query signature, and the all-partition
            # re-pad a pad-to change forces — only when a bucket doubles.
            # The cold jump lands deterministically on the first mutation
            # (not on whichever upsert happens to outgrow a partition), so
            # steady-state ingest after it retraces zero times per bucket.
            # The padded tail is flag-free zero packets, which the kernels
            # stream as a continuation of the open sentinel row
            # (answer-preserving; <= 2x stream bytes worst case, reclaimed
            # by the next compact()).
            if preserve_caps and self._packet_cap >= 0:
                pass  # checkpoint restore: the saved cap is authoritative
            elif self._packet_cap < 0:
                self._packet_cap = max_p          # anchor refresh: exact
            else:                                 # mutation refresh: bucket
                self._packet_cap = max(
                    self._packet_cap, kernel_ops.bucket_packets(max_p, mult)
                )
            max_p = self._packet_cap
        if not self.config.incremental_snapshots or max_p != self._padded_max_p:
            dirty = set(range(len(self._streams)))
        else:
            dirty = self._dirty
        for ci in sorted(dirty):
            padded = bscsr_lib.pad_packets(self._streams[ci], max_p)
            self._padded_streams[ci] = padded
            self._padded_words[ci] = bscsr_lib.fuse_stream(padded) if fused else None
            self._part_starts[ci] = kernel_ops.max_step_row_starts(
                self._streams[ci].flags[None], mult
            )
        # The kernels' segment space: a step's row starts plus the carried
        # row, in whole lanes.  Churn-stable, it only grows (in steps of
        # 128 lanes) until compact() re-anchors it, so an upsert that stays
        # inside the bucket keeps the compiled signature; the served bound
        # is still never below the snapshot's own (PackedPartitions.seg_slots).
        seg = kernel_ops.segment_slots(
            max(self._part_starts), mult * self._streams[0].block_size
        )
        if not self.config.churn_stable:
            seg_floor = 0
        else:
            if preserve_caps and self._seg_cap >= 0:
                pass  # checkpoint restore: the saved floor is authoritative
            elif self._seg_cap < 0:
                self._seg_cap = seg                   # anchor refresh: exact
            else:
                self._seg_cap = max(self._seg_cap, seg)
            seg_floor = self._seg_cap
        self._padded_max_p = max_p
        self._dirty = set()
        self.last_refresh_repadded = len(dirty)
        self.total_repadded += len(dirty)
        # Mid-COW-rewrite: padded streams rebuilt, stacked buffers not yet.
        faults_lib.fault_point("refresh.cow_rewrite")

        # Mixed-precision plane: per-width-class tagged fused groups.  Each
        # class pads to its OWN packet cap (anchor-then-bucket, like
        # ``_packet_cap``) so narrow partitions never inherit the widest
        # class's packet count; only dirty / cap-shifted / format-flipped
        # partitions re-fuse, and with ``cow_snapshots`` the class stacks are
        # buffer-pool leases that copy only stale member streams — a
        # steady-state hetero refresh is O(mutated partitions) like the twin
        # plane, not O(class bytes).
        groups = None
        fmt_codes = None
        group_bufs = []
        group_copied = 0
        if hetero:
            nat: dict = {}
            for n in self._native:
                cname = width_class_of(n.value_format).name
                p = max(-(-n.num_packets // mult) * mult, mult)
                nat[cname] = max(nat.get(cname, 0), p)
            if self.config.churn_stable:
                if preserve_caps and self._class_caps is not None:
                    pass  # checkpoint restore: saved class caps authoritative
                elif self._class_caps is None:
                    self._class_caps = dict(nat)      # anchor refresh: exact
                else:                                 # mutation refresh: bucket
                    for cname, p in nat.items():
                        self._class_caps[cname] = max(
                            self._class_caps.get(cname, 0),
                            kernel_ops.bucket_packets(p, mult),
                        )
                caps = self._class_caps
            else:
                caps = nat
            by_class: dict = {}
            for ci, n in enumerate(self._native):
                cname = width_class_of(n.value_format).name
                cap = caps[cname]
                cached = self._padded_tagged[ci]
                if (ci in dirty or cached is None or cached[0] != cap
                        or cached[1] != n.value_format.name):
                    words = bscsr_lib.fuse_stream(
                        bscsr_lib.pad_packets(n, cap), tagged=True
                    )
                    self._padded_tagged[ci] = (cap, n.value_format.name, words)
                by_class.setdefault(cname, []).append(ci)
            built = []
            for cname, cores in sorted(by_class.items()):
                cap = caps[cname]
                words_list = [self._padded_tagged[ci][2] for ci in cores]
                if self.config.cow_snapshots:
                    gbuf, gcop = self._buffer_pool.lease_group(
                        tuple(cores), words_list,
                        self._part_stamps[np.asarray(cores)], cap,
                        packets_multiple=mult,
                    )
                    group_bufs.append(gbuf)
                    group_copied += gcop
                    words = gbuf.view()
                else:
                    words = np.stack(words_list)
                    group_copied += len(cores)
                built.append(
                    kernel_ops.StreamGroup(
                        cname, tuple(cores), words,
                        self._streams[0].block_size,
                    )
                )
            groups = tuple(built)
            fmt_codes = np.array(
                [FORMATS[f].code for f in self._part_fmts], np.int32
            )

        num_slots = np.array([len(s) for s in self._slots], dtype=np.int32)
        width = max(int(num_slots.max()) if num_slots.size else 0, 1)
        tomb_len = max(self._next_gid, 1)
        if self.config.churn_stable:
            # Slot-map width (= the kernel's per-core slot budget) and the
            # tombstone bitmap length grow with the id space; pad both to
            # power-of-two buckets so a refresh reuses the compiled query
            # signature.  Padded slot entries are INVALID_ROW and padded
            # tombstone bits are False — ``finalize_candidates`` masks the
            # former and never reads the latter (global row ids are always
            # < n_rows_total), so the padding is answer-preserving; the
            # phantom-slot hazard analysis lives in ``bscsr_topk_spmv.py``.
            width = kernel_ops.pow2_bucket(width)
            tomb_len = kernel_ops.pow2_bucket(tomb_len)
        slot_map = np.full(
            (len(self._slots), width), bscsr_lib.INVALID_ROW, dtype=np.int32
        )
        for ci, slots in enumerate(self._slots):
            if slots:
                slot_map[ci, : len(slots)] = np.asarray(slots, dtype=np.int32)
        self._deleted.grow(self._next_gid)
        tombs = np.zeros(tomb_len, dtype=bool)
        tombs[: self._next_gid] = self._deleted.bits[: self._next_gid]
        segment_fields = dict(
            slot_to_row=slot_map,
            num_slots=num_slots,
            n_rows_total=self._next_gid,
            tombstones=tombs,
            base_packets=self._base_packets,
            delta_nnz=self._delta_nnz,
            dead_nnz=self._dead_nnz,
            tombstone_count=self._tombstone_slots,
            fmt_codes=fmt_codes,
            groups=groups,
            seg_slots_floor=seg_floor,
        )
        if self.config.cow_snapshots:
            buf, copied = self._buffer_pool.lease(
                self._padded_streams,
                self._padded_words if fused else None,
                self._part_stamps,
                max_p,
                packets_multiple=mult,
            )
            new_packed = kernel_ops.PackedPartitions(
                vals=buf.view("vals"),
                cols=buf.view("cols"),
                flags=buf.view("flags"),
                plan=self._plan,
                n_cols=self._n_cols,
                nnz=self._live_nnz,
                block_size=self._padded_streams[0].block_size,
                value_format=self._fmt,
                stream_layout=self.config.stream_layout,
                words=buf.view("words") if fused else None,
                **segment_fields,
            )
            buf.attach(new_packed)
        else:
            copied = len(self._padded_streams)  # np.stack copies everything
            new_packed = kernel_ops.stack_padded_streams(
                self._padded_streams,
                self._plan,
                self._n_cols,
                self._live_nnz,
                stream_layout=self.config.stream_layout,
                words=self._padded_words if fused else None,
                **segment_fields,
            )
        for gbuf in group_bufs:
            gbuf.attach(new_packed)
        # Mid-atomic-swap: the fresh snapshot exists, the served one is
        # still the old one.  A failure here drops ``new_packed`` (its
        # buffer lease releases via weakref) without tearing the old
        # snapshot; the swap below is a single reference assignment.
        faults_lib.fault_point("refresh.swap")
        self._packed = new_packed
        self.last_refresh_group_copied = group_copied
        self.total_group_copied += group_copied
        self.last_refresh_copied = copied
        self.total_copied += copied
        self._version += 1

    def refresh(self) -> None:
        """Rebuild + swap the serving snapshot.

        The retry entry point after an *interrupted* refresh (a crash or
        injected fault between a mutation landing and the snapshot swap):
        mutations already applied to the stream state are picked up and the
        swap converges — see the crash-atomicity note on :meth:`_refresh`.
        """
        self._refresh()

    @property
    def packed(self) -> kernel_ops.PackedPartitions:
        return self._packed

    @property
    def n_cols(self) -> int:
        """Feature dimensionality of the indexed collection."""
        return self._n_cols

    @property
    def version(self) -> int:
        return self._version

    @property
    def n_rows(self) -> int:
        """Live (queryable) rows."""
        return len(self._loc)

    @property
    def n_rows_total(self) -> int:
        """Size of the global row-id space (live + deleted ids)."""
        return self._next_gid

    @property
    def num_cores(self) -> int:
        return self._plan.num_partitions

    @property
    def deleted_rows(self) -> int:
        return self._deleted.count

    @property
    def snapshot_buffers(self) -> int:
        """COW stacked buffers currently pooled (leased + free)."""
        return len(self._buffer_pool)

    @property
    def expected_precision(self) -> float:
        return expected_precision(
            max(self.n_rows, 1), self.num_cores, self.config.k, self.config.big_k
        )

    @property
    def partition_formats(self) -> Optional[Tuple[str, ...]]:
        """Current per-partition ValueFormat names (None when homogeneous)."""
        return tuple(self._part_fmts) if self._part_fmts is not None else None

    @property
    def predicted_recall(self) -> Optional[float]:
        """The calibration's predicted recall@k at the current assignment."""
        return (
            self._calib.predicted_recall() if self._calib is not None else None
        )

    def _partition_live_csr(self, ci: int) -> bscsr_lib.CSRMatrix:
        """Live rows currently owned by partition ``ci``, as a host CSR."""
        gids = [g for g in self._slots[ci] if g != int(bscsr_lib.INVALID_ROW)]
        lens = np.asarray([len(self._rows[g][0]) for g in gids], np.int64)
        indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
        if gids:
            indices = np.concatenate([self._rows[g][0] for g in gids])
            data = np.concatenate([self._rows[g][1] for g in gids])
        else:
            indices = np.zeros(0, np.int32)
            data = np.zeros(0, np.float32)
        return bscsr_lib.CSRMatrix(
            indptr=indptr, indices=indices, data=data,
            shape=(len(gids), self._n_cols),
        )

    # -- mutation ------------------------------------------------------------

    @staticmethod
    def _normalize_row(cols, vals) -> Tuple[np.ndarray, np.ndarray]:
        cols = np.asarray(cols, dtype=np.int32)
        vals = np.asarray(vals, dtype=np.float32)
        if cols.shape != vals.shape:
            raise ValueError(f"row cols/vals mismatch: {cols.shape} vs {vals.shape}")
        order = np.argsort(cols, kind="stable")
        return cols[order], vals[order]

    def _append_rows(self, items) -> None:
        """Append (gid, (cols, vals)) items as delta packets, least-loaded first."""
        groups: dict = {}
        sizes = [len(s) for s in self._slots]
        for gid, row in items:
            ci = int(np.argmin(sizes))
            groups.setdefault(ci, []).append((gid, row))
            sizes[ci] += 1
        for ci in sorted(groups):
            rows = [row for _, row in groups[ci]]
            delta = bscsr_lib.encode_delta_rows(
                rows, self._n_cols, self.config.block_size, self._fmt
            )
            if self._part_fmts is not None:
                # Keep all three planes append-aligned: the delta encodes
                # exactly (F32) once, then re-quantizes into the partition's
                # current format — structure identical across planes.
                fmt = FORMATS[self._part_fmts[ci]]
                self._exact[ci] = bscsr_lib.append_packets(
                    self._exact[ci], delta
                )
                native_delta = bscsr_lib.requantize_stream(delta, fmt)
                self._native[ci] = bscsr_lib.append_packets(
                    self._native[ci], native_delta
                )
                self._streams[ci] = bscsr_lib.append_packets(
                    self._streams[ci],
                    bscsr_lib.dequantize_stream(native_delta),
                )
            else:
                self._streams[ci] = bscsr_lib.append_packets(
                    self._streams[ci], delta
                )
            self._mark_dirty(ci)
            slots = self._slots[ci]
            # The previously-open sentinel becomes a dead candidate slot.
            slots.append(int(bscsr_lib.INVALID_ROW))
            for gid, (cols, vals) in groups[ci]:
                self._loc[gid] = (ci, len(slots))
                slots.append(gid)
                self._rows[gid] = (cols, vals)
                self._live_nnz += len(cols)
                self._delta_nnz += len(cols)

    def _tombstone_slot(self, gid: int) -> None:
        ci, si = self._loc.pop(gid)
        self._slots[ci][si] = int(bscsr_lib.INVALID_ROW)
        self._tombstone_slots += 1
        cols, _ = self._rows.pop(gid)
        self._live_nnz -= len(cols)
        if si >= self._plan.rows_per_partition[ci]:  # slot lives in a delta segment
            self._delta_nnz -= len(cols)
        self._dead_nnz += len(cols)

    def add_rows(self, rows: Sequence[Tuple[np.ndarray, np.ndarray]]) -> list:
        """Append new rows; returns their freshly assigned global row ids."""
        if not rows:
            return []
        normalized = [self._normalize_row(c, v) for c, v in rows]
        gids = list(range(self._next_gid, self._next_gid + len(rows)))
        self._next_gid += len(rows)
        self._append_rows(list(zip(gids, normalized)))
        self._refresh()
        return gids

    def replace_rows(
        self, row_ids: Sequence[int], rows: Sequence[Tuple[np.ndarray, np.ndarray]]
    ) -> None:
        """Replace rows in place of their ids: tombstone old copy, append new.

        A previously deleted id is resurrected (its tombstone bit clears).
        """
        if len(row_ids) != len(rows):
            raise ValueError("row_ids and rows must be the same length")
        row_ids = self._validate_ids(row_ids)
        normalized = [self._normalize_row(c, v) for c, v in rows]
        for gid in row_ids:
            if gid in self._loc:
                self._tombstone_slot(gid)
        self._deleted.clear(row_ids)
        self._append_rows(list(zip(row_ids, normalized)))
        self._refresh()

    def delete_rows(self, row_ids: Sequence[int]) -> None:
        """Tombstone rows: their slots retire and their ids stay unreturnable."""
        row_ids = self._validate_ids(row_ids, allow_duplicates=True)
        for gid in row_ids:
            if gid in self._loc:
                self._tombstone_slot(gid)
            self._deleted.mark([gid])
        self._refresh()

    def _validate_ids(self, row_ids: Sequence[int], allow_duplicates=False) -> list:
        out = [int(g) for g in row_ids]
        for gid in out:
            if gid < 0 or gid >= self._next_gid:
                raise KeyError(f"row id {gid} was never assigned")
        if not allow_duplicates and len(set(out)) != len(out):
            # a duplicate would append two live slots for one id (ghost copy)
            raise ValueError("duplicate row ids in one replace batch")
        return out

    # -- compaction ----------------------------------------------------------

    def live_csr(self) -> Tuple[bscsr_lib.CSRMatrix, np.ndarray]:
        """Live rows (gid-ascending) as a CSR plus the gid of each CSR row.

        Cached per snapshot version — repeated exact-oracle queries between
        mutations reuse one materialization instead of re-concatenating
        every live row.
        """
        if self._live_csr_cache is not None and (
            self._live_csr_cache[0] == self._version
        ):
            return self._live_csr_cache[1]
        gids = np.asarray(sorted(self._loc), dtype=np.int64)
        lens = np.asarray([len(self._rows[g][0]) for g in gids], dtype=np.int64)
        indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
        if gids.size:
            indices = np.concatenate([self._rows[g][0] for g in gids])
            data = np.concatenate([self._rows[g][1] for g in gids])
        else:
            indices = np.zeros(0, np.int32)
            data = np.zeros(0, np.float32)
        csr = bscsr_lib.CSRMatrix(
            indptr=indptr, indices=indices, data=data,
            shape=(int(gids.size), self._n_cols),
        )
        self._live_csr_cache = (self._version, (csr, gids))
        return csr, gids

    def compact(self) -> None:
        """Re-encode live rows into a fresh base segment, partitions in parallel.

        Reclaims delta packets, dead slots and tombstoned stream bytes,
        restoring base-only bytes/nnz.  With ``config.parallel_compaction``
        (the default) partitions are re-encoded concurrently in a thread
        pool once per-partition work clears ``parallel_compaction_min_nnz``
        — numpy releases the GIL on large-array ops, so wall-clock stops
        scaling with index size once cores cover the partitions, while tiny
        indexes (where pool dispatch would dominate) stay serial.  Either
        way the previous snapshot keeps serving until the single atomic swap
        under the existing version counter; deleted ids stay masked
        afterwards via the global tombstone bitmap.
        """
        csr, gids = self.live_csr()
        with span("index.build", kind="compact", rows=int(csr.shape[0]),
                  nnz=int(csr.nnz)):
            self._compact(csr, gids)

    def _compact(self, csr: bscsr_lib.CSRMatrix, gids: np.ndarray) -> None:
        """The body of :meth:`compact` over the live rows ``csr``/``gids``."""
        c = max(1, self.config.resolve_partitions(max(csr.shape[0], 1)))
        plan = partition_lib.PartitionPlan.build(csr.shape[0], c)
        with span("index.partition", partitions=c):
            parts = partition_lib.partition_csr(csr, plan)

        def encode(ci):
            return self._encode(ci, parts[ci], self._fmt)

        # self._packed still serves while partitions re-encode.
        parallel = (
            self.config.parallel_compaction
            and len(parts) > 1
            and csr.nnz / len(parts) >= self.config.parallel_compaction_min_nnz
        )
        if parallel:
            workers = min(len(parts), os.cpu_count() or 1)
            with ThreadPoolExecutor(max_workers=workers) as pool:
                streams = list(pool.map(encode, range(len(parts))))
        else:
            streams = [encode(ci) for ci in range(len(parts))]
        self.last_compact_parallel = parallel
        new_fmts = new_calib = new_exact = new_native = None
        if self._part_fmts is not None:
            # Full re-assignment (the only place formats may DEMOTE): fresh
            # calibration over the live collection, then rebuild the
            # exact/native/twin planes.  ``self._fmt`` is F32 here, so the
            # parallel-encoded ``streams`` already are the exact plane.
            fmt_plan, new_calib = adaptive_lib.assign_partition_formats(
                csr, plan.num_partitions, self.config.recall_target,
                k=self.config.k, n_queries=self.config.calibration_queries,
                seed=self.config.calibration_seed,
            )
            new_fmts = list(fmt_plan.formats)
            new_exact = streams
            new_native = [
                bscsr_lib.requantize_stream(e, FORMATS[f])
                for e, f in zip(new_exact, new_fmts)
            ]
            streams = [bscsr_lib.dequantize_stream(n) for n in new_native]
        # Everything above built into locals; a failure up to here leaves
        # the index (and its served snapshot) untouched.
        faults_lib.fault_point("compact.swap")
        if self._part_fmts is not None:
            self._part_fmts = new_fmts
            self._calib = new_calib
            self._exact = new_exact
            self._native = new_native
        self._streams = streams
        self._base_packets = max(e.num_packets for e in streams)
        self._plan = plan
        self._reset_padded_cache()
        with span("index.row_maps", rows=int(csr.shape[0])):
            self._slots = [
                [int(g) for g in gids[start : start + size]]
                for start, size in zip(plan.row_starts, plan.rows_per_partition)
            ]
            self._loc = {
                gid: (ci, si)
                for ci, slots in enumerate(self._slots)
                for si, gid in enumerate(slots)
            }
        self._delta_nnz = 0
        self._dead_nnz = 0
        self._tombstone_slots = 0
        self._refresh()

    # -- durable state (core/persistence.py writes/reads this) ---------------

    def export_state(self) -> Tuple[dict, dict]:
        """Full logical + stream state as (json-able meta, named arrays).

        Captures everything :meth:`from_state` needs to reproduce this index
        *bit-identically* — including the churn-stable packet / slot / class
        caps, so the restored snapshot keeps the crashed process's padded
        shapes and therefore its executor signature (zero-retrace resume).

        Heterogeneous (``recall_target``) indexes serialize only the exact
        F32 plane plus the format vector and calibration: the native and
        twin planes are bit-exact functions of those
        (``requantize_stream`` / ``dequantize_stream``).
        """
        hetero = self._part_fmts is not None
        plane = self._exact if hetero else self._streams
        arrays: dict = {}
        stream_meta = []
        for ci, s in enumerate(plane):
            arrays[f"s{ci}_vals"] = s.vals
            arrays[f"s{ci}_cols"] = s.cols
            arrays[f"s{ci}_flags"] = s.flags
            stream_meta.append(
                {"n_rows": int(s.n_rows), "nnz": int(s.nnz),
                 "fmt": s.value_format.name}
            )
        arrays["slot_lens"] = np.asarray(
            [len(s) for s in self._slots], np.int64
        )
        arrays["slots"] = np.asarray(
            [g for slots in self._slots for g in slots], np.int64
        )
        gids = np.asarray(sorted(self._rows), np.int64)
        arrays["row_gids"] = gids
        arrays["row_lens"] = np.asarray(
            [len(self._rows[g][0]) for g in gids], np.int64
        )
        if gids.size:
            arrays["row_cols"] = np.concatenate(
                [self._rows[g][0] for g in gids]
            ).astype(np.int32)
            arrays["row_vals"] = np.concatenate(
                [self._rows[g][1] for g in gids]
            ).astype(np.float32)
        else:
            arrays["row_cols"] = np.zeros(0, np.int32)
            arrays["row_vals"] = np.zeros(0, np.float32)
        self._deleted.grow(self._next_gid)
        arrays["deleted"] = self._deleted.bits[: max(self._next_gid, 1)].copy()
        calib_meta = None
        if self._calib is not None:
            c = self._calib
            arrays["calib_queries"] = c.queries
            arrays["calib_thresholds"] = c.thresholds
            arrays["calib_losses"] = c.losses
            for fname, arr in c.quant_thresholds.items():
                arrays[f"calib_qt_{fname}"] = arr
            calib_meta = {
                "k": int(c.k), "budget": float(c.budget),
                "quant_fmts": sorted(c.quant_thresholds),
            }
        meta = {
            "schema": 1,
            "config": dataclasses.asdict(self.config),
            "n_cols": int(self._n_cols),
            "plan_rows": int(self._plan.n_rows),
            "plan_partitions": int(self._plan.num_partitions),
            "next_gid": int(self._next_gid),
            "live_nnz": int(self._live_nnz),
            "delta_nnz": int(self._delta_nnz),
            "dead_nnz": int(self._dead_nnz),
            "tombstone_slots": int(self._tombstone_slots),
            "base_packets": int(self._base_packets),
            "version": int(self._version),
            "packet_cap": int(self._packet_cap),
            "class_caps": (
                {k: int(v) for k, v in self._class_caps.items()}
                if self._class_caps is not None else None
            ),
            "seg_cap": int(self._seg_cap),
            "part_fmts": (
                list(self._part_fmts) if self._part_fmts is not None else None
            ),
            "streams": stream_meta,
            "calib": calib_meta,
        }
        return meta, arrays

    @classmethod
    def from_state(cls, meta: dict, arrays: dict) -> "MutableTopKSpMVIndex":
        """Reconstruct an index from :meth:`export_state` output.

        The restored snapshot answers queries bit-identically to the
        exported one (streams, slots, tombstones, formats and padded
        shapes all round-trip), so a process resuming from a checkpoint
        re-pins the same executor signature with zero retraces.
        """
        if meta.get("schema") != 1:
            raise ValueError(f"unsupported state schema: {meta.get('schema')}")
        config = TopKSpMVConfig(**meta["config"])
        hetero = meta["part_fmts"] is not None
        obj = cls.__new__(cls)
        obj.config = config
        obj._n_cols = int(meta["n_cols"])
        obj._fmt = F32 if hetero else FORMATS[config.value_format]
        obj._plan = partition_lib.PartitionPlan.build(
            meta["plan_rows"], meta["plan_partitions"]
        )
        plane = []
        for ci, sm in enumerate(meta["streams"]):
            plane.append(bscsr_lib.BSCSRMatrix(
                vals=arrays[f"s{ci}_vals"],
                cols=arrays[f"s{ci}_cols"],
                flags=arrays[f"s{ci}_flags"],
                n_rows=int(sm["n_rows"]),
                n_cols=obj._n_cols,
                nnz=int(sm["nnz"]),
                block_size=config.block_size,
                value_format=FORMATS[sm["fmt"]],
            ))
        obj.last_refresh_promoted = 0
        obj._part_fmts = None
        obj._calib = None
        obj._exact = None
        obj._native = None
        if hetero:
            obj._part_fmts = list(meta["part_fmts"])
            obj._exact = plane
            obj._native = [
                bscsr_lib.requantize_stream(e, FORMATS[f])
                for e, f in zip(obj._exact, obj._part_fmts)
            ]
            obj._streams = [
                bscsr_lib.dequantize_stream(n) for n in obj._native
            ]
            if meta["calib"] is not None:
                cm = meta["calib"]
                obj._calib = adaptive_lib.PrecisionCalibration(
                    queries=arrays["calib_queries"],
                    thresholds=arrays["calib_thresholds"],
                    k=int(cm["k"]),
                    budget=float(cm["budget"]),
                    losses=np.array(arrays["calib_losses"]),
                    quant_thresholds={
                        f: arrays[f"calib_qt_{f}"] for f in cm["quant_fmts"]
                    },
                )
        else:
            obj._streams = plane
        obj._base_packets = int(meta["base_packets"])
        slot_lens = arrays["slot_lens"]
        flat_slots = arrays["slots"]
        obj._slots = []
        off = 0
        for ln in slot_lens:
            obj._slots.append([int(g) for g in flat_slots[off: off + int(ln)]])
            off += int(ln)
        invalid = int(bscsr_lib.INVALID_ROW)
        obj._loc = {
            gid: (ci, si)
            for ci, slots in enumerate(obj._slots)
            for si, gid in enumerate(slots)
            if gid != invalid
        }
        gids = arrays["row_gids"]
        lens = arrays["row_lens"]
        starts = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
        obj._rows = {
            int(g): (
                arrays["row_cols"][starts[i]: starts[i + 1]],
                arrays["row_vals"][starts[i]: starts[i + 1]],
            )
            for i, g in enumerate(gids)
        }
        obj._next_gid = int(meta["next_gid"])
        obj._deleted = bscsr_lib.TombstoneBitmap(
            bits=np.array(arrays["deleted"], dtype=bool)
        )
        obj._deleted.grow(obj._next_gid)
        obj._live_nnz = int(meta["live_nnz"])
        obj._delta_nnz = int(meta["delta_nnz"])
        obj._dead_nnz = int(meta["dead_nnz"])
        obj._tombstone_slots = int(meta["tombstone_slots"])
        obj._version = int(meta["version"]) - 1  # _refresh bumps it back
        obj._packed = None
        obj._live_csr_cache = None
        obj._buffer_pool = kernel_ops.SnapshotBufferPool()
        obj._stamp_counter = 0
        obj._reset_padded_cache()
        obj.last_refresh_repadded = 0
        obj.total_repadded = 0
        obj.last_refresh_copied = 0
        obj.total_copied = 0
        obj.last_refresh_group_copied = 0
        obj.total_group_copied = 0
        obj.last_compact_parallel = False
        # Restore the churn-stable caps verbatim, then build the snapshot
        # around them (preserve_caps): same padded shapes as at export.
        obj._packet_cap = int(meta["packet_cap"])
        obj._seg_cap = int(meta.get("seg_cap", -1))
        if meta["class_caps"] is not None:
            obj._class_caps = {
                k: int(v) for k, v in meta["class_caps"].items()
            }
        obj._refresh(preserve_caps=True)
        return obj


def query_executor(config: TopKSpMVConfig) -> executor_lib.QueryExecutor:
    """The process-wide device-resident executor serving this config.

    Pins each snapshot's streams on device once (keyed by snapshot uid) and
    caches end-to-end compiled query fns, so steady-state dispatch performs
    zero host->device transfers — see ``kernels/executor.py``.
    """
    return executor_lib.get_executor(
        big_k=config.big_k,
        k=config.k,
        packets_per_step=config.packets_per_step,
        gather_mode=config.gather_mode,
        inner_loop=config.inner_loop,
        interpret=config.resolve_interpret(),
    )


def topk_spmv(
    index: TopKSpMVIndex, x: jnp.ndarray, use_kernel: bool = True
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-device approximate Top-K query.

    With ``config.use_executor`` (default) both the kernel and the reference
    path dispatch through the device-resident snapshot plane; the legacy
    per-call upload dispatch stays available as the opt-out baseline.
    """
    cfg = index.config
    if cfg.use_executor:
        return query_executor(cfg).query(
            x, index.packed, path="kernel" if use_kernel else "reference"
        )
    if use_kernel:
        return kernel_ops.topk_spmv_blocked(
            x,
            index.packed,
            big_k=cfg.big_k,
            k=cfg.k,
            packets_per_step=cfg.packets_per_step,
            gather_mode=cfg.gather_mode,
            inner_loop=cfg.inner_loop,
            interpret=cfg.resolve_interpret(),
        )
    return kernel_ops.topk_spmv_reference(x, index.packed, big_k=cfg.big_k, k=cfg.k)


def topk_spmv_batched(
    index: TopKSpMVIndex, xs: jnp.ndarray, use_kernel: bool = True,
    query_cols=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched approximate Top-K: Q queries, one pass over the stream.

    ``xs`` is (Q, M); returns (Q, big_k) values and global row ids.  With
    ``use_kernel`` the multi-query Pallas kernel amortizes every packet read
    across all Q queries (per-query bytes/nnz divided by Q — §Perf C);
    otherwise the vmapped jnp oracle evaluates the same approximation.
    With ``config.use_executor`` (default) either path dispatches through the
    device-resident snapshot plane with power-of-two Q bucketing, and
    ``query_cols`` (``QueryExecutor.set_columns``) narrows the kernel's
    gather to the block's set columns.
    """
    cfg = index.config
    if cfg.use_executor:
        return query_executor(cfg).query_batched(
            xs, index.packed, path="kernel" if use_kernel else "reference",
            query_cols=query_cols,
        )
    if use_kernel:
        return kernel_ops.topk_spmv_batched(
            xs,
            index.packed,
            big_k=cfg.big_k,
            k=cfg.k,
            packets_per_step=cfg.packets_per_step,
            gather_mode=cfg.gather_mode,
            inner_loop=cfg.inner_loop,
            interpret=cfg.resolve_interpret(),
        )
    return kernel_ops.topk_spmv_reference_batched(
        xs, index.packed, big_k=cfg.big_k, k=cfg.k
    )


def topk_spmv_exact(
    csr: bscsr_lib.CSRMatrix, x: jnp.ndarray, big_k: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Exact CSR Top-K on host — ground truth for accuracy studies."""
    v, r = ref_lib.csr_topk_numpy(
        csr.indptr, csr.indices, csr.data, np.asarray(x, np.float32), big_k
    )
    return v, r


# ---------------------------------------------------------------------------
# Mesh-distributed query
# ---------------------------------------------------------------------------

def distributed_topk_spmv_fn(
    index: TopKSpMVIndex, mesh: Mesh, shard_axis="data", batched: bool = False
):
    """Build a jitted query fn with the index sharded core-wise over ``mesh``.

    Returns (fn, device_arrays): arrays are placed with the core dim sharded
    over ``shard_axis`` (one group of cores per device = one FPGA per HBM
    stack, scaled out).  ``fn(x, *device_arrays) -> (topk_vals, topk_rows)``.
    ``shard_axis`` may be a tuple of mesh axes (e.g. ("pod", "data")).

    With ``batched`` the returned fn takes a replicated (Q, M) query batch
    and answers all Q queries in one multi-query pass per device, returning
    (Q, big_k) arrays — still only c*k*Q candidate pairs cross ICI.
    """
    cfg = index.config
    packed = index.packed
    axes = (shard_axis,) if isinstance(shard_axis, str) else tuple(shard_axis)
    n_dev = 1
    for a in axes:
        n_dev *= mesh.shape[a]
    shard_axis = axes if len(axes) > 1 else axes[0]
    if packed.num_cores % n_dev != 0:
        raise ValueError(
            f"num_partitions ({packed.num_cores}) must be a multiple of the "
            f"mesh axis {shard_axis!r} size ({n_dev})"
        )
    core_sharded = NamedSharding(mesh, P(shard_axis))
    replicated = NamedSharding(mesh, P())

    # One fused word stream per core, or the legacy three split streams.
    # Mixed-precision snapshots ship their f32 split twins: the per-class
    # tagged groups are ragged across cores, which a core-sharded mesh
    # layout cannot carry (single-device dispatch streams them natively).
    layout = "split" if packed.is_heterogeneous else packed.stream_layout
    if layout == "fused":
        host_arrays = (packed.fused_words(),)
    else:
        host_arrays = (packed.vals, packed.cols, packed.flags)
    device_arrays = tuple(
        jax.device_put(jnp.asarray(a), core_sharded) for a in host_arrays
    )
    n_streams = len(device_arrays)
    # The merge sees all c*k candidates, so its per-core inputs are replicated.
    row_starts = jax.device_put(jnp.asarray(packed.row_starts), replicated)
    rows_per = jax.device_put(jnp.asarray(packed.candidate_slots), replicated)
    slot_to_row = None
    if packed.slot_to_row is not None:
        slot_to_row = jax.device_put(jnp.asarray(packed.slot_to_row), replicated)
    tombstones = None
    if packed.has_tombstones:  # computed once at snapshot build
        tombstones = jax.device_put(jnp.asarray(packed.tombstones), replicated)
    max_rows = packed.max_slots
    interpret = cfg.resolve_interpret()
    gather_mode = kernel_ops.resolve_gather_mode(cfg.gather_mode)

    def _local(x, *streams):
        from repro.kernels.bscsr_topk_spmv import (
            bscsr_topk_spmv,
            bscsr_topk_spmv_multiquery,
        )

        kernel = bscsr_topk_spmv_multiquery if batched else bscsr_topk_spmv
        lv, lr = kernel(
            x,
            *streams,
            k=cfg.k,
            n_rows=max_rows,
            packets_per_step=cfg.packets_per_step,
            fmt_name=packed.value_format.name,
            inner_loop=cfg.inner_loop,
            stream_layout=layout,
            block_size=packed.block_size,
            gather_mode=gather_mode,
            interpret=interpret,
        )
        # c*k candidates: tiny; one all-gather hands every device the merge.
        return (jax.lax.all_gather(lv, shard_axis, tiled=True),
                jax.lax.all_gather(lr, shard_axis, tiled=True))

    @partial(
        jax.jit,
        in_shardings=(replicated,) + (core_sharded,) * n_streams,
        out_shardings=(replicated, replicated),
    )
    def query(x, *streams):
        lv, lr = jax.shard_map(
            _local,
            mesh=mesh,
            in_specs=(P(),) + (P(shard_axis),) * n_streams,
            out_specs=(P(), P()),
            check_vma=False,  # pallas_call outputs carry no vma info
        )(x, *streams)
        finalize = (
            kernel_ops.finalize_candidates_batched
            if batched
            else kernel_ops.finalize_candidates
        )
        return finalize(
            lv, lr, row_starts, rows_per, cfg.big_k, packed.n_rows_logical,
            slot_to_row=slot_to_row, tombstones=tombstones,
        )

    return query, device_arrays

"""Host-side packing + jit'd dispatch around the BS-CSR Top-K SpMV kernel.

``PackedPartitions`` is a *segmented* container: each core's stream is the
concatenation of its base segment and any appended delta tile-packets
(``bscsr.append_packets``).  The kernel is oblivious to segments — it streams
packets and counts row-start flags into *slot* ids.  Two optional host-side
arrays translate slots back to the logical index:

  slot_to_row   (C, L) int32 — kernel-local slot -> global row id;
                ``bscsr.INVALID_ROW`` retires a slot (dead sentinel slot
                between segments, or a tombstoned/replaced row).
  tombstones    (n_rows,) bool — deleted global row ids (kept across
                compaction so a deleted id can never be returned).

Both are applied by ``finalize_candidates`` before the merge; a pure-base
index (``pack_partitions``) leaves them ``None`` and uses the affine
``row_starts`` mapping.

Stream layouts
--------------

``stream_layout="fused"`` additionally carries the fused single-stream form
(``words``: each packet's ``flags | cols | vals`` packed into one contiguous
int32 word row — see the diagram in ``core/bscsr.py``), and the dispatch
functions ship ONLY that one array to the kernel, so every grid step
pipelines a single VMEM block from a single contiguous HBM region instead of
three separately-strided ones.  The split ``vals``/``cols``/``flags`` arrays
are always kept host-side (the jnp reference oracle and the delta-append
machinery read them); total stream bytes are identical between layouts —
fused changes the burst *shape*, not the byte count:

  bytes/nnz (B = 256, int16 idx):  F32 6.125 | BF16 4.125 | Q15 4.125
  | Q7 3.125 — vs 12 for naive COO; fused == split, in ONE burst per step.

Host-snapshot vs device-snapshot lifecycle
------------------------------------------

``PackedPartitions`` is the HOST plane: numpy arrays (for a mutable index,
read-only copy-on-write views leased from a ``SnapshotBufferPool``).  The
dispatch helpers in this module (``topk_spmv_blocked`` / ``topk_spmv_batched``
/ the reference oracles) upload those arrays per call — simple, correct, and
the baseline the benchmarks compare against.  Production queries go through
``kernels/executor.py`` instead: a ``DeviceSnapshot`` pins each host
snapshot's kernel streams + finalize arrays on device exactly once (keyed by
the snapshot ``uid`` assigned below, evicted when the host snapshot is
collected), and a ``QueryExecutor`` fuses kernel + finalize into one cached
jitted call — steady-state dispatch does zero host->device transfers.

The end-to-end data path (encode -> fuse -> kernel -> finalize -> dispatch)
is walked through in docs/ARCHITECTURE.md; docs/SERVING.md documents the
dispatch lifecycle, cache keys and tuning knobs.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import weakref
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bscsr as bscsr_lib
from repro.core import partition as partition_lib
from repro.core.quantization import (
    FORMAT_BY_CODE,
    FORMATS,
    WIDTH_CLASSES,
    ValueFormat,
    width_class_of,
)
from repro.kernels import ref as ref_lib
from repro.kernels.bscsr_topk_spmv import (
    FLAG_WORD_BITS,
    bscsr_spmv,
    bscsr_topk_spmv,
    bscsr_topk_spmv_multiquery,
    segment_slots,
)

NEG_INF = ref_lib.NEG_INF
INVALID_ROW = bscsr_lib.INVALID_ROW


def pow2_bucket(n: int, minimum: int = 1) -> int:
    """Next power-of-two >= max(n, minimum) — the churn-stable dim bucket.

    A mutable index's snapshot dims (tombstone bitmap length, slot-map
    width, padded packet count) grow with the id space, and every distinct
    value is a distinct compiled-function signature.  Rounding them up to
    power-of-two buckets makes a steady stream of upserts hit ONE signature
    until a bucket doubles — O(log growth) retraces instead of O(upserts) —
    the same discipline as the executor's power-of-two Q buckets.  See
    docs/ARCHITECTURE.md ("where does a query retrace?").
    """
    return 1 << (max(int(n), minimum, 1) - 1).bit_length()


def bucket_packets(n: int, multiple: int) -> int:
    """Power-of-two packet bucket, kept a multiple of ``packets_per_step``.

    The padded tail streams zero packets with no row-start flags, which the
    kernels already treat as a continuation of the open sentinel row — so
    the bucket changes HBM bytes (<= 2x worst case, zeros) but never the
    answer.
    """
    return -(-pow2_bucket(n) // multiple) * multiple


def max_step_row_starts(flag_words: np.ndarray, packets_per_step: int) -> int:
    """Most row starts any grid step holds, over cores and steps.

    ``flag_words`` is a (C, P, B/32) row-start bit stream; a step is each
    run of ``packets_per_step`` packets from packet 0 (a ragged tail counts
    as a step).  The kernel's segment space has to exceed this count.
    """
    c, p, _ = flag_words.shape
    bits = np.bitwise_count(np.ascontiguousarray(flag_words).view(np.uint32))
    per_packet = bits.sum(axis=2, dtype=np.int64)
    t = packets_per_step
    per_packet = np.pad(per_packet, ((0, 0), (0, -p % t)))
    return int(per_packet.reshape(c, -1, t).sum(axis=2).max(initial=0))


# Monotonic snapshot identities: the device-resident plane
# (``kernels/executor.py``) pins each snapshot's arrays on device exactly
# once, keyed by this uid, and evicts when the host snapshot is collected.
_SNAPSHOT_UIDS = itertools.count()


@dataclasses.dataclass(frozen=True)
class StreamGroup:
    """One storage-width class of a mixed-precision snapshot's fused streams.

    Heterogeneous snapshots cannot stream one rectangular fused array — a
    uniform word width would pad every partition to the widest format and
    erase the byte savings.  Instead partitions are grouped by value storage
    width (``TAG4``/``TAG2``/``TAG1``); each group keeps its own tagged
    ``(Cg, Pg, Wg)`` word array with an independent packet bucket, and the
    dispatchers run one kernel call per group, scattering the per-core
    candidates back into ``(C, k)`` by ``cores``.
    """

    class_name: str               # WIDTH_CLASSES key (TAG4 | TAG2 | TAG1)
    cores: Tuple[int, ...]        # snapshot core indices in this group
    words: np.ndarray             # (Cg, Pg, 1 + W) tagged fused word streams
    block_size: int

    @property
    def stream_bytes(self) -> int:
        return int(self.words.nbytes)

    @property
    def value_stream_bytes(self) -> int:
        """Bytes of this group's value sections (padding packets included)."""
        cg, pg, _ = self.words.shape
        bpv = WIDTH_CLASSES[self.class_name].bytes_per_value
        return cg * pg * self.block_size * bpv


@dataclasses.dataclass(frozen=True)
class PackedPartitions:
    """All core partitions of one matrix, stacked for the (cores, steps) grid.

    Immutable snapshot: a mutable index swaps in a fresh instance per update
    batch, so queries holding an older snapshot keep answering consistently.

    Each instance gets a fresh ``uid`` (including via ``dataclasses.replace``)
    and a ``has_tombstones`` bit computed ONCE here — per-dispatch code must
    never re-scan the tombstone bitmap.

    Mixed-precision snapshots additionally carry ``fmt_codes`` (the
    per-partition :class:`ValueFormat` code vector) and ``groups`` (tagged
    fused streams per storage-width class).  Their split ``vals`` are the
    exactly-dequantized F32 twins (``bscsr.dequantize_stream``) so the
    reference oracle and the split-layout parity path read one uniform
    dtype; byte accounting uses the native group words instead.
    """

    vals: np.ndarray          # (C, P, B) base+delta concatenated streams
    cols: np.ndarray          # (C, P, B)
    flags: np.ndarray         # (C, P, B//32)
    plan: partition_lib.PartitionPlan
    n_cols: int
    nnz: int                  # live nnz (tombstoned stream entries excluded)
    block_size: int
    value_format: ValueFormat
    stream_layout: str = "split"               # "split" | "fused"
    words: Optional[np.ndarray] = None         # (C, P, W) fused word streams
    # --- segmented-extension fields (None for a pure-base index) ---
    slot_to_row: Optional[np.ndarray] = None   # (C, L) int32 slot -> global row
    num_slots: Optional[np.ndarray] = None     # (C,) candidate slots per core
    n_rows_total: Optional[int] = None         # global row-id space size
    tombstones: Optional[np.ndarray] = None    # (n_rows_total,) bool, deleted ids
    base_packets: Optional[int] = None         # packets in the base segment
    delta_nnz: int = 0                         # live nnz held in delta segments
    dead_nnz: int = 0                          # stream nnz under retired slots
    tombstone_count: int = 0                   # retired (tombstoned) slots
    # --- mixed-precision fields (None for a homogeneous snapshot) ---
    fmt_codes: Optional[np.ndarray] = None     # (C,) int32 per-partition codes
    groups: Optional[Tuple[StreamGroup, ...]] = None  # tagged fused streams
    # --- churn-stable floor of the kernel's segment space (0: none) ---
    seg_slots_floor: int = 0
    # init=False: always derived in __post_init__, never copied stale through
    # dataclasses.replace.
    uid: int = dataclasses.field(init=False, compare=False, repr=False,
                                 default=-1)
    has_tombstones: bool = dataclasses.field(init=False, compare=False,
                                             default=False)
    # packets_per_step -> max_step_row_starts over every streamed array
    _step_starts: dict = dataclasses.field(init=False, compare=False,
                                           repr=False, default=None)

    def __post_init__(self):
        object.__setattr__(self, "uid", next(_SNAPSHOT_UIDS))
        object.__setattr__(
            self, "has_tombstones",
            self.tombstones is not None and bool(self.tombstones.any()),
        )
        object.__setattr__(self, "_step_starts", {})

    def max_step_row_starts(self, packets_per_step: int) -> int:
        """Most row starts in one grid step of any stream the kernels read:
        the split flags (the fused words carry the same bits) and, for a
        mixed-precision snapshot, every width class's tagged words."""
        cached = self._step_starts.get(packets_per_step)
        if cached is None:
            arrays = [self.flags]
            if self.groups is not None:
                wf = self.block_size // FLAG_WORD_BITS
                arrays += [g.words[..., 1 : 1 + wf] for g in self.groups]
            cached = max(max_step_row_starts(a, packets_per_step) for a in arrays)
            self._step_starts[packets_per_step] = cached
        return cached

    def seg_slots(self, packets_per_step: int) -> int:
        """The kernels' static segment space for this snapshot at
        ``packets_per_step``: its own row-start bound, raised to a
        churn-stable index's ``seg_slots_floor``, at most one slot per
        entry (``bscsr_topk_spmv.segment_slots``)."""
        e = packets_per_step * self.block_size
        need = max(self.max_step_row_starts(packets_per_step),
                   self.seg_slots_floor - 1)
        return segment_slots(need, e)

    @property
    def num_cores(self) -> int:
        return int(self.vals.shape[0])

    @property
    def row_starts(self) -> np.ndarray:
        return np.asarray(self.plan.row_starts, dtype=np.int32)

    @property
    def rows_per_partition(self) -> np.ndarray:
        return np.asarray(self.plan.rows_per_partition, dtype=np.int32)

    @property
    def is_segmented(self) -> bool:
        return self.slot_to_row is not None

    @property
    def candidate_slots(self) -> np.ndarray:
        """(C,) number of kernel-local candidate slots per core."""
        if self.num_slots is not None:
            return np.asarray(self.num_slots, dtype=np.int32)
        return self.rows_per_partition

    @property
    def max_slots(self) -> int:
        """Per-core candidate-slot budget — the kernel's static slot count.

        For a segmented snapshot this is the slot-map width, which a
        churn-stable mutable index pads to a power-of-two bucket: the
        kernel/reference slot budget then keys one compiled signature per
        bucket instead of one per refresh.  Padded slots beyond a core's
        live count can never displace real candidates: the kernel only ever
        materializes them as NEG_INF scratchpad sentinels, the reference
        oracle masks them to NEG_INF before its local top-k, and
        ``finalize_candidates`` masks by the exact traced per-core counts.
        """
        if self.slot_to_row is not None:
            return int(self.slot_to_row.shape[1])
        return max(int(self.candidate_slots.max()), 1)

    @property
    def n_rows_logical(self) -> int:
        """Size of the global row-id space (sentinel id for the merge mask)."""
        return self.n_rows_total if self.n_rows_total is not None else self.plan.n_rows

    @property
    def delta_fraction(self) -> float:
        return self.delta_nnz / max(self.nnz, 1)

    @property
    def is_heterogeneous(self) -> bool:
        """True when partitions carry per-partition value formats."""
        return self.fmt_codes is not None

    @property
    def fmt_signature(self) -> Optional[Tuple[int, ...]]:
        """Per-partition format-code tuple keying compiled signatures.

        ``None`` for homogeneous snapshots (whose single ``fmt_name`` is
        already part of the executor signature); for mixed-precision
        snapshots a reassignment changes this tuple and therefore the
        signature — the executor's retrace counter sees format churn.
        """
        if self.fmt_codes is None:
            return None
        return tuple(int(c) for c in self.fmt_codes)

    def format_histogram(self) -> dict:
        """{format name: partition count} of the served streams."""
        if self.fmt_codes is None:
            return {self.value_format.name: self.num_cores}
        out: dict = {}
        for c in self.fmt_codes:
            name = FORMAT_BY_CODE[int(c)].name
            out[name] = out.get(name, 0) + 1
        return out

    @property
    def stream_bytes(self) -> int:
        if self.groups is not None:  # native tagged words, not the f32 twins
            return int(sum(g.stream_bytes for g in self.groups))
        return self.vals.nbytes + self.cols.nbytes + self.flags.nbytes

    @property
    def value_stream_bytes(self) -> int:
        """Bytes of the streamed value sections alone (padding included)."""
        if self.groups is not None:
            return int(sum(g.value_stream_bytes for g in self.groups))
        c, p, _ = self.vals.shape
        return c * p * self.block_size * int(self.value_format.bytes_per_value)

    @property
    def bytes_per_nnz(self) -> float:
        """Effective bytes streamed per *live* nnz (grows with delta/dead mass)."""
        return self.stream_bytes / max(self.nnz, 1)

    @property
    def value_bytes_per_nnz(self) -> float:
        """Value-section bytes per live nnz — the mixed-precision win metric."""
        return self.value_stream_bytes / max(self.nnz, 1)

    def fused_words(self) -> np.ndarray:
        """The (C, P, W) fused word streams; derived on the fly if not carried."""
        if self.groups is not None:
            raise ValueError(
                "mixed-precision snapshot has no single fused array — "
                "dispatch its StreamGroups (fused) or its f32 split arrays"
            )
        if self.words is not None:
            return self.words
        return bscsr_lib.fuse_words(self.vals, self.cols, self.flags)

    def signature_info(self) -> dict:
        """The churn-varying dims that key compiled-query-fn signatures.

        Each ``*_bucket`` is a padded (power-of-two for a churn-stable
        mutable index) dim that enters the executor's shape signature; the
        paired ``*_live`` value is the exact count the snapshot actually
        uses.  A signature — and therefore a compiled query fn — is reused
        until a bucket overflows, so ``bucket > live`` headroom is what
        steady-state zero-retrace serving runs on.  Surfaced through
        ``dispatch_info()`` (see docs/SERVING.md).
        """
        live_slots = (
            int(np.max(self.num_slots)) if self.num_slots is not None
            else int(np.max(self.rows_per_partition))
        )
        return {
            "packets_bucket": int(self.vals.shape[1]),
            "slot_bucket": self.max_slots,
            "slots_live": live_slots,
            "tombstone_bucket": (
                int(self.tombstones.shape[0]) if self.tombstones is not None
                else 0
            ),
            "rows_live": self.n_rows_logical,
            "value_formats": self.format_histogram(),
        }


def stack_padded_streams(
    padded: Sequence[bscsr_lib.BSCSRMatrix],
    plan: partition_lib.PartitionPlan,
    n_cols: int,
    nnz: int,
    stream_layout: str = "split",
    words: Optional[Sequence[np.ndarray]] = None,
    **segment_fields,
) -> PackedPartitions:
    """Stack already-padded per-partition streams into one snapshot.

    The incremental mutable-index path calls this directly with its cached
    padded streams (and cached per-partition fused ``words``), so only the
    mutated partitions paid a re-pad/re-fuse.  With ``stream_layout="fused"``
    and no precomputed ``words``, each partition is fused here.
    """
    if stream_layout not in bscsr_lib.STREAM_LAYOUTS:
        raise ValueError(
            f"stream_layout must be one of {bscsr_lib.STREAM_LAYOUTS}, "
            f"got {stream_layout!r}"
        )
    words_arr = None
    if stream_layout == "fused" and segment_fields.get("groups") is None:
        # Mixed-precision snapshots never fuse their f32 twins: the fused
        # dispatch plane is the per-width-class tagged ``groups`` instead.
        if words is None:
            words = [bscsr_lib.fuse_stream(e) for e in padded]
        words_arr = np.stack(list(words))
    return PackedPartitions(
        vals=np.stack([e.vals for e in padded]),
        cols=np.stack([e.cols for e in padded]),
        flags=np.stack([e.flags for e in padded]),
        plan=plan,
        n_cols=n_cols,
        nnz=nnz,
        block_size=padded[0].block_size,
        value_format=padded[0].value_format,
        stream_layout=stream_layout,
        words=words_arr,
        **segment_fields,
    )


def stack_streams(
    streams: Sequence[bscsr_lib.BSCSRMatrix],
    plan: partition_lib.PartitionPlan,
    n_cols: int,
    nnz: int,
    packets_multiple: int = 2,
    stream_layout: str = "split",
    **segment_fields,
) -> PackedPartitions:
    """Pad per-partition streams to a common step-aligned packet count & stack.

    ``segment_fields`` forwards the segmented-extension fields (slot_to_row,
    num_slots, n_rows_total, tombstones, ...) straight into the container.
    """
    if not streams:
        raise ValueError("need at least one partition stream")
    max_p = max(e.num_packets for e in streams)
    max_p = max(-(-max_p // packets_multiple) * packets_multiple, packets_multiple)
    padded = [bscsr_lib.pad_packets(e, max_p) for e in streams]
    return stack_padded_streams(
        padded, plan, n_cols, nnz, stream_layout=stream_layout, **segment_fields
    )


def build_stream_groups(
    encoded: Sequence[bscsr_lib.BSCSRMatrix],
    packets_multiple: int = 2,
    pad_to: Optional[dict] = None,
) -> Tuple[StreamGroup, ...]:
    """Group native-format partition streams by storage width and fuse (tagged).

    Each width class pads to its OWN step-aligned packet bucket — a narrow
    group never inherits the widest partition's packet count, which is where
    the mixed-precision byte savings become real.  ``pad_to`` optionally
    pins per-class packet counts (churn-stable mutable indexes pass their
    bucketed caps); classes absent from it use their natural maximum.
    """
    by_class: dict = {}
    for ci, e in enumerate(encoded):
        by_class.setdefault(width_class_of(e.value_format).name, []).append(ci)
    groups = []
    for cname in sorted(by_class):
        cores = by_class[cname]
        max_p = max(encoded[ci].num_packets for ci in cores)
        max_p = max(-(-max_p // packets_multiple) * packets_multiple,
                    packets_multiple)
        if pad_to is not None and cname in pad_to:
            max_p = max(max_p, int(pad_to[cname]))
        words = np.stack([
            bscsr_lib.fuse_stream(
                bscsr_lib.pad_packets(encoded[ci], max_p), tagged=True
            )
            for ci in cores
        ])
        groups.append(
            StreamGroup(cname, tuple(cores), words, encoded[0].block_size)
        )
    return tuple(groups)


def pack_partitions(
    csr: bscsr_lib.CSRMatrix,
    num_partitions: int,
    block_size: int = 256,
    value_format: ValueFormat | str = "F32",
    packets_multiple: int = 2,
    stream_layout: str = "split",
    value_formats: Optional[Sequence[ValueFormat | str]] = None,
) -> PackedPartitions:
    """Partition a CSR row-wise (§III-A) and BS-CSR encode each partition.

    ``value_formats`` (one entry per partition) builds a mixed-precision
    snapshot instead: each partition is encoded in its own format, the
    tagged fused streams are grouped by storage width, and the split arrays
    are the exactly-dequantized f32 twins (reference / parity path).
    """
    plan = partition_lib.PartitionPlan.build(csr.shape[0], num_partitions)
    parts = partition_lib.partition_csr(csr, plan)
    if value_formats is None:
        fmt = FORMATS[value_format] if isinstance(value_format, str) else value_format
        encoded = [bscsr_lib.encode_bscsr(p, block_size, fmt) for p in parts]
        return stack_streams(
            encoded, plan, csr.shape[1], csr.nnz,
            packets_multiple=packets_multiple, stream_layout=stream_layout,
        )
    if len(value_formats) != len(parts):
        raise ValueError(
            f"value_formats has {len(value_formats)} entries for "
            f"{len(parts)} partitions"
        )
    fmts = [FORMATS[f] if isinstance(f, str) else f for f in value_formats]
    native = [
        bscsr_lib.encode_bscsr(p, block_size, f) for p, f in zip(parts, fmts)
    ]
    groups = build_stream_groups(native, packets_multiple=packets_multiple)
    return stack_streams(
        [bscsr_lib.dequantize_stream(e) for e in native],
        plan, csr.shape[1], csr.nnz,
        packets_multiple=packets_multiple, stream_layout=stream_layout,
        fmt_codes=np.array([f.code for f in fmts], np.int32),
        groups=groups,
    )


class _StackBuffer:
    """One preallocated (C, capacity, ·) stacked stream buffer, leased out.

    ``stamps`` records, per partition, the mutation stamp of the data the
    buffer currently holds; ``sync`` copies in only partitions whose stamp
    (or common padded packet count) went stale.  ``attach`` registers the
    snapshot viewing the buffer — the buffer may be re-leased only once every
    attached snapshot has been garbage collected, which is what keeps frozen
    snapshots bit-identical while later refreshes write elsewhere.
    """

    def __init__(self, geometry: tuple, capacity: int):
        c, block, vdtype, cdtype, flag_words, word_width = geometry
        self.geometry = geometry
        self.capacity = capacity      # packet capacity, including headroom
        self.pad_to = -1              # packet count the contents pad to
        self.stamps = np.full(c, -1, np.int64)
        self.vals = np.zeros((c, capacity, block), vdtype)
        self.cols = np.zeros((c, capacity, block), cdtype)
        self.flags = np.zeros((c, capacity, flag_words), np.int32)
        self.words = (
            np.zeros((c, capacity, word_width), np.int32) if word_width else None
        )
        self._leases: list = []

    def is_free(self) -> bool:
        """True when no live snapshot views this buffer."""
        self._leases = [r for r in self._leases if r() is not None]
        return not self._leases

    def attach(self, snapshot) -> None:
        self._leases.append(weakref.ref(snapshot))

    def sync(
        self,
        padded: Sequence[bscsr_lib.BSCSRMatrix],
        words: Optional[Sequence[np.ndarray]],
        stamps: np.ndarray,
        pad_to: int,
    ) -> int:
        """Copy in stale partitions; returns how many were copied."""
        stale_all = pad_to != self.pad_to
        copied = 0
        for ci, e in enumerate(padded):
            if not stale_all and self.stamps[ci] == stamps[ci]:
                continue
            self.vals[ci, :pad_to] = e.vals
            self.cols[ci, :pad_to] = e.cols
            self.flags[ci, :pad_to] = e.flags
            if self.words is not None:
                self.words[ci, :pad_to] = words[ci]
            copied += 1
        self.stamps[:] = stamps
        self.pad_to = pad_to
        return copied

    def view(self, name: str) -> np.ndarray:
        """Read-only (C, pad_to, ·) view of one stream for a snapshot.

        The strict slice (capacity > pad_to; see the lease() invariant) is
        non-contiguous for C > 1, so any host->device upload of it must
        copy.  A size-1 core dim keeps the slice contiguous — numpy ignores
        unit dims in the contiguity check — and a contiguous buffer CAN be
        zero-copy aliased by ``jnp.asarray`` on CPU, so that (degenerate,
        single-partition) case hands out a copy instead.
        """
        assert self.capacity > self.pad_to
        v = getattr(self, name)[:, : self.pad_to]
        if v.flags.c_contiguous:
            v = v.copy()
        v.setflags(write=False)
        return v


class _GroupStackBuffer:
    """One preallocated (Cg, capacity, 1+W) tagged width-class stack.

    The mixed-precision analogue of ``_StackBuffer``: a width class's tagged
    word streams stacked across its member cores, leased to ``StreamGroup``
    snapshots.  ``stamps`` holds the member cores' mutation stamps in group
    order, so ``sync`` rewrites only the members whose partitions actually
    mutated — a format flip always rides a mutation stamp (refresh only ever
    promotes *mutated* partitions), and a membership change alters the
    geometry key, so stamp equality is a sufficient freshness check.
    """

    def __init__(self, geometry: tuple, capacity: int):
        cores, word_width = geometry
        self.geometry = geometry
        self.capacity = capacity
        self.pad_to = -1
        self.stamps = np.full(len(cores), -1, np.int64)
        self.words = np.zeros((len(cores), capacity, word_width), np.int32)
        self._leases: list = []

    def is_free(self) -> bool:
        self._leases = [r for r in self._leases if r() is not None]
        return not self._leases

    def attach(self, snapshot) -> None:
        self._leases.append(weakref.ref(snapshot))

    def sync(
        self,
        words_list: Sequence[np.ndarray],
        stamps: np.ndarray,
        pad_to: int,
    ) -> int:
        """Copy in stale member streams; returns how many were copied."""
        stale_all = pad_to != self.pad_to
        copied = 0
        for j, w in enumerate(words_list):
            if not stale_all and self.stamps[j] == stamps[j]:
                continue
            self.words[j, :pad_to] = w
            copied += 1
        self.stamps[:] = stamps
        self.pad_to = pad_to
        return copied

    def view(self) -> np.ndarray:
        """Read-only (Cg, pad_to, 1+W) view (same aliasing rules as
        ``_StackBuffer.view``: strict slice, copy when contiguous)."""
        assert self.capacity > self.pad_to
        v = self.words[:, : self.pad_to]
        if v.flags.c_contiguous:
            v = v.copy()
        v.setflags(write=False)
        return v


class SnapshotBufferPool:
    """Copy-on-write stacked snapshot buffers for a mutable index.

    A mutable index refreshes by stacking its padded per-partition streams
    into fresh (C, P, ·) arrays; that ``np.stack`` is O(index bytes) even
    when a single row changed.  This pool keeps a few preallocated stacked
    buffers with packet headroom: each refresh leases a buffer that no live
    snapshot views (weakref-tracked), copies in ONLY the partitions whose
    mutation stamp differs from what the buffer already holds, and hands the
    snapshot read-only sliced views.  Steady-state serving ping-pongs between
    two buffers, so refresh cost is O(mutated partitions), not O(index
    bytes); holding many old snapshots alive just grows the pool.

    Caveat: liveness is tracked on the ``PackedPartitions`` object — keep the
    snapshot itself alive, not bare references to its arrays.
    """

    def __init__(self, headroom: float = 0.5, max_free: int = 2):
        self.headroom = headroom
        self.max_free = max_free
        self._buffers: list = []
        self._group_buffers: list = []

    def __len__(self) -> int:
        return len(self._buffers) + len(self._group_buffers)

    def lease(
        self,
        padded: Sequence[bscsr_lib.BSCSRMatrix],
        words: Optional[Sequence[np.ndarray]],
        stamps: np.ndarray,
        pad_to: int,
        packets_multiple: int = 2,
    ) -> Tuple[_StackBuffer, int]:
        """A free, synced buffer for these streams -> (buffer, copied count).

        Free buffers with a stale geometry (or too little capacity) are
        dropped; if every compatible buffer is still viewed by a live
        snapshot a fresh one is allocated with ``headroom`` extra packets.
        """
        word_width = words[0].shape[1] if words is not None else 0
        geometry = (
            len(padded), padded[0].vals.shape[1], padded[0].vals.dtype,
            padded[0].cols.dtype, padded[0].flags.shape[1], word_width,
        )
        # capacity must STRICTLY exceed pad_to (fresh allocations guarantee
        # it): a full-capacity lease would hand out *contiguous* views, which
        # jnp.asarray zero-copy aliases on CPU — a later re-lease would then
        # mutate memory a live jax array (from a per-call-upload dispatch)
        # still reads.  Non-contiguous views force every upload to copy.
        buf, keep, free_kept = None, [], 0
        for b in self._buffers:
            if b.is_free():
                if (b.geometry != geometry or b.capacity <= pad_to
                        or free_kept >= self.max_free):
                    continue              # unusable and unreferenced: drop
                free_kept += 1
                if buf is None:
                    buf = b
            keep.append(b)
        if buf is None:
            extra = -(-int(pad_to * self.headroom) // packets_multiple)
            cap = pad_to + max(packets_multiple, extra * packets_multiple)
            buf = _StackBuffer(geometry, cap)
            keep.append(buf)
        self._buffers = keep
        return buf, buf.sync(padded, words, stamps, pad_to)

    def lease_group(
        self,
        cores: Tuple[int, ...],
        words_list: Sequence[np.ndarray],
        stamps: np.ndarray,
        pad_to: int,
        packets_multiple: int = 2,
    ) -> Tuple[_GroupStackBuffer, int]:
        """A free, synced width-class stack -> (buffer, copied count).

        ``cores`` (the class's member partitions, in group order) is part of
        the geometry key: membership changes — a promotion moving a core
        between width classes — land in a fresh buffer rather than a stale
        one.  Same capacity/aliasing invariants as ``lease``.
        """
        geometry = (tuple(cores), words_list[0].shape[1])
        buf, keep, free_kept = None, [], 0
        for b in self._group_buffers:
            if b.is_free():
                if (b.geometry != geometry or b.capacity <= pad_to
                        or free_kept >= self.max_free):
                    continue
                free_kept += 1
                if buf is None:
                    buf = b
            keep.append(b)
        if buf is None:
            extra = -(-int(pad_to * self.headroom) // packets_multiple)
            cap = pad_to + max(packets_multiple, extra * packets_multiple)
            buf = _GroupStackBuffer(geometry, cap)
            keep.append(buf)
        self._group_buffers = keep
        return buf, buf.sync(words_list, stamps, pad_to)


def finalize_candidates(
    local_vals: jnp.ndarray,   # (C, k)
    local_rows: jnp.ndarray,   # (C, k) partition-local slot ids
    row_starts: jnp.ndarray,   # (C,)
    rows_per_part: jnp.ndarray,  # (C,) candidate slots per core
    big_k: int,
    n_rows: int,
    slot_to_row: Optional[jnp.ndarray] = None,  # (C, L) slot -> global row id
    tombstones: Optional[jnp.ndarray] = None,   # (n_rows,) bool deleted ids
    row_map: Optional[jnp.ndarray] = None,      # (L2,) local -> global row id
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mask sentinels/tombstones, globalize slot ids, merge c*k into Top-K.

    Pure-base indexes use the affine mapping ``row_starts + local``;
    segmented indexes pass ``slot_to_row``, whose ``INVALID_ROW`` entries
    retire dead slots (inter-segment sentinels, replaced/deleted rows).  The
    ``tombstones`` bitmap additionally masks deleted global row ids — it is
    what keeps a deleted id unreturnable after compaction re-encodes the
    stream.

    ``row_map`` is the sharded plane's extra hop: a shard-local index
    resolves candidates to *shard-local* ids, and ``row_map`` translates
    those to the sharded collection's global ids (``INVALID_ROW`` entries
    mask padding past the shard's id space).  It applies *after* the local
    ``slot_to_row``/``tombstones`` masks, so ``tombstones`` stays indexed by
    the same (local) id space as ``slot_to_row``; ``n_rows`` must then be
    the *global* sentinel, which makes per-shard merges tie-break on global
    ids — the property that keeps sharded top-k bit-identical to the
    single-device merge.
    """
    valid = local_rows < rows_per_part[:, None]
    if slot_to_row is None:
        global_rows = local_rows + row_starts[:, None]
    else:
        idx = jnp.clip(local_rows, 0, slot_to_row.shape[1] - 1)
        global_rows = jnp.take_along_axis(slot_to_row, idx, axis=1)
        valid = valid & (global_rows != INVALID_ROW)
    if tombstones is not None:
        safe = jnp.clip(global_rows, 0, tombstones.shape[0] - 1)
        valid = valid & ~tombstones[safe]
    if row_map is not None:
        safe = jnp.clip(global_rows, 0, row_map.shape[0] - 1)
        mapped = row_map[safe]
        valid = valid & (mapped != INVALID_ROW)
        global_rows = mapped
    vals = jnp.where(valid, local_vals, NEG_INF)
    rows = jnp.where(valid, global_rows, n_rows)
    return partition_lib.merge_topk(vals, rows, big_k, n_rows)


def finalize_candidates_batched(
    local_vals: jnp.ndarray,   # (C, Q, k)
    local_rows: jnp.ndarray,   # (C, Q, k)
    row_starts: jnp.ndarray,
    rows_per_part: jnp.ndarray,
    big_k: int,
    n_rows: int,
    slot_to_row: Optional[jnp.ndarray] = None,
    tombstones: Optional[jnp.ndarray] = None,
    row_map: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-query finalize over the multi-query kernel's (C, Q, k) candidates."""
    fin = functools.partial(
        finalize_candidates,
        row_starts=row_starts,
        rows_per_part=rows_per_part,
        big_k=big_k,
        n_rows=n_rows,
        slot_to_row=slot_to_row,
        tombstones=tombstones,
        row_map=row_map,
    )
    return jax.vmap(fin, in_axes=(1, 1))(local_vals, local_rows)  # (Q, big_k)


def check_segment_slots(packed: PackedPartitions, packets_per_step: int,
                        seg_slots: int) -> None:
    """Refuse a segment space some step of ``packed`` overflows.

    Stage 2 drops every entry whose segment id is >= ``seg_slots`` without
    a trace, so a bound that is too small gives wrong answers: it is
    checked on the host before the stream is served, never clipped.
    """
    starts = packed.max_step_row_starts(packets_per_step)
    if starts >= seg_slots:
        raise ValueError(
            f"a grid step of {packets_per_step} packets holds {starts} row "
            f"starts, which {seg_slots} segment slots cannot hold (segment 0 "
            f"is the carried row)"
        )


def _finalize_kwargs(packed: PackedPartitions) -> dict:
    """Device-array finalize inputs for a packed snapshot (shared by paths)."""
    kw = dict(
        row_starts=jnp.asarray(packed.row_starts),
        rows_per_part=jnp.asarray(packed.candidate_slots),
        n_rows=packed.n_rows_logical,
    )
    if packed.slot_to_row is not None:
        kw["slot_to_row"] = jnp.asarray(packed.slot_to_row)
    if packed.has_tombstones:  # computed once at snapshot build, never re-scanned
        kw["tombstones"] = jnp.asarray(packed.tombstones)
    return kw


def default_interpret() -> bool:
    """The one rule for running the Pallas kernels: compiled by Mosaic on a
    TPU, through the Pallas interpreter on the CPU, and nowhere else."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the BS-CSR kernels run compiled on a TPU or interpreted on the CPU, "
        f"not on {backend!r}"
    )


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> :func:`default_interpret`; an explicit choice is kept."""
    return default_interpret() if interpret is None else bool(interpret)


def resolve_gather_mode(gather_mode: str) -> str:
    """Map "auto" to the gather the compiled kernel uses ("onehot").

    "take" stays available as the interpret-only reference gather; the
    kernel refuses it when ``interpret=False``.
    """
    return "onehot" if gather_mode == "auto" else gather_mode


def _kernel_streams(packed: PackedPartitions, stream_layout: Optional[str]):
    """(layout, device stream args) for a dispatch call.

    ``stream_layout=None`` follows the snapshot's own layout; an explicit
    layout overrides it (deriving the fused words on the fly if the snapshot
    carries only the split arrays — parity tests lean on this).
    """
    layout = stream_layout or packed.stream_layout
    if layout == "fused":
        return layout, (jnp.asarray(packed.fused_words()), None, None)
    return layout, (
        jnp.asarray(packed.vals),
        jnp.asarray(packed.cols),
        jnp.asarray(packed.flags),
    )


def _grouped_local_topk(
    x: jnp.ndarray,
    packed: PackedPartitions,
    *,
    k: int,
    packets_per_step: int,
    gather_mode: str,
    inner_loop: str,
    interpret: bool,
    batched: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mixed-precision fused dispatch: one kernel call per width class.

    Each :class:`StreamGroup` streams its own tagged word array (narrow
    groups stream narrow packets — the byte savings); the per-core
    candidates are scattered back into the snapshot's ``(C, [Q,] k)`` order
    before the shared finalize.  Every core belongs to exactly one group,
    so the scatter fully overwrites the init sentinels.
    """
    c = packed.num_cores
    shape = (c, x.shape[0], k) if batched else (c, k)
    lv = jnp.full(shape, NEG_INF, jnp.float32)
    lr = jnp.full(shape, packed.max_slots, jnp.int32)
    for g in packed.groups:
        common = dict(
            k=k, n_rows=packed.max_slots, packets_per_step=packets_per_step,
            fmt_name=g.class_name, gather_mode=gather_mode,
            inner_loop=inner_loop, stream_layout="fused",
            block_size=packed.block_size,
            seg_slots=packed.seg_slots(packets_per_step), interpret=interpret,
        )
        kernel = bscsr_topk_spmv_multiquery if batched else bscsr_topk_spmv
        gv, gr = kernel(x, jnp.asarray(g.words), **common)
        cores = jnp.asarray(np.asarray(g.cores, np.int32))
        lv = lv.at[cores].set(gv)
        lr = lr.at[cores].set(gr)
    return lv, lr


def topk_spmv_blocked(
    x: jnp.ndarray,
    packed: PackedPartitions,
    big_k: int,
    k: int = 8,
    packets_per_step: int = 2,
    gather_mode: str = "onehot",
    inner_loop: str = "linear",
    stream_layout: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-device multi-core approximate Top-K SpMV via the Pallas kernel.

    ``interpret=None`` follows :func:`default_interpret`.
    """
    interpret = resolve_interpret(interpret)
    layout = stream_layout or packed.stream_layout
    if layout == "fused" and packed.groups is not None:
        lv, lr = _grouped_local_topk(
            jnp.asarray(x, jnp.float32), packed, k=k,
            packets_per_step=packets_per_step,
            gather_mode=resolve_gather_mode(gather_mode),
            inner_loop=inner_loop, interpret=interpret, batched=False,
        )
        return finalize_candidates(
            lv, lr, big_k=big_k, **_finalize_kwargs(packed)
        )
    layout, streams = _kernel_streams(packed, stream_layout)
    lv, lr = bscsr_topk_spmv(
        jnp.asarray(x, jnp.float32),
        *streams,
        k=k,
        n_rows=packed.max_slots,
        packets_per_step=packets_per_step,
        fmt_name=packed.value_format.name,
        gather_mode=resolve_gather_mode(gather_mode),
        inner_loop=inner_loop,
        stream_layout=layout,
        block_size=packed.block_size,
        seg_slots=packed.seg_slots(packets_per_step),
        interpret=interpret,
    )
    return finalize_candidates(lv, lr, big_k=big_k, **_finalize_kwargs(packed))


def topk_spmv_batched(
    xs: jnp.ndarray,           # (Q, M) query batch
    packed: PackedPartitions,
    big_k: int,
    k: int = 8,
    packets_per_step: int = 2,
    gather_mode: str = "onehot",
    inner_loop: str = "linear",
    stream_layout: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Q queries in ONE pass over the stream via the multi-query kernel.

    Returns (Q, big_k) values and global row ids — the batched analogue of
    ``topk_spmv_blocked``; per-query HBM traffic is divided by Q.
    """
    if xs.ndim != 2 or xs.shape[0] == 0:
        raise ValueError(f"xs must be a non-empty (Q, M) batch, got {xs.shape}")
    interpret = resolve_interpret(interpret)
    gather_mode = resolve_gather_mode(gather_mode)
    layout = stream_layout or packed.stream_layout
    if layout == "fused" and packed.groups is not None:
        lv, lr = _grouped_local_topk(
            jnp.asarray(xs, jnp.float32), packed, k=k,
            packets_per_step=packets_per_step, gather_mode=gather_mode,
            inner_loop=inner_loop, interpret=interpret, batched=True,
        )
        return finalize_candidates_batched(
            lv, lr, big_k=big_k, **_finalize_kwargs(packed)
        )
    layout, streams = _kernel_streams(packed, stream_layout)
    lv, lr = bscsr_topk_spmv_multiquery(
        jnp.asarray(xs, jnp.float32),
        *streams,
        k=k,
        n_rows=packed.max_slots,
        packets_per_step=packets_per_step,
        fmt_name=packed.value_format.name,
        gather_mode=gather_mode,
        inner_loop=inner_loop,
        stream_layout=layout,
        block_size=packed.block_size,
        seg_slots=packed.seg_slots(packets_per_step),
        interpret=interpret,
    )
    return finalize_candidates_batched(
        lv, lr, big_k=big_k, **_finalize_kwargs(packed)
    )


# ---------------------------------------------------------------------------
# Accumulate mode (select_topk=False): y = alpha * A @ x + beta * y.
#
# The top-k select stage never runs: the kernel (or the jnp oracle) emits raw
# per-core slot sums, and the masking that `finalize_candidates` would have
# applied to candidates — per-core live-slot counts, slot->row retirement
# (INVALID_ROW), tombstoned global ids, the sharded plane's local->global
# row_map — moves HERE, into the dense scatter.  `finalize_candidates` must
# never see accumulate-mode output (its NEG_INF sentinel algebra is top-k
# specific); `tests/test_graph_workloads.py` pins this.
# ---------------------------------------------------------------------------

def scatter_slot_sums(
    slot_sums: jnp.ndarray,      # (C, L) raw per-core slot sums
    row_starts: jnp.ndarray,     # (C,)
    rows_per_part: jnp.ndarray,  # (C,) live candidate slots per core
    n_out: int,                  # static output length (global row space)
    slot_to_row: Optional[jnp.ndarray] = None,  # (C, L) slot -> global row
    tombstones: Optional[jnp.ndarray] = None,   # bool bitmap over global ids
    row_map: Optional[jnp.ndarray] = None,      # (L2,) local -> global row id
) -> jnp.ndarray:
    """Scatter per-core slot sums into one dense (n_out,) vector.

    The accumulate-mode replacement for ``finalize_candidates``: invalid
    lanes — padded slots past a core's live count, retired slots
    (``INVALID_ROW``), tombstoned/deleted rows, and sharded-padding rows the
    ``row_map`` marks invalid — contribute exactly ``0.0`` to ``y`` instead
    of being masked to NEG_INF.  Each live row occupies exactly one slot on
    one core, so the scatter-add never sums two live lanes into one output
    element (load-bearing for the sharded psum bit-identity argument).
    """
    c, l = slot_sums.shape
    slots = jax.lax.broadcasted_iota(jnp.int32, (c, l), 1)
    valid = slots < rows_per_part[:, None]
    if slot_to_row is None:
        rows = slots + row_starts[:, None]
    else:
        rows = slot_to_row
        valid = valid & (rows != INVALID_ROW)
    if tombstones is not None:
        safe = jnp.clip(rows, 0, tombstones.shape[0] - 1)
        valid = valid & ~tombstones[safe]
    if row_map is not None:
        safe = jnp.clip(rows, 0, row_map.shape[0] - 1)
        rows = row_map[safe]
        valid = valid & (rows != INVALID_ROW)
    valid = valid & (rows >= 0) & (rows < n_out)
    contrib = jnp.where(valid, slot_sums, 0.0).reshape(-1)
    idx = jnp.clip(rows, 0, n_out - 1).reshape(-1)
    return jnp.zeros((n_out,), jnp.float32).at[idx].add(contrib)


def _scatter_kwargs(packed: PackedPartitions) -> dict:
    """Device-array scatter inputs for a packed snapshot (accumulate analogue
    of ``_finalize_kwargs`` — note: no ``n_rows`` sentinel; the caller fixes
    the static output length)."""
    kw = dict(
        row_starts=jnp.asarray(packed.row_starts),
        rows_per_part=jnp.asarray(packed.candidate_slots),
    )
    if packed.slot_to_row is not None:
        kw["slot_to_row"] = jnp.asarray(packed.slot_to_row)
    if packed.has_tombstones:
        kw["tombstones"] = jnp.asarray(packed.tombstones)
    return kw


def _grouped_slot_sums(
    x: jnp.ndarray,
    packed: PackedPartitions,
    *,
    packets_per_step: int,
    gather_mode: str,
    inner_loop: str,
    interpret: bool,
) -> jnp.ndarray:
    """Mixed-precision accumulate dispatch: one kernel call per width class,
    per-core slot sums scattered back into snapshot ``(C, L)`` order."""
    sums = jnp.zeros((packed.num_cores, packed.max_slots), jnp.float32)
    for g in packed.groups:
        gs = bscsr_spmv(
            x, jnp.asarray(g.words),
            n_rows=packed.max_slots, packets_per_step=packets_per_step,
            fmt_name=g.class_name, gather_mode=gather_mode,
            inner_loop=inner_loop, stream_layout="fused",
            block_size=packed.block_size,
            seg_slots=packed.seg_slots(packets_per_step), interpret=interpret,
        )
        cores = jnp.asarray(np.asarray(g.cores, np.int32))
        sums = sums.at[cores].set(gs)
    return sums


def bscsr_spmv_blocked(
    x: jnp.ndarray,
    packed: PackedPartitions,
    *,
    alpha: float | jnp.ndarray = 1.0,
    beta: float | jnp.ndarray = 0.0,
    y: Optional[jnp.ndarray] = None,
    n_out: Optional[int] = None,
    packets_per_step: int = 2,
    gather_mode: str = "onehot",
    inner_loop: str = "linear",
    stream_layout: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """``y = alpha * A @ x + beta * y`` via the accumulate-mode Pallas kernel.

    The per-call-upload baseline (the accumulate analogue of
    ``topk_spmv_blocked``); iterative workloads go through
    ``QueryExecutor.spmv`` instead, which pins the snapshot and keeps ``y``
    device-resident between iterations.  ``n_out`` defaults to the snapshot's
    global row space (or ``y``'s length when given).
    """
    if n_out is None:
        n_out = int(y.shape[0]) if y is not None else packed.n_rows_logical
    interpret = resolve_interpret(interpret)
    layout = stream_layout or packed.stream_layout
    xd = jnp.asarray(x, jnp.float32)
    if layout == "fused" and packed.groups is not None:
        sums = _grouped_slot_sums(
            xd, packed, packets_per_step=packets_per_step,
            gather_mode=resolve_gather_mode(gather_mode),
            inner_loop=inner_loop, interpret=interpret,
        )
    else:
        layout, streams = _kernel_streams(packed, stream_layout)
        sums = bscsr_spmv(
            xd, *streams,
            n_rows=packed.max_slots,
            packets_per_step=packets_per_step,
            fmt_name=packed.value_format.name,
            gather_mode=resolve_gather_mode(gather_mode),
            inner_loop=inner_loop,
            stream_layout=layout,
            block_size=packed.block_size,
            seg_slots=packed.seg_slots(packets_per_step),
            interpret=interpret,
        )
    ax = scatter_slot_sums(sums, n_out=n_out, **_scatter_kwargs(packed))
    if y is None:
        return alpha * ax
    return alpha * ax + beta * jnp.asarray(y, jnp.float32)


def bscsr_spmv_reference(
    x: jnp.ndarray,
    packed: PackedPartitions,
    *,
    alpha: float | jnp.ndarray = 1.0,
    beta: float | jnp.ndarray = 0.0,
    y: Optional[jnp.ndarray] = None,
    n_out: Optional[int] = None,
) -> jnp.ndarray:
    """Accumulate mode via the pure-jnp oracle (same masking epilogue)."""
    if n_out is None:
        n_out = int(y.shape[0]) if y is not None else packed.n_rows_logical
    sums = ref_lib.bscsr_slot_sums_stacked(
        jnp.asarray(packed.vals),
        jnp.asarray(packed.cols),
        jnp.asarray(packed.flags),
        jnp.asarray(x, jnp.float32),
        packed.max_slots,
        packed.value_format,
    )
    ax = scatter_slot_sums(sums, n_out=n_out, **_scatter_kwargs(packed))
    if y is None:
        return alpha * ax
    return alpha * ax + beta * jnp.asarray(y, jnp.float32)


def topk_spmv_reference(
    x: jnp.ndarray,
    packed: PackedPartitions,
    big_k: int,
    k: int = 8,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Same partitioned approximation, evaluated with the pure-jnp oracle."""
    lv, lr = ref_lib.bscsr_topk_ref_stacked(
        jnp.asarray(packed.vals),
        jnp.asarray(packed.cols),
        jnp.asarray(packed.flags),
        jnp.asarray(x, jnp.float32),
        jnp.asarray(packed.candidate_slots),
        packed.max_slots,
        k,
        packed.value_format,
    )
    return finalize_candidates(lv, lr, big_k=big_k, **_finalize_kwargs(packed))


def topk_spmv_reference_batched(
    xs: jnp.ndarray,           # (Q, M)
    packed: PackedPartitions,
    big_k: int,
    k: int = 8,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched oracle: vmap of the vectorized reference over the query batch."""
    max_slots = packed.max_slots
    vals = jnp.asarray(packed.vals)
    cols = jnp.asarray(packed.cols)
    flags = jnp.asarray(packed.flags)
    slots_per = jnp.asarray(packed.candidate_slots)
    fin_kwargs = _finalize_kwargs(packed)

    def one_query(x):
        lv, lr = ref_lib.bscsr_topk_ref_stacked(
            vals, cols, flags, x, slots_per, max_slots, k, packed.value_format
        )
        return finalize_candidates(lv, lr, big_k=big_k, **fin_kwargs)

    return jax.vmap(one_query)(jnp.asarray(xs, jnp.float32))

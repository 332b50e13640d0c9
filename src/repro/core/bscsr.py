"""Block-Streaming CSR (BS-CSR) — the paper's §III-B layout, adapted to TPU.

The FPGA original packs ``B`` non-zeros plus packet-local metadata into one
512-bit HBM transaction: reduced-precision ``idx``/``val``, a packet-relative
``ptr`` of ceil(log2 B)-bit counters and a single ``new_row`` carry bit.  The
packet is an *independent mini-CSR*: global row ids are never stored, they are
recovered by streaming.

TPU adaptation (DESIGN.md §2): the HBM<->VMEM transfer granule is a tile, so a
*tile-packet* holds ``B`` non-zeros as three parallel, tile-aligned streams:

  vals   (P, B)        float32 | bfloat16 | int16/int8 Q-format   (paper: val, V bits)
  cols   (P, B)        int32 | int16                              (paper: idx, 10 bits)
  flags  (P, B // 32)  int32 bit-pack, bit i set <=> nnz i starts a new row
                                                                  (paper: ptr + new_row)

Flag semantics: the running row id of nnz ``t`` in the stream is
``popcount(flags[:t+1]) - 1``.  Bit 0 of a packet is the inverse of the paper's
``new_row`` continuation bit.  Rows with zero stored entries receive one
placeholder (col 0, val 0) nnz so the row counter stays aligned (paper §III-B:
"missing rows are handled with placeholder 0 values").  One trailing sentinel
row-start closes the final real row; sentinel candidates are masked at merge
time by ``row_id >= n_rows``.

Like the original, the layout is *oblivious to the row-density distribution*:
throughput depends only on nnz, never on skew.

Fused single-stream packet layout
---------------------------------

The split form above is three separately-pipelined arrays — three strided HBM
access patterns per grid step where the paper's 512-bit packet is ONE burst.
``fuse_stream`` packs each tile-packet's ``(flags | cols | vals)`` into a
single contiguous int32 word row — the TPU analogue of the paper's packet —
so the kernel pipelines exactly one VMEM block from one contiguous HBM region
per grid step and recovers the fields with shift/mask bit-ops::

  word index   0 ........ B/32-1 | B/32 ....... B/32+Wc-1 | ............ end
               +-----------------+------------------------+-----------------+
  packet row   | flags (B bits,  | cols (B ids at int16/  | vals (B values  |
  (W int32)    |  1 bit/nnz)     |  int32 width, packed   |  at ValueFormat |
               |                 |  2-per-word if int16)  |  storage width) |
               +-----------------+------------------------+-----------------+
  Wf = B/32 words        Wc = B*col_bytes/4 words   Wv = B*val_bytes/4 words

Narrow sub-fields are *planar* within their section: with ``n`` values per
word (2 for int16/bf16, 4 for int8), sub-word ``q`` of word ``i`` holds entry
``q * B/n + i``.  Each sub-word plane is then a contiguous run of entries, so
the in-kernel decode is shifts and masks plus a lane concatenation of the
planes — no lane interleave, which the TPU compiler does not lower.
Fused and split forms are bit-identical in content and total bytes; the win
is stream *count* (3 -> 1 contiguous burst per core per step).

*Tagged* fused packets (mixed-precision snapshots) prepend ONE header word
carrying the partition's :class:`~repro.core.quantization.ValueFormat` code::

  word index   0     | 1 ....... B/32 | B/32+1 .. +Wc | .............. end
               +-----+----------------+---------------+--------------------+
  packet row   | tag | flags (B bits) | cols          | vals (width of the |
  (1+W int32)  |     |                |               |  tagged class)     |
               +-----+----------------+---------------+--------------------+

Partitions are grouped by value *storage width* (4B / 2B / 1B classes) so
each group stays rectangular; within the shared-width 2-byte class the tag
is what lets the kernel decode BF16 vs Q15 packets at run time.  The
homogeneous layout above is unchanged — no header, no churn.

Bytes per nnz (B = 256, idx = int16, flag bit amortized):

  format   fused/split stream   plain COO (f32)   note
  F32      6.125                12.0              4 + 2 + 1/8
  BF16     4.125                12.0              2 + 2 + 1/8
  Q15      4.125                12.0              int16 fixed point
  Q7       3.125                12.0              1 + 2 + 1/8

Base / delta / tombstone layout (mutable indexes)
-------------------------------------------------

Because global row ids are never stored — the kernel recovers the running
*slot* id purely by counting row-start flags — a stream can be extended
without re-encoding anything that was already written:

  base segment     the original ``encode_bscsr`` output for a partition,
                   slots 0..n-1 plus its trailing sentinel row-start.
  delta segment    ``encode_delta_rows`` encodes appended/replacement rows as
                   an ordinary mini BS-CSR stream; ``append_packets``
                   concatenates its packets after the base segment.  The
                   delta's first row-start *closes* the base sentinel, which
                   becomes a dead candidate slot; the appended rows occupy the
                   slots after it.  The kernel body is untouched — it just
                   keeps counting flags.
  tombstones       row deletion and replacement never rewrite the stream:
                   the owning slot is retired in the host-side slot->row map
                   (``kernels/ops.py``) and, for deletions, the global row id
                   is marked in a :class:`TombstoneBitmap`.  Both are masked
                   in ``finalize_candidates`` before the merge, so a
                   tombstoned row can never be returned.

Periodic compaction (``MutableTopKSpMVIndex.compact``) re-encodes the live
rows into a fresh base segment, reclaiming dead slots and delta padding and
restoring base-only bytes/nnz.

docs/ARCHITECTURE.md walks this layout through the full query data path
(encode -> fused stream -> kernel stages -> finalize -> executor dispatch).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import numpy as np

from repro.core.quantization import F32, FORMATS, ValueFormat, host_dequantize, quantize

FLAG_WORD_BITS = 32


@dataclasses.dataclass(frozen=True)
class CSRMatrix:
    """Plain host-side CSR (scipy is unavailable offline; this is self-contained)."""

    indptr: np.ndarray   # (N+1,) int64
    indices: np.ndarray  # (nnz,) int32
    data: np.ndarray     # (nnz,) float32
    shape: Tuple[int, int]

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    def to_dense(self) -> np.ndarray:
        n, m = self.shape
        out = np.zeros((n, m), dtype=np.float32)
        rows = np.repeat(np.arange(n), np.diff(self.indptr))
        out[rows, self.indices] = self.data
        return out

    def row_slice(self, start: int, stop: int) -> "CSRMatrix":
        """Rows [start, stop) as a new CSR — used by the partitioner (§III-A)."""
        lo, hi = int(self.indptr[start]), int(self.indptr[stop])
        return CSRMatrix(
            indptr=(self.indptr[start : stop + 1] - lo).astype(np.int64),
            indices=self.indices[lo:hi],
            data=self.data[lo:hi],
            shape=(stop - start, self.shape[1]),
        )


@dataclasses.dataclass(frozen=True)
class BSCSRMatrix:
    """Tile-packet BS-CSR stream for one partition (one 'core')."""

    vals: np.ndarray          # (P, B) storage dtype
    cols: np.ndarray          # (P, B) int32/int16
    flags: np.ndarray         # (P, B // 32) int32 bit-pack (row-start bits)
    n_rows: int               # real rows (excludes the sentinel row)
    n_cols: int
    nnz: int                  # real non-zeros (excludes placeholders/padding)
    block_size: int           # B
    value_format: ValueFormat

    @property
    def num_packets(self) -> int:
        return int(self.vals.shape[0])

    @property
    def stream_bytes(self) -> int:
        return self.vals.nbytes + self.cols.nbytes + self.flags.nbytes

    @property
    def bytes_per_nnz(self) -> float:
        return self.stream_bytes / max(self.nnz, 1)

    def fused_words(self) -> np.ndarray:
        """This stream's fused single-stream form (see :func:`fuse_stream`)."""
        return fuse_stream(self)


def _pack_bits(bits: np.ndarray) -> np.ndarray:
    """(..., B) bool -> (..., B//32) int32 little-endian bit-pack."""
    b = bits.shape[-1]
    assert b % FLAG_WORD_BITS == 0, "block size must be a multiple of 32"
    words = bits.reshape(*bits.shape[:-1], b // FLAG_WORD_BITS, FLAG_WORD_BITS)
    weights = (1 << np.arange(FLAG_WORD_BITS, dtype=np.int64))
    packed = (words.astype(np.int64) * weights).sum(axis=-1)
    # Keep values in int32 range via wrap (bit 31 becomes the sign bit).
    return packed.astype(np.uint32).view(np.int32)


def unpack_bits(packed: np.ndarray, block_size: int) -> np.ndarray:
    """(..., B//32) int32 -> (..., B) bool. Host-side inverse (tests/debug)."""
    w = packed.view(np.uint32).astype(np.uint64)
    shifts = np.arange(FLAG_WORD_BITS, dtype=np.uint64)
    bits = (w[..., None] >> shifts) & 1
    return bits.reshape(*packed.shape[:-1], block_size).astype(bool)


def col_index_dtype(n_cols: int) -> np.dtype:
    """Paper: 'realistic size bounds (idx < 1024) allow much greater coalescing'."""
    return np.dtype(np.int16) if n_cols <= np.iinfo(np.int16).max else np.dtype(np.int32)


def encode_bscsr(
    csr: CSRMatrix,
    block_size: int = 256,
    value_format: ValueFormat | str = "F32",
    pad_packets_to: Optional[int] = None,
) -> BSCSRMatrix:
    """Encode a CSR partition into the BS-CSR tile-packet stream."""
    fmt = FORMATS[value_format] if isinstance(value_format, str) else value_format
    n, m = csr.shape
    row_lens = np.diff(csr.indptr)

    # Insert a placeholder nnz for every empty row so the stream's row counter
    # stays aligned with real row ids (paper's placeholder-0 rule).
    if (row_lens == 0).any():
        out_lens = np.maximum(row_lens, 1)
        total = int(out_lens.sum())
        vals = np.zeros(total, dtype=np.float32)
        cols = np.zeros(total, dtype=np.int64)
        starts = np.concatenate([[0], np.cumsum(out_lens)])[:-1]
        src_rows = np.repeat(np.arange(n), row_lens)
        dst = np.repeat(starts, row_lens) + (
            np.arange(csr.nnz) - np.repeat(csr.indptr[:-1], row_lens)
        )
        vals[dst] = csr.data
        cols[dst] = csr.indices
        row_starts = starts
        total_nnz = total
    else:
        vals = csr.data.astype(np.float32)
        cols = csr.indices.astype(np.int64)
        row_starts = csr.indptr[:-1]
        total_nnz = csr.nnz

    # Row-start flags + one sentinel row-start that closes the final real row.
    flags = np.zeros(total_nnz + 1, dtype=bool)
    flags[row_starts] = True
    flags[total_nnz] = True
    vals = np.concatenate([vals, np.zeros(1, dtype=np.float32)])
    cols = np.concatenate([cols, np.zeros(1, dtype=np.int64)])

    # Pad to a whole number of packets (padding continues the sentinel row).
    stream_len = total_nnz + 1
    num_packets = math.ceil(stream_len / block_size)
    if pad_packets_to is not None:
        num_packets = max(num_packets, pad_packets_to)
    padded = num_packets * block_size
    pad = padded - stream_len
    vals = np.concatenate([vals, np.zeros(pad, dtype=np.float32)])
    cols = np.concatenate([cols, np.zeros(pad, dtype=np.int64)])
    flags = np.concatenate([flags, np.zeros(pad, dtype=bool)])

    cdtype = col_index_dtype(m)
    return BSCSRMatrix(
        vals=quantize(vals, fmt).reshape(num_packets, block_size),
        cols=cols.astype(cdtype).reshape(num_packets, block_size),
        flags=_pack_bits(flags.reshape(num_packets, block_size)),
        n_rows=n,
        n_cols=m,
        nnz=csr.nnz,
        block_size=block_size,
        value_format=fmt,
    )


def pad_packets(bs: BSCSRMatrix, num_packets: int) -> BSCSRMatrix:
    """Extend an encoded stream to ``num_packets`` with empty tail packets.

    Padding continues the sentinel row (zero vals/cols, no row-start flags),
    so the result is identical to encoding with ``pad_packets_to`` — without
    re-running the encoder.
    """
    pad = num_packets - bs.num_packets
    if pad < 0:
        raise ValueError(
            f"cannot shrink a stream: have {bs.num_packets} packets, "
            f"asked for {num_packets}"
        )
    if pad == 0:
        return bs
    return dataclasses.replace(
        bs,
        vals=np.concatenate(
            [bs.vals, np.zeros((pad, bs.block_size), dtype=bs.vals.dtype)]
        ),
        cols=np.concatenate(
            [bs.cols, np.zeros((pad, bs.block_size), dtype=bs.cols.dtype)]
        ),
        flags=np.concatenate(
            [bs.flags, np.zeros((pad, bs.flags.shape[1]), dtype=bs.flags.dtype)]
        ),
    )


# ---------------------------------------------------------------------------
# Fused single-stream packet layout (see module docstring diagram)
# ---------------------------------------------------------------------------

STREAM_LAYOUTS = ("split", "fused")


def fused_word_counts(
    block_size: int, value_format: ValueFormat | str, col_dtype
) -> Tuple[int, int, int]:
    """(flag, col, val) int32 words per fused packet of ``block_size`` nnz."""
    fmt = FORMATS[value_format] if isinstance(value_format, str) else value_format
    col_bytes = np.dtype(col_dtype).itemsize
    val_bytes = int(fmt.bytes_per_value)
    if block_size % FLAG_WORD_BITS:
        raise ValueError("block size must be a multiple of 32")
    if (block_size * col_bytes) % 4 or (block_size * val_bytes) % 4:
        raise ValueError("block size must pack cols/vals into whole int32 words")
    return (
        block_size // FLAG_WORD_BITS,
        block_size * col_bytes // 4,
        block_size * val_bytes // 4,
    )


def _planar_words(a: np.ndarray) -> np.ndarray:
    """(..., B) values -> (..., B*itemsize/4) int32 words, planar sub-words."""
    n = 4 // a.dtype.itemsize
    b = a.shape[-1]
    planes = a.reshape(*a.shape[:-1], n, b // n).swapaxes(-1, -2)
    return np.ascontiguousarray(planes).reshape(a.shape).view(np.int32)


def _from_planar_words(words: np.ndarray, dtype) -> np.ndarray:
    """Inverse of :func:`_planar_words`."""
    a = np.ascontiguousarray(words).view(np.dtype(dtype))
    n = a.shape[-1] // words.shape[-1]
    planes = a.reshape(*words.shape, n).swapaxes(-1, -2)
    return np.ascontiguousarray(planes).reshape(a.shape)


def fuse_words(
    vals: np.ndarray, cols: np.ndarray, flags: np.ndarray, tag: Optional[int] = None
) -> np.ndarray:
    """Pack split ``(..., B)``/``(..., B//32)`` arrays into fused int32 words.

    The single definition of the fused word layout (``flags | cols | vals``
    per packet row, planar sub-words — see the module docstring): every
    byte lands unchanged, so ``defuse_stream`` round-trips losslessly and the
    in-kernel decode (`kernels/bscsr_topk_spmv._decode_fused`)
    reconstructs bit-identical operands.

    ``tag`` (mixed-precision snapshots only) prepends one header word per
    packet row carrying the partition's value-format code — see the tagged
    diagram in the module docstring.  ``None`` keeps the homogeneous layout.
    """
    parts = [np.ascontiguousarray(flags), _planar_words(cols), _planar_words(vals)]
    if tag is not None:
        header = np.full(flags.shape[:-1] + (1,), int(tag), dtype=np.int32)
        parts.insert(0, header)
    return np.concatenate(parts, axis=-1)


def fuse_stream(bs: BSCSRMatrix, tagged: bool = False) -> np.ndarray:
    """A stream's fused ``(P, W)`` int32 word form (see :func:`fuse_words`)."""
    tag = bs.value_format.code if tagged else None
    return fuse_words(bs.vals, bs.cols, bs.flags, tag=tag)


def defuse_stream(
    words: np.ndarray,
    block_size: int,
    value_format: ValueFormat | str,
    col_dtype,
    tagged: bool = False,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Fused ``(P, W)`` words -> ``(vals, cols, flags)`` split arrays (host).

    For ``tagged`` streams the header word of every packet must match
    ``value_format``'s code; the header is stripped before the split.
    """
    fmt = FORMATS[value_format] if isinstance(value_format, str) else value_format
    wf, wc, wv = fused_word_counts(block_size, fmt, col_dtype)
    header = 1 if tagged else 0
    if words.shape[-1] != header + wf + wc + wv:
        raise ValueError(
            f"fused stream width {words.shape[-1]} != expected "
            f"{header + wf + wc + wv} (B={block_size}, fmt={fmt.name}, "
            f"cols={np.dtype(col_dtype).name}, tagged={tagged})"
        )
    if tagged:
        tags = words[..., 0]
        if tags.size and not (tags == fmt.code).all():
            raise ValueError(
                f"tagged stream header mismatch: expected code {fmt.code} "
                f"({fmt.name}), saw {sorted(np.unique(tags).tolist())}"
            )
        words = words[..., 1:]
    flags = np.ascontiguousarray(words[..., :wf])
    cols = _from_planar_words(words[..., wf : wf + wc], col_dtype)
    vals = _from_planar_words(words[..., wf + wc :], fmt.np_dtype)
    return vals, cols, flags


def dequantize_stream(bs: BSCSRMatrix) -> BSCSRMatrix:
    """An F32 twin of a stream: values exactly dequantized on the host.

    Mixed-precision snapshots keep these as their split arrays so the
    reference oracle, split-layout kernel, and delta machinery see one
    uniform dtype; the native quantized bytes live in the tagged fused
    groups.  Dequantization is bit-exact in f32 for every ladder format.
    """
    if bs.value_format.storage_dtype == "float32":
        return bs
    return dataclasses.replace(
        bs, vals=host_dequantize(bs.vals, bs.value_format), value_format=F32
    )


def requantize_stream(bs: BSCSRMatrix, fmt: ValueFormat) -> BSCSRMatrix:
    """Re-encode a stream's values in another format, structure-preserving.

    Only the value payload changes — flags and cols (and therefore the slot
    structure a mutable index's slot map is aligned with) are untouched, so
    a per-partition format promotion never invalidates delta segments or
    the host-side slot bookkeeping.
    """
    if fmt == bs.value_format:
        return bs
    vals = host_dequantize(bs.vals, bs.value_format)
    return dataclasses.replace(bs, vals=quantize(vals, fmt), value_format=fmt)


INVALID_ROW = np.int32(np.iinfo(np.int32).max)
"""Slot-map entry for a dead candidate slot (sentinel / tombstoned row)."""


def encode_delta_rows(
    rows: Sequence[Tuple[np.ndarray, np.ndarray]],
    n_cols: int,
    block_size: int = 256,
    value_format: ValueFormat | str = "F32",
) -> BSCSRMatrix:
    """Encode appended rows as a delta BS-CSR stream.

    ``rows`` is a sequence of ``(indices, data)`` pairs, one per appended row
    (empty rows are legal and get the placeholder-0 treatment).  The result
    is an ordinary mini stream — same packet layout, same kernel — meant to
    be ``append_packets``-ed after a base segment.  The caller owns the
    mapping from delta-local slot to global row id.
    """
    lens = np.array([len(idx) for idx, _ in rows], dtype=np.int64)
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    if len(rows):
        indices = np.concatenate([np.asarray(i, np.int32) for i, _ in rows])
        data = np.concatenate([np.asarray(d, np.float32) for _, d in rows])
    else:
        indices = np.zeros(0, np.int32)
        data = np.zeros(0, np.float32)
    csr = CSRMatrix(indptr=indptr, indices=indices, data=data,
                    shape=(len(rows), n_cols))
    return encode_bscsr(csr, block_size=block_size, value_format=value_format)


def append_packets(
    base: BSCSRMatrix, delta: BSCSRMatrix, pad_packets_to: Optional[int] = None
) -> BSCSRMatrix:
    """Concatenate a delta segment's packets after ``base`` — no re-encode.

    Stream semantics of the result: the delta's first row-start closes the
    base's open sentinel row, so slot ``base.n_rows`` becomes a dead (empty)
    candidate slot and the delta rows occupy slots ``base.n_rows + 1 ..``.
    ``n_rows`` of the result counts *slots* (base rows + dead sentinel slot +
    delta rows); ``decode_bscsr`` accordingly yields the dead slot as an
    empty row.  ``pad_packets_to`` forwards to :func:`pad_packets`.
    """
    if base.block_size != delta.block_size:
        raise ValueError(
            f"block size mismatch: base {base.block_size}, delta {delta.block_size}"
        )
    if base.value_format != delta.value_format:
        raise ValueError(
            f"value format mismatch: base {base.value_format.name}, "
            f"delta {delta.value_format.name}"
        )
    if base.cols.dtype != delta.cols.dtype:
        raise ValueError("column index dtype mismatch between segments")
    out = BSCSRMatrix(
        vals=np.concatenate([base.vals, delta.vals]),
        cols=np.concatenate([base.cols, delta.cols]),
        flags=np.concatenate([base.flags, delta.flags]),
        n_rows=base.n_rows + 1 + delta.n_rows,
        n_cols=max(base.n_cols, delta.n_cols),
        nnz=base.nnz + delta.nnz,
        block_size=base.block_size,
        value_format=base.value_format,
    )
    if pad_packets_to is not None:
        out = pad_packets(out, pad_packets_to)
    return out


@dataclasses.dataclass
class TombstoneBitmap:
    """Deleted global row ids, as a grow-only host-side bitmap.

    Keyed by global row id: ``mark``-ed ids are masked out of every candidate
    merge (``finalize_candidates``) until the id is resurrected by an upsert.
    The bitmap survives compaction — a deleted id stays unreturnable even
    after its stream bytes have been reclaimed.
    """

    bits: np.ndarray  # (n,) bool

    @classmethod
    def empty(cls, n_rows: int) -> "TombstoneBitmap":
        return cls(bits=np.zeros(max(n_rows, 1), dtype=bool))

    def grow(self, n_rows: int) -> None:
        if n_rows > self.bits.shape[0]:
            self.bits = np.concatenate(
                [self.bits, np.zeros(n_rows - self.bits.shape[0], dtype=bool)]
            )

    def mark(self, row_ids) -> None:
        self.grow(int(np.max(row_ids)) + 1)
        self.bits[np.asarray(row_ids, np.int64)] = True

    def clear(self, row_ids) -> None:
        ids = np.asarray(row_ids, np.int64)
        ids = ids[ids < self.bits.shape[0]]
        self.bits[ids] = False

    def __contains__(self, row_id: int) -> bool:
        return 0 <= row_id < self.bits.shape[0] and bool(self.bits[row_id])

    @property
    def count(self) -> int:
        return int(self.bits.sum())


def decode_bscsr(bs: BSCSRMatrix) -> CSRMatrix:
    """Stream -> CSR (host; exercises the row-recovery semantics in tests)."""
    from repro.core.quantization import dequantize  # local to avoid jnp at import

    flags = unpack_bits(bs.flags, bs.block_size).reshape(-1)
    vals = np.asarray(dequantize(bs.vals.reshape(-1), bs.value_format))
    cols = bs.cols.reshape(-1).astype(np.int64)
    row_ids = np.cumsum(flags) - 1
    keep = row_ids < bs.n_rows  # drop sentinel + padding
    vals, cols, row_ids = vals[keep], cols[keep], row_ids[keep]
    # Drop placeholder zeros that were inserted for empty rows.
    real = vals != 0.0
    counts = np.bincount(row_ids[real], minlength=bs.n_rows)
    indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    return CSRMatrix(
        indptr=indptr,
        indices=cols[real].astype(np.int32),
        data=vals[real].astype(np.float32),
        shape=(bs.n_rows, bs.n_cols),
    )


# ---------------------------------------------------------------------------
# Capacity / operational-intensity model (paper §IV-C packet equation + Fig. 6)
# ---------------------------------------------------------------------------

def fpga_packet_capacity(m: int, value_bits: int, packet_bits: int = 512) -> int:
    """The paper's B from  B*(ceil(log2 B) + ceil(log2 M) + V) + 1 = packet_bits."""
    idx_bits = math.ceil(math.log2(max(m, 2)))
    best = 1
    for b in range(1, packet_bits):
        if b * (math.ceil(math.log2(b)) if b > 1 else 1) >= packet_bits:
            break
        used = b * ((math.ceil(math.log2(b)) if b > 1 else 1) + idx_bits + value_bits) + 1
        if used <= packet_bits:
            best = b
    return best


def stream_bytes_per_nnz(
    value_format: ValueFormat | str, n_cols: int, block_size: int = 256
) -> float:
    """Exact bytes moved from HBM per non-zero with our tile-packet layout."""
    fmt = FORMATS[value_format] if isinstance(value_format, str) else value_format
    col_bytes = col_index_dtype(n_cols).itemsize
    flag_bytes = 1.0 / 8.0                      # 1 bit per nnz, bit-packed
    return fmt.bytes_per_value + col_bytes + flag_bytes


def coo_bytes_per_nnz(value_bytes: int = 4) -> float:
    """Naive COO (Fig. 3 baseline): row id + col id + value, 32-bit each."""
    return 4 + 4 + value_bytes


# ---------------------------------------------------------------------------
# Synthetic matrix generation (paper Table III: Uniform and Gamma(3, 4/3))
# ---------------------------------------------------------------------------

def synthetic_embedding_csr(
    n_rows: int,
    n_cols: int,
    mean_nnz_per_row: float,
    distribution: str = "uniform",
    seed: int = 0,
    normalize: bool = True,
) -> CSRMatrix:
    """Random sparse embedding collection matching the paper's evaluation set."""
    rng = np.random.default_rng(seed)
    if distribution == "uniform":
        lens = rng.integers(1, int(2 * mean_nnz_per_row), size=n_rows)
    elif distribution == "gamma":
        # Paper: Gamma(k=3, theta=4/3) scaled to the target mean (left-skewed).
        raw = rng.gamma(shape=3.0, scale=4.0 / 3.0, size=n_rows)
        lens = np.maximum(1, np.round(raw * (mean_nnz_per_row / 4.0))).astype(np.int64)
    else:
        raise ValueError(f"unknown distribution {distribution!r}")
    lens = np.minimum(lens, n_cols)
    nnz = int(lens.sum())
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    indices = np.empty(nnz, dtype=np.int32)
    # Vectorized unique-column sampling per row (sort trick).
    keys = rng.random((n_rows, int(lens.max())))
    order = np.argsort(keys, axis=1)[:, : int(lens.max())]
    for i in range(n_rows):  # unavoidable ragged fill; still fast for test sizes
        indices[indptr[i] : indptr[i + 1]] = np.sort(order[i, : lens[i]])
    data = rng.standard_normal(nnz).astype(np.float32)
    if normalize:  # L2-normalize rows -> dot product == cosine similarity
        sq = np.add.reduceat(data * data, indptr[:-1])
        norms = np.sqrt(np.maximum(sq, 1e-12))
        data = data / np.repeat(norms, lens).astype(np.float32)
    return CSRMatrix(indptr=indptr, indices=indices, data=data, shape=(n_rows, n_cols))


def scale_rows(csr: CSRMatrix, scales: np.ndarray) -> CSRMatrix:
    """Row-wise rescale of a CSR's values (``scales``: one factor per row).

    Models collections whose shards carry systematically different score
    magnitudes (hot vs cold partitions) — the regime where per-partition
    value precision pays: low-magnitude partitions never contend for the
    global top-k, so their values tolerate aggressive quantization.
    """
    scales = np.asarray(scales, np.float32)
    if scales.shape != (csr.shape[0],):
        raise ValueError(f"need one scale per row, got {scales.shape}")
    data = csr.data * np.repeat(scales, np.diff(csr.indptr)).astype(np.float32)
    return dataclasses.replace(csr, data=data)


def sparsify_topm(dense: np.ndarray, m_keep: int, normalize: bool = True) -> CSRMatrix:
    """Magnitude-top-m sparsification of dense embeddings (GloVe stand-in, §V)."""
    n, m = dense.shape
    keep = np.argsort(-np.abs(dense), axis=1)[:, :m_keep]
    keep = np.sort(keep, axis=1)
    data = np.take_along_axis(dense, keep, axis=1).astype(np.float32)
    if normalize:
        norms = np.linalg.norm(data, axis=1, keepdims=True)
        data = data / np.maximum(norms, 1e-12)
    indptr = (np.arange(n + 1) * m_keep).astype(np.int64)
    return CSRMatrix(
        indptr=indptr,
        indices=keep.reshape(-1).astype(np.int32),
        data=data.reshape(-1),
        shape=(n, m),
    )

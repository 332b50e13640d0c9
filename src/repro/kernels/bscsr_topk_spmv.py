"""Pallas TPU kernels for multi-core BS-CSR Top-K SpMV (paper §IV, Alg. 1).

Grid = (cores, steps): grid dim 0 is the paper's "core" (one row-partition per
core, iterated major), dim 1 streams that core's tile-packets in order — the
TPU analogue of one HBM channel feeding one core in max-length bursts.  All
per-core state lives in on-chip scratch, exactly mirroring the FPGA design.

Every stage is written in ops the Mosaic compiler lowers (no 1-D gather,
cumsum, scatter, ``top_k`` or dynamic vector indexing), so the SAME body
compiles for the chip and runs under the Pallas interpreter in the CPU tests.
One grid step holds ``E = T*B`` stream entries as a ``(1, E)`` lane row in
natural stream order:

  stage 1  decode the packet tile (shift/mask, see ``bscsr.fuse_words``), then
           gather x with one-hot matmuls on the MXU (``gather_mode=
           "onehot"``; ``"take"`` is an interpret-only reference gather)
           and multiply.  The top-k kernels gather over the query block's
           set columns only: the wrapper compacts the split query to them
           (``query_cols``, found by ``set_columns``) in chunks of
           ``CHUNK`` = 1024 columns; each core copies the chunks the block
           uses into VMEM at its first step, with their ids transposed to
           columns, and each step compares those ids with its entries'
           columns, (CHUNK, E) per chunk.  The chunk count is data, read
           from SMEM, so every union size runs on one compiled signature.
           A query at most CHUNK wide is one chunk of its own width,
           compared with an iota: the paper's 512 dense columns gather as
           before.  The accumulate kernel gathers over all columns, (M, E)
           against an iota.
  stage 2  segment ids are the prefix count of the row-start flags, computed
           as a matmul with a triangular 0/1 matrix; per-segment sums are a
           matmul with the one-hot (segment x entry) matrix, (S, E).  Its
           segment space S is the static ``seg_slots``: the stream's most
           row starts in any one step, plus segment 0 (the row carried in),
           rounded up to 128 lanes and capped at ``seg_slot_cap(E)``, the
           one-row-per-entry size.  ``ops.PackedPartitions.seg_slots``
           derives it on the host from the flags that are pinned; ``None``
           takes the cap.  A segment id >= S would be dropped without a
           trace, so the bound is checked on the host before the stream is
           served (``ops.check_segment_slots``).
  stage 3  cross-step carry (current row id in SMEM, per-query partial sum —
           the paper's ``new_row`` / ``last_packet_output``), read with masked
           reductions.
  stage 4  top-k scratchpad update: k passes of ``max`` + first-``argmax``
           over the scratchpad and the step's candidates, using iota masks.

Every matmul of stages 1-2 has an exact 0/1 matrix on one side, built in
bf16, and runs as ONE bf16 MXU pass with an f32 result: the other operand is
split three ways (``_split3``: ``hi + mid + lo == a``, 8 significant bits of
the f32's 24 each), the pieces stacked as rows, and the three row blocks of
the result added back.  Each product of a piece and a 0/1 entry is exact, so
where an output has one nonzero term (the gather, the pick at each segment
end, the accumulate kernel's placement) it is what ``precision=HIGHEST``
(Mosaic's multi-pass fp32 contract precision) gives, bit for bit; the prefix
and one-hot segment sums differ from it only in f32 summation order.  The
query block is split once per call, outside the grid; the products in every
step.

``inner_loop`` selects the stage-2 and stage-4 variants:

  inner_loop = "linear"       prefix-difference sums + gated k-pass (default)
               "legacy"       one-hot sums            + k-pass every step
               "linear-seg"   prefix-difference sums + k-pass every step
               "linear-topk"  one-hot sums            + gated k-pass

The prefix-difference sum takes the inclusive prefix sum of the products
(triangular matmul), picks it at each segment's last entry (one-hot matmul)
and first-differences it; the one-hot sum adds each segment directly.  They
differ only in float summation order.  The gated k-pass skips the update when
no candidate beats the running k-th value (the paper's scratchpad admission
test, §IV-B); the scratchpad stays sorted, so gated and ungated results are
bit-identical.  Ties go to the scratchpad entry, then to the lower row id.

The kernel never writes row scores to HBM: per core only k (value, row) pairs
leave the chip, which is the paper's key bandwidth argument (§III-A).

Stream layouts (``stream_layout``):

  "split"   vals / cols / flags as three BlockSpec streams per grid step.
  "fused"   one contiguous int32 word stream per core (``bscsr.fuse_stream``:
            ``flags | cols | vals`` per packet — the TPU analogue of the
            paper's single 512-bit HBM transaction), decoded bit-exactly, so
            fused results are bit-identical to split.

Streams enter as ``(C, steps, T, W)`` with the core and step block dims
squeezed, so every block's trailing two dims equal the array's; outputs are
``(C, Q, k)`` (top-k) or ``(C, 1, n_rows)`` (accumulate).

Out-of-range col ids (padding/sentinel entries carry whatever the encoder or
a corrupted segment left) gather 0: the one-hot row is all zero, or meets
only the zero padding of the compacted query.

Scratch-shape analysis for padded (bucketed) slot counts
--------------------------------------------------------

A churn-stable mutable index (``TopKSpMVConfig.churn_stable``) pads the
per-core slot budget — the ``n_rows`` static arg below — and the padded
packet count to power-of-two buckets so serve-while-ingest reuses one
compiled signature.  Padding a *slot count* is hazardous in general: a slot
that exists only as padding has no non-zeros, so any naive materialization
scores it 0.0, and a zero-score phantom admitted to the k-sized stage-4
scratchpad displaces a real candidate whenever the true top-k scores are
negative.  The padding is safe here because phantom slots are only ever
materialized at NEG_INF:

  * in-kernel, a candidate is complete ONLY where the stream carries a
    row-start flag after it, and flag-free padding packets merely extend the
    open trailing sentinel row, which stage 3 never completes — so bucketing
    ``n_rows`` or the packet count adds NO candidates.  The only scratchpad
    entries a padded slot id ever occupies are the stage-4 init sentinels at
    NEG_INF/``n_rows``, below every real candidate (admission is a strict
    ``>``, so a sentinel never beats a NEG_INF-masked candidate either);
  * the jnp reference oracle (``ref.bscsr_topk_ref_stacked``) DOES
    materialize one score per budgeted slot, so it masks slots >= the
    per-core live count to NEG_INF *before* its local top-k;
  * ``finalize_candidates`` masks by the exact traced per-core live-slot
    counts (and maps padded slot-map entries, INVALID_ROW, to sentinels),
    so whatever sentinel candidates either path emits merge identically.

Net: padded and unpadded paths are bit-identical end to end, on every
inner_loop x stream_layout, including all-negative-score matrices —
asserted by ``tests/test_executor.py::TestChurnStable``.

The segment space is sized the same way.  Every intermediate of stages 2-4
that has a segment axis is (·, S): the (S, E) one-hot, the (3Q, S) pick at
segment ends, the stage-3 (Q, S) lanes, the (Q, k + S) k-pass pool, and the
accumulate kernel's (S, S + 128) placement window and ``S + 128`` lanes of
accumulator slack.  Slots past the step's last row start hold no complete
row (``complete`` is false there), so cutting S from ``seg_slot_cap(E)``
(640 at E = 512) to the stream's bound (128 for rows of ~20 entries or
more) removes only slots that are never filled: the per-row sums and picks
are the same sums, and under the default ``linear`` loop the answers are
bit-identical (``tests/test_segment_slots.py``).  A churn-stable index
holds its bound monotone (it grows in steps of 128, and ``compact()``
re-anchors it), so ingest retraces only when a step's row starts cross a
bucket.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantization import (
    STREAM_FORMATS,
    TaggedFormatClass,
    ValueFormat,
)

NEG_INF = float(np.finfo(np.float32).min)
FLAG_WORD_BITS = 32
LANES = 128

CHUNK = 1024         # columns per stage-1 gather chunk
BF16_ROWS = 16       # sublanes of one bf16 VMEM tile
VMEM_BASE = 16 << 20  # Mosaic's default scoped VMEM, raised by the query chunks

INNER_LOOPS = ("linear", "legacy", "linear-seg", "linear-topk")
GATHER_MODES = ("onehot", "take")


def _inner_loop_flags(inner_loop: str) -> Tuple[bool, bool]:
    """-> (prefix-difference stage-2 sums?, gated stage-4 update?)."""
    if inner_loop not in INNER_LOOPS:
        raise ValueError(f"inner_loop must be one of {INNER_LOOPS}, got {inner_loop!r}")
    return (
        inner_loop in ("linear", "linear-seg"),
        inner_loop in ("linear", "linear-topk"),
    )


def _check_gather_mode(gather_mode: str, interpret: bool) -> None:
    if gather_mode not in GATHER_MODES:
        raise ValueError(f"gather_mode must be one of {GATHER_MODES}, got {gather_mode!r}")
    if gather_mode == "take" and not interpret:
        raise ValueError(
            "gather_mode='take' is an interpret-only reference gather (Mosaic "
            "lowers no 1-D gather); compiled kernels gather with 'onehot'"
        )


def seg_slot_cap(e: int) -> int:
    """Segment slots for one row per entry of an E-entry step, plus the
    carried row, in whole lanes: the most any stream can need."""
    return -(-(e + 1) // LANES) * LANES


def segment_slots(max_starts: int, e: int) -> int:
    """The segment space of a stream whose steps hold at most ``max_starts``
    row starts: the starts plus segment 0, in whole lanes, at most
    ``seg_slot_cap(e)``."""
    return min(seg_slot_cap(e), -(-(int(max_starts) + 1) // LANES) * LANES)


def _check_seg_slots(seg_slots, e: int) -> int:
    """-> the step's segment space; ``None`` takes ``seg_slot_cap(e)``."""
    cap = seg_slot_cap(e)
    if seg_slots is None:
        return cap
    if seg_slots <= 0 or seg_slots % LANES or seg_slots > cap:
        raise ValueError(
            f"seg_slots must be a positive multiple of {LANES} at most "
            f"{cap} for {e} entries per step, got {seg_slots}"
        )
    return seg_slots


def _iota(shape, dim: int) -> jnp.ndarray:
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


NN = (((1,), (0,)), ((), ()))      # a (R, K) . b (K, N)
NT = (((1,), (1,)), ((), ()))      # a (R, K) . b (N, K)^T


def _bf16_head(a: jnp.ndarray) -> jnp.ndarray:
    """The top 8 significant bits of f32 ``a``, as an f32 that bf16 holds exactly.

    Masks the f32 bit pattern, which no compiler folds away: XLA may drop an
    f32 -> bf16 -> f32 round trip as excess precision.
    """
    bits = jax.lax.bitcast_convert_type(a, jnp.int32)
    return jax.lax.bitcast_convert_type(jnp.bitwise_and(bits, jnp.int32(-65536)), jnp.float32)


def _split3(a: jnp.ndarray) -> jnp.ndarray:
    """(R, N) f32 -> (3R, N) bf16 rows ``[hi; mid; lo]``, ``hi + mid + lo == a``.

    ``hi`` is ``a`` cut to 8 significant bits and ``mid`` the remainder cut
    to 8 more, so ``lo`` keeps the last 8 of the f32's 24: the split is
    exact for every finite f32 whose pieces stay in bf16's normal range.
    """
    hi = _bf16_head(a)
    r = a - hi
    mid = _bf16_head(r)
    return jnp.concatenate([hi, mid, r - mid], axis=0).astype(jnp.bfloat16)


def _sum3(y: jnp.ndarray) -> jnp.ndarray:
    """(3R, N) f32 results of the ``[hi; mid; lo]`` rows -> (R, N)."""
    r = y.shape[0] // 3
    return (y[:r] + y[r : 2 * r]) + y[2 * r :]


def _dot_split(a3: jnp.ndarray, onehot: jnp.ndarray, dims) -> jnp.ndarray:
    """Split rows ``a3`` (3R, K) against an exact 0/1 bf16 matrix -> (R, N) f32.

    One bf16 MXU pass: every product of a bf16 piece and a 0/1 entry is
    exact, so where an output has a single nonzero term it equals the f32
    operand bit for bit; otherwise only the f32 summation order is the MXU's.
    """
    return _sum3(jax.lax.dot_general(a3, onehot, dims, preferred_element_type=jnp.float32))


# --------------------------------------------------------------------------
# Stage-1 decode: packet tiles (T, ...) -> natural-order (T, B) entries.
# --------------------------------------------------------------------------

def _sub_words(words: jnp.ndarray, bits: int):
    """(T, n) int32 -> the 32/bits sign-extended sub-word planes, low first."""
    return [
        jax.lax.shift_right_arithmetic(
            jax.lax.shift_left(words, jnp.int32(32 - (q + 1) * bits)),
            jnp.int32(32 - bits),
        )
        for q in range(32 // bits)
    ]


def _decode_val_words(vw: jnp.ndarray, fmt: ValueFormat) -> jnp.ndarray:
    """One value section's (T, Wv) int32 words -> (T, B) f32 values.

    Narrow values are planar within the section (``bscsr.fuse_words``):
    plane ``q`` (sub-word ``q`` of every word) holds a contiguous run of
    entries, so concatenating the planes restores stream order.
    """
    if fmt.storage_dtype == "float32":
        return jax.lax.bitcast_convert_type(vw, jnp.float32)
    if fmt.storage_dtype == "bfloat16":      # bf16 = the top half of an f32
        lo = jax.lax.shift_left(vw, jnp.int32(16))
        hi = jnp.bitwise_and(vw, jnp.int32(-65536))
        planes = [jax.lax.bitcast_convert_type(p, jnp.float32) for p in (lo, hi)]
        return jnp.concatenate(planes, axis=1)
    bits = 16 if fmt.storage_dtype == "int16" else 8
    v = jnp.concatenate(_sub_words(vw, bits), axis=1).astype(jnp.float32)
    return v * jnp.float32(fmt.scale)


def _unpack_flags(fw: jnp.ndarray) -> jnp.ndarray:
    """(T, B/32) packed row-start words -> (T, B) int32 {0, 1} bits."""
    shifts = _iota((1, FLAG_WORD_BITS), 1)
    return jnp.concatenate(
        [
            jnp.bitwise_and(jax.lax.shift_right_arithmetic(fw[:, w : w + 1], shifts), 1)
            for w in range(fw.shape[1])
        ],
        axis=1,
    )


def _decode_fused(words_ref, block: int, fmt, col_words: int):
    """One fused tile ref (T, W) -> (v f32, c int32, f int32), each (T, B).

    ``fmt`` may be a :class:`TaggedFormatClass` (mixed-precision snapshots):
    packet rows then lead with one header word carrying the partition's
    format code; where the class has several members sharing a storage width
    (BF16 vs Q15 in the 2-byte class) the value section is decoded each way
    and the header tag selects per packet row at run time.
    """
    tagged = isinstance(fmt, TaggedFormatClass)
    h = 1 if tagged else 0
    wf = block // FLAG_WORD_BITS
    f = _unpack_flags(words_ref[:, h : h + wf])
    cw = words_ref[:, h + wf : h + wf + col_words]
    vw = words_ref[:, h + wf + col_words :]
    c = cw if col_words == block else jnp.concatenate(_sub_words(cw, 16), axis=1)
    members = fmt.member_formats if tagged else (fmt,)
    v = _decode_val_words(vw, members[0])
    if len(members) > 1:
        tag = words_ref[:, 0:1]                  # (T, 1): every row carries it
        for m in members[1:]:
            v = jnp.where(tag == m.code, _decode_val_words(vw, m), v)
    return v, c, f


def _decode_split(vals_ref, cols_ref, flags_ref, fmt: ValueFormat):
    v = vals_ref[...].astype(jnp.float32)
    if fmt.is_fixed_point:
        v = v * jnp.float32(fmt.scale)
    return v, cols_ref[...].astype(jnp.int32), _unpack_flags(flags_ref[...])


def _decode_tile(stream_refs, layout: str, block: int, fmt, col_words: int):
    if layout == "fused":
        return _decode_fused(stream_refs[0], block, fmt, col_words)
    return _decode_split(*stream_refs, fmt)


# --------------------------------------------------------------------------
# Stages 1-3 (shared by every kernel) and the stage-4 k-pass.
# --------------------------------------------------------------------------

def _gather_x(x3: jnp.ndarray, c: jnp.ndarray, gather_mode: str) -> jnp.ndarray:
    """Split x3 (3Q, M) at cols c (1, E) -> x (Q, E); out-of-range ids gather 0.

    The accumulate kernel's gather, and the top-k kernels' where one chunk
    holds the whole query (``_gather_set_columns``).
    """
    m = x3.shape[1]
    if gather_mode == "onehot":
        sel = (_iota((m, c.shape[1]), 0) == c).astype(jnp.bfloat16)  # (M, E)
        return _dot_split(x3, sel, NN)
    oob = (c < 0) | (c >= m)
    xv = jnp.take(x3.astype(jnp.float32), jnp.clip(c[0], 0, m - 1), axis=1)
    return _sum3(jnp.where(oob, 0.0, xv))


def _column(row: jnp.ndarray) -> jnp.ndarray:
    """(1, N) int32 lane row -> (N, 1) column, by one 32-bit 2-D transpose."""
    return jnp.transpose(jnp.broadcast_to(row, (8, row.shape[1])))[:, :1]


def _gather_set_columns(x_chunks, id_cols, n_chunks, c: jnp.ndarray, q: int,
                        gather_mode: str) -> jnp.ndarray:
    """x (Q, E) at cols c (1, E), gathered over the query block's set columns.

    ``x_chunks`` (3Q + pad, n_max * width) holds the split query compacted
    to the block's set columns and ``id_cols`` (n_max, width, 1) their
    column ids (-1 past the last), as columns; only the first ``n_chunks``
    chunks are read.  Chunk j's (width, E) one-hot compares its ids with
    each entry's column, so an entry whose column no query sets, or an
    out-of-range id, gathers 0.  The ids are distinct, so each entry's
    one-hot has at most one 1 across all chunks: the chunk sums add one
    nonzero term to zeros, and the result is the full-width one-hot's, bit
    for bit.  A query at most CHUNK wide is its own chunk (``_compact_query``
    leaves it whole), gathered by ``_gather_x``.
    """
    n_max, chunk, _ = id_cols.shape
    rows = x_chunks.shape[0]
    e = c.shape[1]
    if n_max == 1:
        return _gather_x(x_chunks[: 3 * q, :], c, gather_mode)
    if gather_mode == "onehot":

        def add_chunk(j, acc):
            sel = (id_cols[j] == c).astype(jnp.bfloat16)               # (width, E)
            xj = x_chunks[:, pl.ds(pl.multiple_of(j * chunk, chunk), chunk)]
            return acc + jax.lax.dot_general(xj, sel, NN, preferred_element_type=jnp.float32)

        acc = jax.lax.fori_loop(0, n_chunks, add_chunk, jnp.zeros((rows, e), jnp.float32))
        return _sum3(acc[: 3 * q])
    ids = id_cols[...].reshape(n_max * chunk)
    xs = x_chunks[: 3 * q, :].astype(jnp.float32)
    hit = (ids[:, None] == c[0][None, :]) & (_iota((n_max * chunk, 1), 0) < n_chunks * chunk)
    xv = jnp.take(xs, jnp.argmax(hit, axis=0), axis=1)
    return _sum3(jnp.where(jnp.any(hit, axis=0)[None, :], xv, 0.0))


def _step_candidates(xg, v, f, carry_row, carry_sum, *, prefix_sums, seg_slots):
    """Stages 1-3 of one grid step, for the gathered query values ``xg`` (Q, E).

    Returns the step's candidates in segment space — ``cand_v`` (Q, S),
    ``cand_r`` (1, S) slot ids and ``complete`` (1, S), S = ``seg_slots`` —
    plus the slot id of segment 0, and advances the carry scratch.  Segment
    0 continues the row left open by the previous step; the step's last
    segment stays open.  S must exceed the step's row starts.
    """
    e = v.shape[0] * v.shape[1]
    v, f = v.reshape(1, e), f.reshape(1, e)

    # ---- stage 1: multiply the gathered x ----
    prods = v * xg                                                    # (Q, E)

    # ---- stage 2: segment ids (prefix count of flags) and segment sums ----
    tri = (_iota((e, e), 0) <= _iota((e, e), 1)).astype(jnp.bfloat16)  # i <= j
    seg = jnp.dot(
        f.astype(jnp.bfloat16), tri, preferred_element_type=jnp.float32,  # exact 0/1
    ).astype(jnp.int32)                                               # (1, E)
    onehot = (_iota((seg_slots, e), 0) == seg).astype(jnp.bfloat16)       # (S, E)
    if prefix_sums:
        ps = _dot_split(_split3(prods), tri, NN)
        is_last = jnp.concatenate([f[:, 1:], jnp.ones((1, 1), f.dtype)], axis=1) == 1
        ends = _dot_split(_split3(jnp.where(is_last, ps, 0.0)), onehot, NT)  # prefix at each end
        prev = jnp.concatenate([jnp.zeros((ends.shape[0], 1), jnp.float32), ends[:, :-1]], axis=1)
        seg_sums = ends - prev
    else:
        seg_sums = _dot_split(_split3(prods), onehot, NT)             # (Q, S)

    # ---- stage 3: cross-step carry (paper's new_row / last_packet_output) ----
    s_last = jnp.sum(f)                          # id of the step's open segment
    sid = _iota((1, seg_slots), 1)
    row0 = carry_row[0]
    part = carry_sum[...]                                             # (Q, 1)
    cand_v = seg_sums + jnp.where(sid == 0, part, 0.0)
    cand_r = row0 + sid
    complete = (sid < s_last) & (cand_r >= 0)
    carry_row[0] = row0 + s_last
    carry_sum[...] = (
        jnp.sum(jnp.where(sid == s_last, seg_sums, 0.0), axis=1, keepdims=True)
        + jnp.where(s_last == 0, part, 0.0)
    )
    return cand_v, cand_r, complete, row0


def _kpass(pool_v: jnp.ndarray, pool_r: jnp.ndarray, k: int):
    """k passes of max + first-argmax over (Q, P) pools -> sorted (Q, k)."""
    q = pool_v.shape[0]
    lane = _iota(pool_v.shape, 1)
    slot = _iota((q, k), 1)
    out_v = jnp.full((q, k), NEG_INF, jnp.float32)
    out_r = jnp.zeros((q, k), jnp.int32)
    for j in range(k):       # unrolled; k is small (paper uses k = 8)
        mv = jnp.max(pool_v, axis=1, keepdims=True)
        first = jnp.min(jnp.where(pool_v == mv, lane, pool_v.shape[1]), axis=1, keepdims=True)
        hit = lane == first
        mr = jnp.sum(jnp.where(hit, pool_r, 0), axis=1, keepdims=True)
        out_v = jnp.where(slot == j, mv, out_v)
        out_r = jnp.where(slot == j, mr, out_r)
        pool_v = jnp.where(hit, -jnp.inf, pool_v)  # taken: below every sentinel
    return out_v, out_r


# --------------------------------------------------------------------------
# Kernels.
# --------------------------------------------------------------------------

def _topk_kernel(
    nch_ref,          # (1,) SMEM: chunks of the block's set columns to read
    ids_ref,          # (n_max, width) int32 set-column ids, -1 past the last
    x_hbm,            # (3Q + pad, n_max * width) bf16 compacted split query
    *refs,            # stream refs (1 fused or 3 split), outputs topv/topr
                      # (Q, k), scratch x_vmem like x_hbm (URAM
                      # analogue), id_cols (n_max, width, 1) the ids as
                      # columns, acc_v/acc_r (Q, k), carry_row (1,) SMEM,
                      # carry_sum (Q, 1) VMEM
    n_streams: int,
    k: int,
    n_rows: int,
    num_steps: int,
    fmt,
    gather_mode: str,
    inner_loop: str,
    stream_layout: str,
    block: int,
    col_words: int,
    seg_slots: int,
):
    streams = refs[:n_streams]
    topv_ref, topr_ref, x_vmem, id_cols, acc_v, acc_r, carry_row, carry_sum = (
        refs[n_streams:])
    prefix_sums, gated = _inner_loop_flags(inner_loop)
    step = pl.program_id(1)
    q = acc_v.shape[0]
    n_chunks = nch_ref[0]

    # -- per-core reset (each grid-dim-0 core owns an independent partition) --
    @pl.when(step == 0)
    def _init():
        acc_v[...] = jnp.full((q, k), NEG_INF, jnp.float32)
        acc_r[...] = jnp.full((q, k), n_rows, jnp.int32)
        carry_row[0] = -1
        carry_sum[...] = jnp.zeros((q, 1), jnp.float32)

        if ids_ref.shape[0] == 1:    # the query's own chunk, against an iota
            pltpu.sync_copy(x_hbm, x_vmem)
        else:

            def load(j, carry):      # only the chunks the block sets reach VMEM
                cols = pl.ds(pl.multiple_of(j * CHUNK, CHUNK), CHUNK)
                pltpu.sync_copy(x_hbm.at[:, cols], x_vmem.at[:, cols])
                id_cols[j] = _column(ids_ref[pl.ds(j, 1), :])
                return carry

            jax.lax.fori_loop(0, n_chunks, load, 0)

    v, c, f = _decode_tile(streams, stream_layout, block, fmt, col_words)
    c = c.reshape(1, -1)
    xg = _gather_set_columns(x_vmem, id_cols, n_chunks, c, q, gather_mode)
    cand_v, cand_r, complete, _ = _step_candidates(
        xg, v, f, carry_row, carry_sum, prefix_sums=prefix_sums, seg_slots=seg_slots,
    )
    cand_v = jnp.where(complete, cand_v, NEG_INF)

    # ---- stage 4: top-k scratchpad update ----
    def _update():
        pool_v = jnp.concatenate([acc_v[...], cand_v], axis=1)
        pool_r = jnp.concatenate([acc_r[...], jnp.broadcast_to(cand_r, cand_v.shape)], axis=1)
        acc_v[...], acc_r[...] = _kpass(pool_v, pool_r, k)

    if gated:   # admission test: skip unless a candidate beats the k-th value
        thr = jnp.min(acc_v[...], axis=1, keepdims=True)
        pl.when(jnp.any(cand_v > thr))(_update)
    else:
        _update()

    # ---- emit the core's k candidates on its final step ----
    @pl.when(step == num_steps - 1)
    def _emit():
        topv_ref[...] = acc_v[...]
        topr_ref[...] = acc_r[...]


# Accumulate mode (beyond-paper): y = A @ x without the top-k select stage.
#
# Iterative graph workloads (PPR, power-iteration eigensolvers) run the SAME
# packet stream but keep every row's score: stages 1-3 are identical, and
# stage 4's scratchpad is replaced by a dense per-core accumulator of one f32
# per slot.  A step's completed rows are the consecutive slots row0 .. row0 +
# s_last - 1, so one masked one-hot matmul places the step's segment sums at
# their lane offset inside a 128-aligned window of the accumulator.  Each row
# completes exactly once, so the add never mixes two rows.  The open trailing
# sentinel row never completes, so padding packets and bucketed slot budgets
# add nothing.  alpha/beta scaling, tombstone masking and the slot->global-row
# scatter live in the jnp epilogue (``ops.scatter_slot_sums``).

def _accum_kernel(
    x_ref,            # (3, M) bf16 split query
    *refs,            # stream refs, output y (1, n_rows), scratch y_acc
                      # (1, L) VMEM, carry_row (1,) SMEM, carry_sum (1, 1) VMEM
    n_streams: int,
    n_rows: int,
    num_steps: int,
    fmt,
    gather_mode: str,
    inner_loop: str,
    stream_layout: str,
    block: int,
    col_words: int,
    seg_slots: int,
):
    streams = refs[:n_streams]
    y_ref, y_acc, carry_row, carry_sum = refs[n_streams:]
    prefix_sums, _ = _inner_loop_flags(inner_loop)  # stage 4 has no variants here
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        y_acc[...] = jnp.zeros(y_acc.shape, jnp.float32)
        carry_row[0] = -1
        carry_sum[...] = jnp.zeros(carry_sum.shape, jnp.float32)

    v, c, f = _decode_tile(streams, stream_layout, block, fmt, col_words)
    cand_v, _, complete, row0 = _step_candidates(
        _gather_x(x_ref[...], c.reshape(1, -1), gather_mode), v, f,
        carry_row, carry_sum, prefix_sums=prefix_sums, seg_slots=seg_slots,
    )
    # ---- stage 4': place segment s at slot row0 + s (row0 >= -1) ----
    s_pad = cand_v.shape[1]
    width = s_pad + LANES
    base = (jnp.maximum(row0, 0) // LANES) * LANES
    place = _iota((s_pad, width), 1) == _iota((s_pad, width), 0) + (row0 - base)
    window = _dot_split(
        _split3(jnp.where(complete, cand_v, 0.0)), place.astype(jnp.bfloat16), NN,
    )
    base = pl.multiple_of(base, LANES)
    y_acc[:, pl.ds(base, width)] += window

    @pl.when(step == num_steps - 1)
    def _emit():
        y_ref[...] = y_acc[:, :n_rows]


# --------------------------------------------------------------------------
# Wrappers.
# --------------------------------------------------------------------------

def _fused_geometry(width: int, block: int, fmt) -> int:
    """Validate a fused stream width and return its col-section word count.

    Tagged classes budget one extra header word per packet row.
    """
    wf = block // FLAG_WORD_BITS
    wv = block * int(fmt.bytes_per_value) // 4
    header = 1 if isinstance(fmt, TaggedFormatClass) else 0
    col_words = width - header - wf - wv
    if col_words not in (block // 2, block):
        raise ValueError(
            f"fused stream width {width} inconsistent with block={block}, "
            f"fmt={fmt.name}: col section would be {col_words} words"
        )
    return col_words


def _stream_operands(vals, cols, flags, *, fmt_name, stream_layout, block_size,
                     packets_per_step, gather_mode, interpret, seg_slots):
    """Validate the knobs; -> (static kernel kwargs, 4-D streams, specs, grid)."""
    fmt = STREAM_FORMATS[fmt_name]
    if isinstance(fmt, TaggedFormatClass) and stream_layout != "fused":
        raise ValueError(
            f"tagged format class {fmt_name!r} requires stream_layout='fused'"
        )
    _check_gather_mode(gather_mode, interpret)
    n_cores, n_packets, last = vals.shape
    if stream_layout == "fused":
        if block_size is None:
            raise ValueError("stream_layout='fused' requires block_size")
        block = block_size
        col_words = _fused_geometry(last, block, fmt)
        streams = (vals,)
    elif stream_layout == "split":
        block, col_words = last, 0
        streams = (vals, cols, flags)
    else:
        raise ValueError(f"stream_layout must be 'split' or 'fused', got {stream_layout!r}")
    t = packets_per_step
    if n_packets % t:
        raise ValueError(
            f"packet count {n_packets} is not a multiple of packets_per_step={t}"
        )
    num_steps = n_packets // t
    streams = tuple(s.reshape(n_cores, num_steps, t, s.shape[-1]) for s in streams)
    specs = [
        pl.BlockSpec((None, None, t, s.shape[-1]), lambda c, i: (c, i, 0, 0))
        for s in streams
    ]
    static = dict(
        n_streams=len(streams), num_steps=num_steps, fmt=fmt,
        gather_mode=gather_mode, stream_layout=stream_layout, block=block,
        col_words=col_words, seg_slots=_check_seg_slots(seg_slots, t * block),
    )
    return static, streams, specs, (n_cores, num_steps)


def chunk_capacity(n_cols: int) -> int:
    """Gather chunks that hold every column of an ``n_cols``-wide query."""
    return -(-n_cols // CHUNK)


def set_columns(xs: np.ndarray) -> Tuple[Optional[np.ndarray], int]:
    """A (Q, M) host query block -> (its set columns, their count).

    The columns are those some query of the block sets (any nonzero), in
    ascending order and padded with -1 to ``chunk_capacity(M) * CHUNK``: the
    ``query_cols`` that ``bscsr_topk_spmv_multiquery`` takes; None where one
    chunk holds the whole query, which the kernel gathers whole.
    """
    used = np.flatnonzero(np.any(np.asarray(xs) != 0, axis=0)).astype(np.int32)
    n_max = chunk_capacity(xs.shape[1])
    if n_max == 1:
        return None, int(used.size)
    cols = np.full(n_max * CHUNK, -1, np.int32)
    cols[: used.size] = used
    return cols, int(used.size)


def gather_width(n_set: int, n_cols: int) -> int:
    """Columns the stage-1 gather covers for ``n_set`` set columns of ``n_cols``."""
    if chunk_capacity(n_cols) == 1:
        return n_cols
    return max(1, -(-n_set // CHUNK)) * CHUNK


def _compact_query(x: jnp.ndarray, query_cols):
    """(Q, M) f32 -> (chunk count (1,), ids (n_max, width), x (3Q + pad,
    n_max * width) bf16): the split query at its set columns, in chunks.

    A query at most ``CHUNK`` wide is one chunk of its own width, whole:
    compaction cannot narrow it.  Wider ones are compacted to ``query_cols``
    (``None`` takes every column) in chunks of ``CHUNK``, padded with id -1
    and zeros; at least one chunk is read, so a block that sets no column
    gathers its first, all-zero chunk.  The rows are padded to whole bf16
    tiles, for the chunk copies.
    """
    q, m = x.shape
    n_max = chunk_capacity(m)
    if query_cols is not None and query_cols.shape != (n_max * CHUNK,):
        raise ValueError(
            f"query_cols must have shape {(n_max * CHUNK,)} for {m} columns, "
            f"got {query_cols.shape}"
        )
    if n_max == 1:
        n_chunks = jnp.ones((1,), jnp.int32)
        ids = jnp.arange(m, dtype=jnp.int32)[None, :]
        x3 = _split3(x)
    else:
        lanes = jnp.arange(n_max * CHUNK, dtype=jnp.int32)
        if query_cols is None:
            query_cols = jnp.where(lanes < m, lanes, -1)
        query_cols = jnp.asarray(query_cols, jnp.int32)
        live = query_cols >= 0
        n_chunks = jnp.maximum(1, (jnp.sum(live, dtype=jnp.int32) + CHUNK - 1) // CHUNK)
        n_chunks = n_chunks.reshape(1)
        ids = query_cols.reshape(n_max, CHUNK)
        x3 = _split3(jnp.where(live[None, :], jnp.take(x, jnp.clip(query_cols, 0, m - 1), axis=1), 0.0))
    pad = -x3.shape[0] % BF16_ROWS
    x3 = jnp.concatenate([x3, jnp.zeros((pad, x3.shape[1]), x3.dtype)], axis=0)
    return n_chunks, ids, x3


_TOPK_STATICS = (
    "k", "n_rows", "packets_per_step", "fmt_name", "gather_mode",
    "inner_loop", "stream_layout", "block_size", "seg_slots", "interpret",
)


@functools.partial(jax.jit, static_argnames=_TOPK_STATICS)
def bscsr_topk_spmv_multiquery(
    x: jnp.ndarray,        # (Q, M) float32 query batch
    vals: jnp.ndarray,     # split: (C, P, B) storage dtype; fused: (C, P, W) i32
    cols: jnp.ndarray = None,   # (C, P, B) int16/int32 (split only)
    flags: jnp.ndarray = None,  # (C, P, B//32) int32   (split only)
    query_cols: jnp.ndarray = None,  # (n_max * CHUNK,) int32, see set_columns
    *,
    k: int,
    n_rows: int,           # per-core slot budget (uniform; may be a bucketed
                           # pad of the live count — see the scratch-shape
                           # analysis in the module docstring)
    interpret: bool,
    packets_per_step: int = 2,
    fmt_name: str = "F32",
    gather_mode: str = "onehot",
    inner_loop: str = "linear",
    stream_layout: str = "split",
    block_size: int = None,  # required for "fused" (W hides B); ignored otherwise
    seg_slots: int = None,   # stage-2 segment space; None: seg_slot_cap(E)
):
    """Q queries share one stream pass; returns per-core (vals, rows), (C, Q, k).

    With ``stream_layout="fused"`` pass the ``bscsr.fuse_stream`` word array
    as ``vals`` (``cols``/``flags`` stay ``None``).  ``fmt_name`` may also
    name a tagged width class (``TAG4``/``TAG2``/``TAG1``) for one group of a
    mixed-precision snapshot — fused layout only.  ``interpret`` is required:
    ``False`` compiles with Mosaic, ``True`` runs the Pallas interpreter.

    ``query_cols`` lists the columns the block's queries set (``set_columns``
    finds them on the host); a column left out must be 0 in every query.
    It is data: every column count runs on one compiled signature, and the
    gather reads only the chunks that hold them.  ``None`` takes all columns.

    ``seg_slots`` sizes stage 2's segment space; it must exceed every
    step's row starts (``ops.PackedPartitions.seg_slots`` derives it from
    the flags, ``ops.check_segment_slots`` checks it).  ``None`` takes one
    slot per entry, which every stream fits.
    """
    static, streams, specs, grid = _stream_operands(
        vals, cols, flags, fmt_name=fmt_name, stream_layout=stream_layout,
        block_size=block_size, packets_per_step=packets_per_step,
        gather_mode=gather_mode, interpret=interpret, seg_slots=seg_slots,
    )
    q = x.shape[0]
    # constant over the grid: compacted and split once per call
    n_chunks, ids, x3 = _compact_query(x.astype(jnp.float32), query_cols)
    kernel = functools.partial(
        _topk_kernel, k=k, n_rows=n_rows, inner_loop=inner_loop, **static
    )
    out_spec = pl.BlockSpec((None, q, k), lambda c, i: (c, 0, 0))
    # the chunks, and their ids as (width, 1) columns, whole (8, 128) tiles each
    chunk_bytes = x3.size * x3.dtype.itemsize + ids.size * LANES * 4
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(ids.shape, lambda c, i: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            *specs,
        ],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((grid[0], q, k), jnp.float32),
            jax.ShapeDtypeStruct((grid[0], q, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM(x3.shape, jnp.bfloat16),
            pltpu.VMEM(ids.shape + (1,), jnp.int32),
            pltpu.VMEM((q, k), jnp.float32),
            pltpu.VMEM((q, k), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BASE + chunk_bytes),
        interpret=interpret,
        name="bscsr_topk_spmv_multiquery",
    )(n_chunks, ids, x3, *streams)


@functools.partial(jax.jit, static_argnames=_TOPK_STATICS)
def bscsr_topk_spmv(
    x: jnp.ndarray,        # (M,) float32 query embedding
    vals: jnp.ndarray,
    cols: jnp.ndarray = None,
    flags: jnp.ndarray = None,
    query_cols: jnp.ndarray = None,
    *,
    k: int,
    n_rows: int,
    interpret: bool,
    packets_per_step: int = 2,
    fmt_name: str = "F32",
    gather_mode: str = "onehot",
    inner_loop: str = "linear",
    stream_layout: str = "split",
    block_size: int = None,
    seg_slots: int = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One query: the multi-query kernel at Q=1; per-core (vals, rows), (C, k)."""
    v, r = bscsr_topk_spmv_multiquery(
        x[None, :], vals, cols, flags, query_cols, k=k, n_rows=n_rows, interpret=interpret,
        packets_per_step=packets_per_step, fmt_name=fmt_name,
        gather_mode=gather_mode, inner_loop=inner_loop,
        stream_layout=stream_layout, block_size=block_size, seg_slots=seg_slots,
    )
    return v[:, 0], r[:, 0]


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_rows", "packets_per_step", "fmt_name", "gather_mode",
        "inner_loop", "stream_layout", "block_size", "seg_slots", "interpret",
    ),
)
def bscsr_spmv(
    x: jnp.ndarray,        # (M,) float32
    vals: jnp.ndarray,     # split: (C, P, B) storage dtype; fused: (C, P, W) i32
    cols: jnp.ndarray = None,   # (C, P, B) int16/int32 (split only)
    flags: jnp.ndarray = None,  # (C, P, B//32) int32   (split only)
    *,
    n_rows: int,           # per-core slot budget (may be a bucketed pad)
    interpret: bool,
    packets_per_step: int = 2,
    fmt_name: str = "F32",
    gather_mode: str = "onehot",
    inner_loop: str = "linear",
    stream_layout: str = "split",
    block_size: int = None,
    seg_slots: int = None,   # stage-2 segment space, as for the top-k kernel
) -> jnp.ndarray:
    """Accumulate-mode kernel pass: per-core dense slot sums, (C, n_rows) f32.

    This is ``select_topk=False``: the top-k scratchpad never runs and every
    slot's full row sum leaves the kernel.  ``inner_loop`` still selects the
    stage-2 segmented-sum variant; the stage-4 half of each mode is vacuous
    here.  Callers map slots to global rows, mask tombstones, and apply
    alpha/beta via ``ops.scatter_slot_sums`` — `finalize_candidates` must NOT
    run on this output.
    """
    static, streams, specs, grid = _stream_operands(
        vals, cols, flags, fmt_name=fmt_name, stream_layout=stream_layout,
        block_size=block_size, packets_per_step=packets_per_step,
        gather_mode=gather_mode, interpret=interpret, seg_slots=seg_slots,
    )
    acc_len = -(-(n_rows + 1) // LANES) * LANES + static["seg_slots"] + LANES
    kernel = functools.partial(
        _accum_kernel, n_rows=n_rows, inner_loop=inner_loop, **static
    )
    x3 = _split3(x[None, :].astype(jnp.float32))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(x3.shape, lambda c, i: (0, 0)), *specs],
        out_specs=pl.BlockSpec((None, 1, n_rows), lambda c, i: (c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0], 1, n_rows), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((1, acc_len), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
        name="bscsr_spmv",
    )(x3, *streams)[:, 0]

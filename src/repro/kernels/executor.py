"""Device-resident snapshot plane: pin streams once, dispatch with zero copies.

The BS-CSR stream is laid out once and then *streamed* — that is the paper's
whole bandwidth argument — yet a naive dispatch re-uploads the packed index
host->device on every query call (``jnp.asarray`` per stream per call).  This
module is the layer between the host snapshot containers and the kernels that
makes the steady-state query path transfer-free:

    host plane                      device plane                 compiled plane
    ----------                      ------------                 --------------
    PackedPartitions --pin once--> DeviceSnapshot ---args---> jitted query fn
    (numpy arrays;     per (uid,    (jnp arrays: kernel  ^     (kernel + final
     COW stacked        layout)     streams + finalize   |      merge fused in
     views)                         arrays)              |      ONE jit; cached
        |                               |                |      per shape sig,
     mutation                        evicted when the ---+      config knobs
        v                            host snapshot is           and Q-bucket)
    new PackedPartitions (uid') ---> fresh DeviceSnapshot       garbage collected

* ``DeviceSnapshot`` pins one immutable ``PackedPartitions``'s kernel streams
  (fused words, or split vals/cols/flags) plus the finalize arrays
  (row_starts, candidate slots, slot_to_row, tombstones) on device exactly
  once, keyed by the snapshot's ``uid`` (+ stream layout).  The cache entry
  dies with the host snapshot (``weakref.finalize``), so a mutable index
  bumping its version naturally invalidates the device copy.
* ``QueryExecutor`` caches end-to-end jitted query functions — Pallas kernel
  (or the jnp reference oracle) and ``finalize_candidates`` fused into ONE
  jit — per (path, Q-bucket, shape signature).  Batched queries are padded up
  to power-of-two Q buckets so a drifting batch size does not retrace.

Steady state, a query dispatch is two dict hits and one compiled call with
arrays already on device: **zero** host->device transfers, asserted by the
``jax.transfer_guard("disallow")`` regression test in
``tests/test_executor.py``.  This is the TPU-serving analogue of Serpens /
the streaming-SpMV FPGA designs keeping the sparse stream resident in HBM
next to the compute units across queries.

Churn-stable signatures: "steady state" includes *serve-while-ingest*.  A
mutable-index refresh grows the id space, but a churn-stable index
(``TopKSpMVConfig.churn_stable``, default) pads the churn-varying dims —
tombstone bitmap length, slot-map width (= the per-core slot budget) and
padded packet count — to power-of-two buckets, and this module passes the
row-id sentinel as a device-pinned *traced* scalar instead of baking it into
the trace.  The first query after an upsert then re-pins the new snapshot
(one host->device upload of the changed arrays) but reuses the already
compiled query fn: ZERO retraces until a bucket doubles (``retraces``
counter in ``cache_info``; asserted over upsert->query cycles in
``tests/test_executor.py``).  The padding is answer-preserving — the kernel
scratch analysis lives in ``bscsr_topk_spmv.py``'s docstring, and the
negative-score parity tests prove bit-identity against the unpadded path.
Stale compiled fns are still evicted (``_evict_stale``) so a non-bucketed
or compact()-reshaped working set cannot leak executables.

See docs/SERVING.md for the full dispatch lifecycle and cache-key reference,
and docs/ARCHITECTURE.md for the end-to-end data path.
"""
from __future__ import annotations

import functools
import weakref
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults as faults_lib
from repro.core.quantization import FORMATS
from repro.kernels import ops
from repro.kernels import ref as ref_lib
from repro.kernels.bscsr_topk_spmv import (
    bscsr_spmv,
    bscsr_topk_spmv,
    bscsr_topk_spmv_multiquery,
    gather_width,
)
from repro.kernels.bscsr_topk_spmv import set_columns as find_set_columns
from repro.utils.tracing import span

# (snapshot uid, stream layout, row-map key, device, packets per step) ->
# DeviceSnapshot; entries evicted when the host PackedPartitions is garbage
# collected.
_DEVICE_CACHE: dict = {}


def device_cache_size() -> int:
    return len(_DEVICE_CACHE)


def clear_device_cache() -> None:
    _DEVICE_CACHE.clear()


def evict_snapshot(uid) -> int:
    """Drop every device pin of snapshot ``uid``; returns entries evicted.

    Shard-failover recovery path: after a dispatch failure the host
    ``PackedPartitions`` is still good, but its device copies are suspect —
    evicting forces the next ``device_snapshot`` call to re-place fresh
    device arrays from the host copy.
    """
    stale = [k for k in _DEVICE_CACHE if k[0] == uid]
    for k in stale:
        _DEVICE_CACHE.pop(k, None)
    return len(stale)


class DeviceSnapshot:
    """Device-pinned arrays of one immutable ``PackedPartitions`` snapshot.

    ``args`` is the positional device-array tail every compiled query fn
    takes after the query itself; ``signature`` keys the jit cache (shapes,
    dtypes and static geometry — two snapshots with equal signatures can
    share one compiled fn without retracing).  ``seg_slots`` is the
    kernels' segment space for steps of ``packets_per_step`` packets
    (``PackedPartitions.seg_slots``), checked against the stream before
    anything is pinned.
    """

    __slots__ = (
        "uid", "stream_layout", "streams", "row_starts", "rows_per_part",
        "slot_to_row", "tombstones", "row_map", "args", "signature",
        "max_slots", "seg_slots", "n_rows_logical", "n_rows_sentinel",
        "sentinel_index", "block_size", "fmt_name", "groups_meta",
        "num_cores",
    )

    def __init__(
        self,
        packed: ops.PackedPartitions,
        stream_layout: str,
        row_map=None,
        device=None,
        packets_per_step: int = 2,
    ):
        self.uid = packed.uid
        self.stream_layout = stream_layout
        self.seg_slots = packed.seg_slots(packets_per_step)
        ops.check_segment_slots(packed, packets_per_step, self.seg_slots)
        if device is not None:
            # Pin on a specific device (the sharded plane places each shard
            # on its mesh column).  jax.default_device keeps jnp.array's
            # copy semantics — device buffers must never alias host COW
            # buffers — while committing the arrays there.
            with jax.default_device(device):
                self._pin_arrays(packed, stream_layout, row_map)
            return
        self._pin_arrays(packed, stream_layout, row_map)

    def _pin_arrays(self, packed, stream_layout, row_map):
        # Mixed-precision snapshots pin one tagged word array PER width
        # class; ``groups_meta`` (class name + core indices, static) tells
        # the compiled fn how to dispatch and scatter them.
        self.groups_meta = None
        # jnp.array (copy=True): device buffers must not alias host COW
        # buffers that a later refresh may recycle.
        if stream_layout == "fused" and packed.groups is not None:
            self.streams = tuple(jnp.array(g.words) for g in packed.groups)
            self.groups_meta = tuple(
                (g.class_name, g.cores) for g in packed.groups
            )
        elif stream_layout == "fused":
            self.streams = (jnp.array(packed.fused_words()),)
        else:
            self.streams = (
                jnp.array(packed.vals),
                jnp.array(packed.cols),
                jnp.array(packed.flags),
            )
        self.num_cores = packed.num_cores
        self.row_starts = jnp.array(packed.row_starts)
        self.rows_per_part = jnp.array(packed.candidate_slots)
        self.slot_to_row = (
            jnp.array(packed.slot_to_row)
            if packed.slot_to_row is not None else None
        )
        # The tombstone bitmap is shipped whenever the snapshot CARRIES one
        # (mutable indexes always do, bucket-padded with False), not only
        # when a bit is set: the first delete must flip a traced value, not
        # the compiled signature.  Pure-base snapshots (None) stay free.
        self.tombstones = (
            jnp.array(packed.tombstones)
            if packed.tombstones is not None else None
        )
        # The sharded plane's local->global id translation rides the snapshot
        # as one more pinned device array (same lifecycle as the streams).
        self.row_map = jnp.array(row_map) if row_map is not None else None
        self.max_slots = packed.max_slots
        self.n_rows_logical = packed.n_rows_logical
        # The row-id sentinel is a device-pinned TRACED scalar: the id space
        # grows with every upsert, and baking it into the trace would force
        # a retrace per refresh no matter how well the shapes are bucketed.
        self.n_rows_sentinel = jnp.asarray(packed.n_rows_logical, jnp.int32)
        self.sentinel_index = len(self.streams) + 2
        self.block_size = packed.block_size
        self.fmt_name = packed.value_format.name
        args = list(self.streams) + [
            self.row_starts, self.rows_per_part, self.n_rows_sentinel,
        ]
        if self.slot_to_row is not None:
            args.append(self.slot_to_row)
        if self.tombstones is not None:
            args.append(self.tombstones)
        if self.row_map is not None:
            args.append(self.row_map)
        self.args = tuple(args)
        self.signature = (
            stream_layout,
            tuple((a.shape, str(a.dtype)) for a in self.args),
            self.slot_to_row is not None,
            self.tombstones is not None,
            self.row_map is not None,
            self.max_slots, self.seg_slots, self.block_size,
            self.fmt_name,
            # Mixed precision: the per-partition format-code vector and the
            # width-class grouping are part of the compiled signature — a
            # format reassignment is a REAL retrace and the ``retraces``
            # counter must see it, while an unchanged assignment reuses the
            # compiled fn bit-for-bit across upsert->query cycles.
            packed.fmt_signature,
            self.groups_meta,
        )

    def call_args(self, n_rows_override=None) -> tuple:
        """``args`` with the traced row-id sentinel optionally swapped out.

        The sharded plane serves a shard-local snapshot against the
        *collection's* (growing) id space: the override is another pinned
        traced scalar, so swapping it neither retraces nor uploads.
        """
        if n_rows_override is None:
            return self.args
        i = self.sentinel_index
        return self.args[:i] + (n_rows_override,) + self.args[i + 1:]


def device_snapshot(
    packed: ops.PackedPartitions,
    stream_layout: Optional[str] = None,
    row_map=None,
    row_map_key=None,
    device=None,
    packets_per_step: int = 2,
) -> DeviceSnapshot:
    """The device-pinned form of ``packed``, uploading at most once per uid.

    ``row_map``/``row_map_key`` pin a local->global id translation alongside
    the snapshot (the key distinguishes pins of the same snapshot with and
    without a map — a given ``row_map_key`` must always name the same map
    contents for a given uid).  ``device`` commits the pin to a specific
    device instead of the process default.  ``packets_per_step`` is the
    grid step the kernels will run, which fixes the segment space.
    """
    layout = stream_layout or packed.stream_layout
    key = (packed.uid, layout, row_map_key, device, packets_per_step)
    snap = _DEVICE_CACHE.get(key)
    if snap is None:
        with span("executor.pin") as s:
            snap = DeviceSnapshot(packed, layout, row_map=row_map, device=device,
                                  packets_per_step=packets_per_step)
            s.set_metadata(bytes=sum(int(a.nbytes) for a in snap.args),
                           seg_slots=snap.seg_slots)
        _DEVICE_CACHE[key] = snap
        weakref.finalize(packed, _DEVICE_CACHE.pop, key, None)
    return snap


def _compile_on_first_call(fn: Callable, **ids) -> Callable:
    """``fn`` whose first call (trace + compile) runs in ``executor.compile``."""
    compiled = False

    def call(*args):
        nonlocal compiled
        if compiled:
            return fn(*args)
        with span("executor.compile", **ids):
            out = fn(*args)
        compiled = True
        return out

    return call


def _q_bucket(q: int) -> int:
    """Next power-of-two batch bucket, so drifting Q reuses compiled fns."""
    return 1 << max(q - 1, 0).bit_length()


@functools.lru_cache(maxsize=None)
def _query_padder(pad: int):
    """Tiny jitted pad-to-bucket step; the zero rows never leave the device."""

    @jax.jit
    def pad_fn(xs):
        return jnp.concatenate(
            [xs, jnp.zeros((pad, xs.shape[1]), xs.dtype)], axis=0
        )

    return pad_fn


@functools.lru_cache(maxsize=None)
def _query_unpadder(q: int):
    """Jitted bucket->Q un-pad: an eager ``[:q]`` would ship its index scalar
    host->device per call, breaking the zero-transfer steady state."""

    @jax.jit
    def unpad_fn(vals, rows):
        return vals[:q], rows[:q]

    return unpad_fn


class QueryExecutor:
    """Compiled end-to-end query dispatch over device-resident snapshots.

    One executor per set of query knobs (big_k, k, T, gather, inner loop,
    interpret) — ``get_executor`` interns them process-wide.  ``query`` /
    ``query_batched`` accept any snapshot (immutable or a mutable index's
    current ``packed``): the device pin is per snapshot uid, the compiled fn
    per shape signature, so steady-state dispatch is two dict hits and one
    compiled call.  ``path="reference"`` runs the jnp oracle instead of the
    Pallas kernel through the same plane (same zero-transfer property).
    """

    def __init__(
        self,
        big_k: int,
        k: int = 8,
        packets_per_step: int = 2,
        gather_mode: str = "auto",
        inner_loop: str = "linear",
        interpret: Optional[bool] = None,
        q_bucketing: bool = True,
    ):
        self.big_k = big_k
        self.k = k
        self.packets_per_step = packets_per_step
        self.gather_mode = ops.resolve_gather_mode(gather_mode)
        self.inner_loop = inner_loop
        self.interpret = ops.resolve_interpret(interpret)
        self.q_bucketing = q_bucketing
        self._fns: dict = {}
        self._pinned: set = set()  # device-cache keys this executor touched
        self._last_sig: dict = {}  # (path, q) -> signature it last compiled
        self.fn_builds = 0
        self.dispatches = 0
        # Builds caused by a (path, Q) pair CHANGING signature — i.e. genuine
        # churn-triggered recompiles, as opposed to first-touch compiles.
        # With churn-stable snapshot bucketing this stays 0 across upserts
        # until a bucket doubles.
        self.retraces = 0
        # Batched dispatches that reused an already-compiled fn, split by
        # HOW they hit: ``q_bucket_hits`` = the batch was padded up to a
        # power-of-two bucket compiled for a different Q (the micro-batching
        # frontend's drifting batch sizes live here), ``q_exact_hits`` = the
        # batch size was already a compiled bucket.  Together with
        # ``retraces`` these let tests assert drifting Q stays retrace-free
        # without parsing ``fn_builds``.
        self.q_bucket_hits = 0
        self.q_exact_hits = 0
        # Stage-1 gather width (columns: chunks x CHUNK, or the query's own
        # width when one chunk holds it) -> dispatches, for the blocks whose
        # set columns ``set_columns`` found.
        self.gather_widths: dict = {}

    # -- dispatch ------------------------------------------------------------

    def prepare(
        self,
        packed: ops.PackedPartitions,
        q: Optional[int] = None,
        path: str = "kernel",
        stream_layout: Optional[str] = None,
        row_map=None,
        row_map_key=None,
        device=None,
    ):
        """Resolve (compiled fn, device snapshot) without running.

        This IS the per-query dispatch overhead: a steady-state ``query`` is
        ``prepare`` plus the compiled call.  ``q=None`` selects the
        single-query fn; otherwise the (padded) batch size — or, for the
        accumulate paths, the ``("spmv", n_out)`` static-output key.
        """
        if path in ("reference", "accumulate_ref"):
            layout = "split"  # the oracles read the split arrays
        else:
            layout = stream_layout or packed.stream_layout
        snap = device_snapshot(
            packed, layout,
            row_map=row_map, row_map_key=row_map_key, device=device,
            packets_per_step=self.packets_per_step,
        )
        pin = (snap.uid, layout, row_map_key, device, self.packets_per_step)
        if pin not in self._pinned:
            # A new pin means a snapshot refresh: drop dead pins now.  The
            # zero-retrace steady state never misses the fn cache, so
            # _evict_stale alone would let this set grow by one dead tuple
            # per upsert forever.
            self._pinned &= set(_DEVICE_CACHE.keys())
            self._pinned.add(pin)
        key = (path, q, snap.signature)
        fn = self._fns.get(key)
        if fn is None:
            live = self._evict_stale()    # misses mark a shifting working set
            prev = self._last_sig.get((path, q))
            # A retrace is churn: this pair's previous signature is DEAD
            # (its snapshots were replaced and collected).  A build while
            # the previous signature still serves live snapshots is just a
            # first touch for another collection sharing this interned
            # executor — not a churn signal.
            retrace = (prev is not None and prev != snap.signature
                       and prev not in live)
            ids = dict(path=path, q=q if isinstance(q, int) else str(q))
            with span("executor.build", retrace=int(retrace), **ids):
                fn = _compile_on_first_call(self._build(path, q, snap), **ids)
            self._fns[key] = fn
            self.fn_builds += 1
            self.retraces += retrace
            self._last_sig[(path, q)] = snap.signature
        return fn, snap

    def _evict_stale(self) -> set:
        """Drop compiled fns (and pin records) for dead snapshot signatures.

        Under non-bucketed serve-while-ingest churn almost every snapshot
        version has a distinct shape signature (slot map width, tombstone
        length and the per-core slot count all grow with the id space), so
        without eviction a long-lived interned executor would accumulate
        one compiled executable per version ever served.  Signatures still
        live in the device cache are kept — shape-sharing snapshots reuse
        their fns.  Returns the live-signature set (the caller's retrace
        accounting reuses it).
        """
        # list()/set() first: GC-driven weakref.finalize callbacks pop cache
        # entries and must not race the iteration
        live = {s.signature for s in list(_DEVICE_CACHE.values())}
        self._fns = {k: f for k, f in self._fns.items() if k[2] in live}
        self._pinned &= set(_DEVICE_CACHE.keys())
        return live

    def set_columns(self, xs: np.ndarray) -> Optional[np.ndarray]:
        """The set columns of a host (Q, M) query block: ``query_cols``.

        Found on the host before the block's upload, so the gather width is
        known without waiting for the device; the width is data to the
        compiled fn, so a block of any union size reuses it.  None where
        one gather chunk holds the whole query.
        """
        with span("executor.columns", q=int(xs.shape[0])) as s:
            cols, n_set = find_set_columns(xs)
            width = gather_width(n_set, xs.shape[1])
            s.set_metadata(cols=n_set, width=width)
        self.gather_widths[width] = self.gather_widths.get(width, 0) + 1
        return cols

    def query(
        self,
        x: jnp.ndarray,
        packed: ops.PackedPartitions,
        path: str = "kernel",
        stream_layout: Optional[str] = None,
        row_map=None,
        row_map_key=None,
        device=None,
        n_rows=None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Top-``big_k`` (values, global rows) for one (M,) query."""
        fn, snap = self.prepare(
            packed, None, path, stream_layout,
            row_map=row_map, row_map_key=row_map_key, device=device,
        )
        self.dispatches += 1
        return fn(x, None, *snap.call_args(n_rows))

    def query_batched(
        self,
        xs: jnp.ndarray,
        packed: ops.PackedPartitions,
        path: str = "kernel",
        stream_layout: Optional[str] = None,
        row_map=None,
        row_map_key=None,
        device=None,
        n_rows=None,
        query_cols=None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(Q, big_k) answers for a (Q, M) batch, one pass over the stream.

        ``query_cols`` (from ``set_columns``) narrows the kernel's gather to
        the block's set columns; ``None`` gathers over every column.
        """
        xs = jnp.asarray(xs)
        if xs.ndim != 2 or xs.shape[0] == 0:
            raise ValueError(
                f"xs must be a non-empty (Q, M) batch, got {xs.shape}"
            )
        q = xs.shape[0]
        bucket = _q_bucket(q) if self.q_bucketing else q
        with span("executor.dispatch", q=q, bucket=bucket):
            builds_before = self.fn_builds
            fn, snap = self.prepare(
                packed, bucket, path, stream_layout,
                row_map=row_map, row_map_key=row_map_key, device=device,
            )
            if self.fn_builds == builds_before:  # reused a compiled fn
                if bucket != q:
                    self.q_bucket_hits += 1      # padded into a shared bucket
                else:
                    self.q_exact_hits += 1
            self.dispatches += 1
            if bucket != q:
                xs = _query_padder(bucket - q)(xs)
            vals, rows = fn(xs, query_cols, *snap.call_args(n_rows))
            return _query_unpadder(q)(vals, rows) if bucket != q else (vals, rows)

    def spmv(
        self,
        x: jnp.ndarray,
        packed: ops.PackedPartitions,
        *,
        alpha: jnp.ndarray,
        beta: jnp.ndarray,
        y: jnp.ndarray,
        path: str = "accumulate",
        stream_layout: Optional[str] = None,
        row_map=None,
        row_map_key=None,
        device=None,
    ) -> jnp.ndarray:
        """``alpha * A @ x + beta * y`` with the top-k select stage skipped.

        The iterative-workload dispatch: one compiled call per step, with the
        dense output vector (and ``x``/``alpha``/``beta``, when the caller
        pins them) device-resident between iterations — zero host round-trips
        per step once warm.  ``y``'s (static) length fixes the output row
        space and is part of the fn cache key; ``finalize_candidates`` never
        runs on this path (masking lives in ``ops.scatter_slot_sums``).
        ``path="accumulate_ref"`` runs the jnp oracle through the same plane.
        """
        n_out = int(y.shape[0])
        fn, snap = self.prepare(
            packed, ("spmv", n_out), path, stream_layout,
            row_map=row_map, row_map_key=row_map_key, device=device,
        )
        self.dispatches += 1
        return fn(x, alpha, beta, y, *snap.call_args())

    def cache_info(self) -> dict:
        # prune dead pins so the count (and this set) track live pins only;
        # set() snapshots the keys against concurrent finalize-driven pops
        self._pinned &= set(_DEVICE_CACHE.keys())
        seg_slots: dict = {}
        for key in list(self._pinned):
            snap = _DEVICE_CACHE.get(key)
            if snap is not None:
                seg_slots[snap.seg_slots] = seg_slots.get(snap.seg_slots, 0) + 1
        return {
            "compiled_fns": len(self._fns),
            "fn_builds": self.fn_builds,
            "retraces": self.retraces,                  # churn-driven rebuilds
            "dispatches": self.dispatches,
            "q_bucket_hits": self.q_bucket_hits,        # padded-batch fn reuse
            "q_exact_hits": self.q_exact_hits,          # exact-bucket fn reuse
            "device_snapshots": len(self._pinned),      # this executor's pins
            "device_snapshots_process_wide": device_cache_size(),
            "interpret": self.interpret,                # False: Mosaic-compiled
            "gather_mode": self.gather_mode,
            "gather_widths": dict(sorted(self.gather_widths.items())),
            # stage-2 segment space -> this executor's live pins holding it
            "seg_slots": dict(sorted(seg_slots.items())),
            "paths": sorted({key[0] for key in self._fns}),  # compiled fn paths
        }

    # -- compilation ---------------------------------------------------------

    def _build(self, path: str, q: Optional[int], snap: DeviceSnapshot):
        """One jitted end-to-end query fn for this (path, Q, signature)."""
        layout = snap.stream_layout
        n_streams = len(snap.streams)
        has_slot = snap.slot_to_row is not None
        has_tomb = snap.tombstones is not None
        has_map = snap.row_map is not None
        fmt = FORMATS[snap.fmt_name]
        big_k, k = self.big_k, self.k
        max_slots = snap.max_slots
        seg_slots = snap.seg_slots

        def split_args(arrs):
            streams = arrs[:n_streams]
            row_starts, rows_per = arrs[n_streams], arrs[n_streams + 1]
            n_rows = arrs[n_streams + 2]     # traced row-id sentinel scalar
            i = n_streams + 3
            slot_to_row = arrs[i] if has_slot else None
            i += 1 if has_slot else 0
            tombstones = arrs[i] if has_tomb else None
            i += 1 if has_tomb else 0
            row_map = arrs[i] if has_map else None
            return (streams, row_starts, rows_per, n_rows, slot_to_row,
                    tombstones, row_map)

        if path == "reference":

            def run(x, query_cols, *arrs):
                streams, row_starts, rows_per, n_rows, slot, tombs, rmap = (
                    split_args(arrs)
                )
                vals, cols, flags = streams

                def one(xi):
                    lv, lr = ref_lib.bscsr_topk_ref_stacked(
                        vals, cols, flags, jnp.asarray(xi, jnp.float32),
                        rows_per, max_slots, k, fmt,
                    )
                    return ops.finalize_candidates(
                        lv, lr, row_starts, rows_per, big_k, n_rows,
                        slot_to_row=slot, tombstones=tombs, row_map=rmap,
                    )

                if q is None:
                    return one(x)
                return jax.vmap(one)(jnp.asarray(x, jnp.float32))

        elif path == "kernel":
            kernel = bscsr_topk_spmv if q is None else bscsr_topk_spmv_multiquery
            kwargs = dict(
                k=k, n_rows=max_slots,
                packets_per_step=self.packets_per_step,
                fmt_name=snap.fmt_name, gather_mode=self.gather_mode,
                inner_loop=self.inner_loop, stream_layout=layout,
                block_size=snap.block_size, seg_slots=seg_slots,
                interpret=self.interpret,
            )

            if snap.groups_meta is not None:
                # Mixed precision: one kernel call per width class over its
                # tagged word array, candidates scattered back to (C,[Q,]k)
                # core order before the shared finalize.  Class names and
                # core index vectors are static (baked into the trace).
                num_cores = snap.num_cores

                def run(x, query_cols, *arrs):
                    streams, row_starts, rows_per, n_rows, slot, tombs, rmap = (
                        split_args(arrs)
                    )
                    xq = jnp.asarray(x, jnp.float32)
                    shape = (
                        (num_cores, k) if q is None else (num_cores, q, k)
                    )
                    lv = jnp.full(shape, ops.NEG_INF, jnp.float32)
                    lr = jnp.full(shape, max_slots, jnp.int32)
                    for (cname, cores), words in zip(
                        snap.groups_meta, streams
                    ):
                        gv, gr = kernel(
                            xq, words, query_cols=query_cols,
                            **dict(kwargs, fmt_name=cname),
                        )
                        idx = jnp.asarray(list(cores), jnp.int32)
                        lv = lv.at[idx].set(gv)
                        lr = lr.at[idx].set(gr)
                    finalize = (
                        ops.finalize_candidates if q is None
                        else ops.finalize_candidates_batched
                    )
                    return finalize(
                        lv, lr, row_starts, rows_per, big_k, n_rows,
                        slot_to_row=slot, tombstones=tombs, row_map=rmap,
                    )

            else:

                def run(x, query_cols, *arrs):
                    streams, row_starts, rows_per, n_rows, slot, tombs, rmap = (
                        split_args(arrs)
                    )
                    lv, lr = kernel(
                        jnp.asarray(x, jnp.float32), *streams,
                        query_cols=query_cols, **kwargs,
                    )
                    finalize = (
                        ops.finalize_candidates if q is None
                        else ops.finalize_candidates_batched
                    )
                    return finalize(
                        lv, lr, row_starts, rows_per, big_k, n_rows,
                        slot_to_row=slot, tombstones=tombs, row_map=rmap,
                    )

        elif path in ("accumulate", "accumulate_ref"):
            # q is the ("spmv", n_out) key: the dense output length is static
            # (it shapes the scatter), everything else — x, alpha, beta, y and
            # the snapshot tail — is traced, so warm iterations neither
            # retrace nor transfer.  finalize_candidates NEVER runs here.
            _, n_out = q
            if path == "accumulate_ref":

                def run(x, alpha, beta, y, *arrs):
                    streams, row_starts, rows_per, n_rows, slot, tombs, rmap = (
                        split_args(arrs)
                    )
                    vals, cols, flags = streams
                    sums = ref_lib.bscsr_slot_sums_stacked(
                        vals, cols, flags, jnp.asarray(x, jnp.float32),
                        max_slots, fmt,
                    )
                    ax = ops.scatter_slot_sums(
                        sums, row_starts, rows_per, n_out,
                        slot_to_row=slot, tombstones=tombs, row_map=rmap,
                    )
                    return alpha * ax + beta * y

            else:
                kwargs = dict(
                    n_rows=max_slots,
                    packets_per_step=self.packets_per_step,
                    fmt_name=snap.fmt_name, gather_mode=self.gather_mode,
                    inner_loop=self.inner_loop, stream_layout=layout,
                    block_size=snap.block_size, seg_slots=seg_slots,
                    interpret=self.interpret,
                )
                if snap.groups_meta is not None:
                    num_cores = snap.num_cores

                    def run(x, alpha, beta, y, *arrs):
                        (streams, row_starts, rows_per, n_rows, slot, tombs,
                         rmap) = split_args(arrs)
                        xq = jnp.asarray(x, jnp.float32)
                        sums = jnp.zeros((num_cores, max_slots), jnp.float32)
                        for (cname, cores), words in zip(
                            snap.groups_meta, streams
                        ):
                            gs = bscsr_spmv(
                                xq, words, **dict(kwargs, fmt_name=cname)
                            )
                            idx = jnp.asarray(list(cores), jnp.int32)
                            sums = sums.at[idx].set(gs)
                        ax = ops.scatter_slot_sums(
                            sums, row_starts, rows_per, n_out,
                            slot_to_row=slot, tombstones=tombs, row_map=rmap,
                        )
                        return alpha * ax + beta * y

                else:

                    def run(x, alpha, beta, y, *arrs):
                        (streams, row_starts, rows_per, n_rows, slot, tombs,
                         rmap) = split_args(arrs)
                        sums = bscsr_spmv(
                            jnp.asarray(x, jnp.float32), *streams, **kwargs
                        )
                        ax = ops.scatter_slot_sums(
                            sums, row_starts, rows_per, n_out,
                            slot_to_row=slot, tombstones=tombs, row_map=rmap,
                        )
                        return alpha * ax + beta * y

        else:
            raise ValueError(
                "path must be 'kernel', 'reference', 'accumulate' or "
                f"'accumulate_ref', got {path!r}"
            )

        return jax.jit(run)


class ShardedDeviceBundle:
    """Per-shard host blocks pinned per mesh column, assembled into global
    sharded ``jax.Array``s — the multi-device analogue of the device pin.

    Each *family* (one named array the sharded query fn takes — word streams,
    slot maps, live-slot counts, tombstone bitmaps, id maps) is a list of
    per-shard host blocks stacked along a leading shard dim.  ``sync`` ships
    shard ``s``'s block to every device in its mesh column (all replicas) ONLY
    when that shard's version changed, and — when per-partition mutation
    stamps are provided and the block shape is unchanged — ships only the
    *dirty partitions* via an in-place device scatter (the COW stamp
    machinery already knows which ones).  Steady-state queries then dispatch
    against the cached assembled arrays with zero host->device transfers.

    Shipped-byte accounting is per shard (``shard_uploads`` /
    ``shard_bytes``) plus global counters; ``dispatch_info()`` surfaces them.
    """

    def __init__(self, mesh, shard_axis: str = "shard"):
        self.mesh = mesh
        self.shard_axis = shard_axis
        self.n_shards = int(mesh.shape[shard_axis])
        self._fams: dict = {}
        self.uploads = 0
        self.host_bytes_shipped = 0
        self.partitions_shipped = 0
        self.shard_uploads = [0] * self.n_shards
        self.shard_bytes = [0] * self.n_shards

    def _count(self, s: Optional[int], nbytes: int) -> None:
        self.uploads += 1
        self.host_bytes_shipped += int(nbytes)
        if s is not None:
            self.shard_uploads[s] += 1
            self.shard_bytes[s] += int(nbytes)

    def _sharded_spec(self):
        return jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(self.shard_axis)
        )

    def _replicated_spec(self):
        return jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec()
        )

    def _device_blocks(self, sharding, gshape) -> dict:
        """device -> shard block index along the leading dim."""
        out = {}
        for d, idx in sharding.addressable_devices_indices_map(gshape).items():
            sl = idx[0]
            out[d] = 0 if sl.start is None else int(sl.start)
        return out

    def _assemble(self, fam) -> jax.Array:
        return jax.make_array_from_single_device_arrays(
            fam["gshape"], fam["sharding"],
            [fam["pieces"][d] for d in fam["devmap"]],
        )

    def sync(
        self,
        name: str,
        block_shape: tuple,
        dtype,
        blocks_fn: Callable[[int], np.ndarray],
        versions: Sequence,
        stamps: Optional[Sequence[Optional[np.ndarray]]] = None,
    ) -> jax.Array:
        """The assembled global array for this family, shipping only change.

        ``blocks_fn(s)`` lazily materialises shard ``s``'s host block (only
        called for shards whose version moved).  ``stamps[s]`` (optional)
        enables partition-granular scatter updates along the block's leading
        dim.  A ``block_shape`` change (a common bucket doubled) rebuilds the
        family outright — an O(log growth) event.
        """
        S = self.n_shards
        versions = list(versions)
        gshape = (S,) + tuple(block_shape)
        np_dtype = np.dtype(dtype)
        fam = self._fams.get(name)
        if fam is None or fam["gshape"] != gshape or fam["dtype"] != np_dtype:
            sharding = self._sharded_spec()
            devmap = self._device_blocks(sharding, gshape)
            blocks = [
                np.ascontiguousarray(blocks_fn(s)).astype(np_dtype, copy=False)
                for s in range(S)
            ]
            pieces = {}
            for d, s in devmap.items():
                pieces[d] = jax.device_put(blocks[s][None], d)
                self._count(s, blocks[s].nbytes)
            fam = {
                "gshape": gshape, "dtype": np_dtype, "sharding": sharding,
                "devmap": devmap, "pieces": pieces, "versions": versions,
                "stamps": [
                    None if stamps is None or stamps[s] is None
                    else np.array(stamps[s])
                    for s in range(S)
                ],
            }
            fam["global"] = self._assemble(fam)
            self._fams[name] = fam
            return fam["global"]

        changed = False
        for s in range(S):
            if fam["versions"][s] == versions[s]:
                continue
            blk = np.ascontiguousarray(blocks_fn(s)).astype(
                np_dtype, copy=False
            )
            # A crash past this point leaves this shard's version marker
            # unmoved (it only advances after every device piece is placed),
            # so the next sync re-ships the shard — device pieces are
            # replaced functionally, never mutated, making re-ship safe.
            faults_lib.fault_point("bundle.scatter")
            st_old = fam["stamps"][s]
            st_new = (
                None if stamps is None or stamps[s] is None
                else np.asarray(stamps[s])
            )
            dirty = None
            if (st_old is not None and st_new is not None
                    and st_old.shape == st_new.shape):
                dirty = np.nonzero(st_new != st_old)[0]
            if dirty is not None and dirty.size == 0:
                pass  # version moved but every partition's bytes are current
            elif (dirty is not None
                    and dirty.size <= max(1, blk.shape[0] // 2)):
                rows = np.ascontiguousarray(blk[dirty])
                nb = ops.pow2_bucket(int(dirty.size))
                if nb != dirty.size:
                    # Pad the scatter to a power-of-two width by REPEATING
                    # the first dirty index (idempotent: the padded rows
                    # carry that same partition's data), bounding the number
                    # of distinct scatter shapes ever compiled.
                    pad = nb - dirty.size
                    idxp = np.concatenate(
                        [dirty, np.full(pad, dirty[0])]
                    ).astype(np.int32)
                    rows = np.concatenate(
                        [rows, np.repeat(rows[:1], pad, axis=0)]
                    )
                else:
                    idxp = dirty.astype(np.int32)
                for d, sb in fam["devmap"].items():
                    if sb != s:
                        continue
                    di = jax.device_put(idxp, d)
                    dr = jax.device_put(rows, d)
                    fam["pieces"][d] = fam["pieces"][d].at[0, di].set(dr)
                    self._count(s, idxp.nbytes + rows.nbytes)
                self.partitions_shipped += int(dirty.size)
            else:
                for d, sb in fam["devmap"].items():
                    if sb != s:
                        continue
                    fam["pieces"][d] = jax.device_put(blk[None], d)
                    self._count(s, blk.nbytes)
                if dirty is not None:
                    self.partitions_shipped += int(dirty.size)
            fam["versions"][s] = versions[s]
            fam["stamps"][s] = st_new
            changed = True
        if changed:
            fam["global"] = self._assemble(fam)
        return fam["global"]

    def placement(self, name: str) -> dict:
        """device -> shard block held there, for one family (e.g. "words")."""
        return dict(self._fams[name]["devmap"])

    def sync_replicated(self, name: str, value: np.ndarray, version) -> jax.Array:
        """A fully replicated (every device) global array for small metadata
        like the traced global row-id sentinel."""
        value = np.asarray(value)
        fam = self._fams.get(name)
        if (fam is not None and fam["versions"] == [version]
                and fam["gshape"] == value.shape):
            return fam["global"]
        sharding = self._replicated_spec()
        pieces = {}
        for d in self.mesh.devices.flat:
            pieces[d] = jax.device_put(value, d)
            self._count(None, value.nbytes)
        fam = {
            "gshape": value.shape, "dtype": value.dtype,
            "sharding": sharding, "devmap": dict.fromkeys(pieces, -1),
            "pieces": pieces, "versions": [version], "stamps": [],
        }
        fam["global"] = jax.make_array_from_single_device_arrays(
            value.shape, sharding, list(pieces.values())
        )
        self._fams[name] = fam
        return fam["global"]

    def counters(self) -> dict:
        return {
            "uploads": self.uploads,
            "host_bytes_shipped": self.host_bytes_shipped,
            "partitions_shipped": self.partitions_shipped,
            "per_shard": [
                {"uploads": u, "bytes_shipped": b}
                for u, b in zip(self.shard_uploads, self.shard_bytes)
            ],
        }


def get_executor(
    big_k: int,
    k: int = 8,
    packets_per_step: int = 2,
    gather_mode: str = "auto",
    inner_loop: str = "linear",
    interpret: Optional[bool] = None,
) -> QueryExecutor:
    """Process-wide interned executor for one set of query knobs.

    ``gather_mode`` and ``interpret`` are resolved BEFORE interning, so
    ``"auto"``/``None`` and their resolutions share one executor.
    """
    return _interned_executor(
        big_k, k, packets_per_step, ops.resolve_gather_mode(gather_mode),
        inner_loop, ops.resolve_interpret(interpret),
    )


@functools.lru_cache(maxsize=None)
def _interned_executor(
    big_k, k, packets_per_step, gather_mode, inner_loop, interpret
) -> QueryExecutor:
    return QueryExecutor(
        big_k=big_k, k=k, packets_per_step=packets_per_step,
        gather_mode=gather_mode, inner_loop=inner_loop, interpret=interpret,
    )

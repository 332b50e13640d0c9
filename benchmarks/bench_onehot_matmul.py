#!/usr/bin/env python3
"""One grid step's one-hot MXU matmuls of the BS-CSR kernel, timed on a TPU.

    python benchmarks/bench_onehot_matmul.py [--steps 4096] [--repeats 5]

A standalone Pallas kernel runs, in every grid step, the three matmuls of
stages 1-2 of ``bscsr_topk_spmv_multiquery`` at the deployment widths
(Q=64 queries, M=512 columns, E=512 stream entries per step, S=128 segment
slots: the bound of rows that average 20 entries, where one slot per entry
would take 640), with the step's masks built in the kernel from a streamed
column and row-start row, two ways:

  f32_highest  f32 0/1 matrices, every dot at ``precision=HIGHEST`` (Mosaic:
               fp32 contract precision);
  split_bf16   bf16 0/1 matrices against the other operand split three ways
               (``_split3``), one bf16 pass per dot (``_dot_split``).

The three dots are the stage-1 gather ``x @ sel``, the stage-2 prefix sum
``prods @ tri`` and the pick at each segment's end against ``onehot``.  The
last line of stdout is one JSON object: microseconds per step of each
variant (median and least of ``--repeats`` timed calls of ``--steps``
steps), the device, and the largest gap between the two variants' outputs.
Without a TPU it exits non-zero.
"""
from __future__ import annotations

import argparse
import functools
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

from repro.kernels.bscsr_topk_spmv import (  # noqa: E402
    NN,
    NT,
    _dot_split,
    _iota,
    _split3,
)

Q, M, E, S = 64, 512, 512, 128
_HIGHEST = jax.lax.Precision.HIGHEST


def _step(x, v, c, f, split: bool):
    """The three matmuls of one step; -> segment-end prefix sums (Q, S)."""
    dt = jnp.bfloat16 if split else jnp.float32
    sel = (_iota((M, E), 0) == c).astype(dt)
    tri = (_iota((E, E), 0) <= _iota((E, E), 1)).astype(dt)
    seg = jnp.dot(f.astype(jnp.bfloat16), tri.astype(jnp.bfloat16),
                  preferred_element_type=jnp.float32).astype(jnp.int32)
    onehot = (_iota((S, E), 0) == seg).astype(dt)
    is_last = jnp.concatenate([f[:, 1:], jnp.ones((1, 1), f.dtype)], axis=1) == 1
    if split:
        prods = v * _dot_split(x, sel, NN)
        ps = _dot_split(_split3(prods), tri, NN)
        return _dot_split(_split3(jnp.where(is_last, ps, 0.0)), onehot, NT)
    prods = v * jnp.dot(x, sel, precision=_HIGHEST, preferred_element_type=jnp.float32)
    ps = jnp.dot(prods, tri, precision=_HIGHEST, preferred_element_type=jnp.float32)
    return jax.lax.dot_general(jnp.where(is_last, ps, 0.0), onehot, NT,
                               precision=_HIGHEST, preferred_element_type=jnp.float32)


def _kernel(x_ref, v_ref, c_ref, f_ref, o_ref, *, split: bool):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    o_ref[...] += _step(x_ref[...], v_ref[...], c_ref[...], f_ref[...], split)


@functools.partial(jax.jit, static_argnames="split")
def run(x, v, c, f, *, split: bool):
    steps = v.shape[0]
    if split:
        x = _split3(x)
    row = pl.BlockSpec((None, 1, E), lambda i: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, split=split),
        grid=(steps,),
        in_specs=[pl.BlockSpec(x.shape, lambda i: (0, 0)), row, row, row],
        out_specs=pl.BlockSpec((Q, S), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((Q, S), jnp.float32),
        name="onehot_matmul_split" if split else "onehot_matmul_f32",
    )(x, v, c, f)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=4096)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1

    rng = np.random.default_rng(args.seed)
    x = jnp.asarray(rng.standard_normal((Q, M), np.float32))
    v = jnp.asarray(rng.standard_normal((args.steps, 1, E), np.float32))
    c = jnp.asarray(rng.integers(0, M, (args.steps, 1, E), np.int32))
    f = jnp.asarray((rng.random((args.steps, 1, E)) < 1 / 20).astype(np.int32))

    out, us = {}, {}
    for name, split in (("f32_highest", False), ("split_bf16", True)):
        out[name] = run(x, v, c, f, split=split).block_until_ready()   # compile
        samples = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            run(x, v, c, f, split=split).block_until_ready()
            samples.append((time.perf_counter() - t0) / args.steps * 1e6)
        us[name] = {"median": statistics.median(samples), "min": min(samples)}
        print(f"{name}: {us[name]['median']:.4f} us/step (median of {args.repeats})",
              file=sys.stderr)

    gap = float(jnp.max(jnp.abs(out["f32_highest"] - out["split_bf16"])))
    scale = float(jnp.max(jnp.abs(out["f32_highest"])))
    print(json.dumps({
        "us_per_step": us,
        "ratio_f32_over_split": us["f32_highest"]["median"] / us["split_bf16"]["median"],
        "steps": args.steps, "shape": {"Q": Q, "M": M, "E": E, "S": S},
        "max_abs_gap": gap, "max_abs_out": scale,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count()},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

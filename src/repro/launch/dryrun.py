import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks device count on first init.
#
# Multi-pod dry-run: lower + compile every (architecture x shape x mesh) cell
# against the production mesh and record memory / cost / collective analysis
# (the roofline inputs).  No arrays are ever allocated: all inputs are
# ShapeDtypeStructs.
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
#       --mesh both --out experiments/dryrun
#   PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b \
#       --shape train_4k --mesh single

import argparse
import dataclasses
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_NAMES, ALIASES, get_config
from repro.configs.base import SHAPES, TrainConfig, shape_applicable
from repro.launch import analysis
from repro.launch.mesh import make_production_mesh
from repro.models.model_zoo import count_params_analytic, get_model
from repro.sharding.rules import (
    DEFAULT_RULES, ShardingRules, logical_to_spec, shard_params, use_rules,
)
from repro.train import optimizer as opt_lib


def _batch_shardings(api, shape, mesh, rules, spec_tree):
    logical = api.batch_logical(shape)
    out = {}
    for k, v in spec_tree.items():
        if k == "cache" or v is None:
            continue
        dims = tuple(logical.get(k, P()))
        out[k] = NamedSharding(mesh, logical_to_spec(dims, v.shape, mesh, rules))
    return out


def build_cell(cfg, shape, mesh, rules: ShardingRules = DEFAULT_RULES,
               microbatches: int = 1, grad_dtype: str = "float32",
               serve_dtype: str = ""):
    """Returns (jitted_fn, abstract_args) for one dry-run cell.

    ``grad_dtype``: accumulation/reduction dtype for train cells (bf16 halves
    gradient all-reduce traffic against fp32 master weights).
    ``serve_dtype``: if set, prefill/decode cells hold parameters in this
    dtype (serving from a bf16 weight copy: half the weight traffic, and the
    fp32 master stays with the trainer).
    """
    api = get_model(cfg)
    abstract_params = jax.eval_shape(
        lambda: api.init_params(jax.random.key(0), shape.seq_len)
    )
    if serve_dtype and shape.kind != "train":
        sd = jnp.dtype(serve_dtype)
        abstract_params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, sd if s.dtype == jnp.float32 else s.dtype),
            abstract_params,
        )
    param_sh = shard_params(abstract_params, api.param_specs(), mesh, rules)

    if shape.kind == "train":
        tc = TrainConfig(microbatches=microbatches, grad_dtype=grad_dtype)
        step = opt_lib.make_train_step(api.loss_fn, tc)
        abstract_opt = jax.eval_shape(opt_lib.init_opt_state, abstract_params)
        opt_sh = opt_lib.opt_state_specs(param_sh)
        batch = api.batch_spec(shape)
        if microbatches > 1:
            batch = {
                k: jax.ShapeDtypeStruct(
                    (microbatches, v.shape[0] // microbatches) + v.shape[1:],
                    v.dtype)
                for k, v in batch.items()
            }
        batch_sh = _batch_shardings(api, shape, mesh, rules, batch)
        fn = jax.jit(
            step,
            in_shardings=(param_sh, opt_sh, batch_sh),
            out_shardings=(param_sh, opt_sh, NamedSharding(mesh, P())),
            donate_argnums=(0, 1),
        )
        return fn, (abstract_params, abstract_opt, batch)

    if shape.kind == "prefill":
        batch = api.batch_spec(shape)
        batch_sh = _batch_shardings(api, shape, mesh, rules, batch)
        fn = jax.jit(api.prefill, in_shardings=(param_sh, batch_sh))
        return fn, (abstract_params, batch)

    # decode: one new token against a seq_len-deep cache
    cache = api.cache_shape(shape.global_batch, shape.seq_len)
    cache_sh = shard_params(cache, api.cache_specs(), mesh, rules)
    tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    tok_sh = NamedSharding(
        mesh, logical_to_spec(("batch", None), tokens.shape, mesh, rules)
    )
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    fn = jax.jit(
        api.decode_step,
        in_shardings=(param_sh, cache_sh, tok_sh, NamedSharding(mesh, P())),
        donate_argnums=(1,),
    )
    return fn, (abstract_params, cache, tokens, pos)


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS convention: 6*N*D train, 2*N*D prefill, 2*N*B decode
    (N = active params; D = global tokens in the step)."""
    n = count_params_analytic(cfg, active_only=True)
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch


def auto_microbatches(shape, mesh, max_tokens_per_device: int = 16384) -> int:
    """Largest divisor of the per-device batch keeping live activations sane.

    Per-layer saved activations scale with per-microbatch tokens; v5e has
    16 GB/chip, so the production default bounds tokens/device/microbatch.
    """
    if shape.kind != "train":
        return 1
    dp = 1
    for ax in ("pod", "data"):
        dp *= mesh.shape.get(ax, 1)
    b_local = max(shape.global_batch // dp, 1)
    tokens_local = b_local * shape.seq_len
    want = max(1, tokens_local // max_tokens_per_device)
    mb = min(b_local, want)
    while b_local % mb:  # must divide the local batch
        mb -= 1
    return max(mb, 1)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rules: ShardingRules = DEFAULT_RULES,
             rules_label: str = "default",
             microbatches: Optional[int] = None,
             grad_dtype: str = "float32",
             serve_dtype: str = "") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_label = "multi" if multi_pod else "single"
    base = {
        "arch": cfg.name, "shape": shape_name, "mesh": mesh_label,
        "rules": rules_label, "grad_dtype": grad_dtype,
        "serve_dtype": serve_dtype or None,
    }
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {**base, "status": "skip", "reason": why}
    if cfg.sharding_overrides:
        rules = rules.replace(**dict(cfg.sharding_overrides))
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mb = microbatches or auto_microbatches(shape, mesh)
        base["microbatches"] = mb
        with mesh, use_rules(rules):
            fn, args = build_cell(cfg, shape, mesh, rules, microbatches=mb,
                                  grad_dtype=grad_dtype,
                                  serve_dtype=serve_dtype)
            t0 = time.perf_counter()
            lowered = fn.lower(*args)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
            result = analysis.analyze_compiled(
                compiled, chips=mesh.size,
                model_flops=model_flops_for(cfg, shape),
            )
        return {
            **base, "status": "ok",
            "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
            "chips": mesh.size,
            "params": count_params_analytic(cfg),
            "active_params": count_params_analytic(cfg, active_only=True),
            **result,
        }
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        return {**base, "status": "fail", "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:]}


def run_topk_service_cell(multi_pod: bool) -> dict:
    """The paper's own workload on the production mesh (reduced stream size:
    lowering structure is size-independent, HLO just scales by packet count)."""
    import numpy as np

    from repro.configs.topk_spmv import CONFIG
    from repro.core import bscsr as bscsr_lib
    from repro.core import topk_spmv as _unused  # noqa
    import repro.core as core

    mesh_label = "multi" if multi_pod else "single"
    base = {"arch": "topk_spmv_service", "shape": "query", "mesh": mesh_label,
            "rules": "default"}
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        axes = ("pod", "data") if multi_pod else ("data",)
        n_parts = mesh.size // mesh.shape["model"]
        # Structure-preserving reduced stream: same partitions, fewer packets.
        csr = bscsr_lib.synthetic_embedding_csr(
            n_rows=n_parts * 64, n_cols=CONFIG.n_cols,
            mean_nnz_per_row=CONFIG.mean_nnz_per_row, seed=0,
        )
        idx = core.build_index(
            csr,
            core.TopKSpMVConfig(
                big_k=CONFIG.big_k, k=CONFIG.k, num_partitions=n_parts,
                block_size=CONFIG.block_size, value_format="F32",
            ),
        )
        with mesh:
            fn, arrays = core.distributed_topk_spmv_fn(idx, mesh, axes)
            x = jax.ShapeDtypeStruct((CONFIG.n_cols,), jnp.float32)
            abstract = tuple(
                jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrays
            )
            t0 = time.perf_counter()
            lowered = fn.lower(x, *abstract)
            compiled = lowered.compile()
            t1 = time.perf_counter()
            result = analysis.analyze_compiled(compiled, chips=mesh.size)
        return {**base, "status": "ok", "compile_s": round(t1 - t0, 2),
                "chips": mesh.size, **result}
    except Exception as e:  # noqa: BLE001
        return {**base, "status": "fail", "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id, comma list, 'all', or 'topk_spmv'")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="grad-accumulation microbatches for train cells "
                         "(0 = auto: bound tokens/device/microbatch)")
    args = ap.parse_args()

    archs = list(ARCH_NAMES) if args.arch == "all" else [
        ALIASES.get(a, a) for a in args.arch.split(",")
    ]
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    results = []
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                if arch == "topk_spmv":
                    r = run_topk_service_cell(multi)
                else:
                    r = run_cell(arch, shape, multi,
                                 microbatches=args.microbatches or None)
                results.append(r)
                tag = f"{r['arch']}/{r['shape']}/{r['mesh']}"
                if r["status"] == "ok":
                    rf = r["roofline"]
                    m = r.get("memory", {})
                    print(f"     memory_analysis: args="
                          f"{m.get('argument_size_in_bytes', 0)/1e9:.2f}GB "
                          f"temp={m.get('temp_size_in_bytes', 0)/1e9:.2f}GB "
                          f"out={m.get('output_size_in_bytes', 0)/1e9:.2f}GB "
                          f"| cost_analysis(xla): {r.get('cost_xla_raw', {})} "
                          f"| hlo_flops/chip={rf['flops']:.3e}")
                    print(f"OK   {tag:46s} compile={r.get('compile_s', 0):6.1f}s "
                          f"bottleneck={rf['bottleneck']:10s} "
                          f"mem={rf['memory_s']*1e3:8.2f}ms "
                          f"comp={rf['compute_s']*1e3:8.2f}ms "
                          f"coll={rf['collective_s']*1e3:8.2f}ms")
                elif r["status"] == "skip":
                    print(f"SKIP {tag:46s} {r['reason']}")
                else:
                    print(f"FAIL {tag:46s} {r['error'][:120]}")
                fname = f"{r['arch'].replace('/', '_')}_{r['shape']}_{r['mesh']}.json"
                with open(os.path.join(args.out, fname), "w") as f:
                    json.dump(r, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skip" for r in results)
    n_fail = sum(r["status"] == "fail" for r in results)
    print(f"\n{n_ok} ok / {n_skip} skip / {n_fail} fail")
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(results, f, indent=1)
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())


def run_pipeline_cell(arch: str, stages: int = 4, multi_pod: bool = False,
                      pp_microbatches: int = 0) -> dict:
    """PP extension cell: train_4k with the block stack pipelined over a
    'stage' mesh axis — (stage, data, model) = (S, 16, 256/(16*S)) chips.
    PP microbatching happens inside the loss (GPipe ticks)."""
    from repro.train.pipeline import (
        PIPELINE_RULES_OVERRIDE, pipeline_applicable, pipelined_loss_fn,
    )

    cfg = get_config(arch)
    shape = SHAPES["train_4k"]
    base = {"arch": cfg.name, "shape": f"train_4k_pp{stages}",
            "mesh": "multi" if multi_pod else "single", "rules": "pipeline"}
    if not pipeline_applicable(cfg, stages):
        return {**base, "status": "skip", "reason": "not pipeline-applicable"}
    try:
        model_par = (512 if multi_pod else 256) // (16 * stages)
        axes = ("stage", "data", "model")
        mesh_shape = (stages, 16, model_par)
        if multi_pod:
            axes = ("pod",) + axes
            mesh_shape = (2,) + mesh_shape
        mesh = jax.make_mesh(mesh_shape, axes)
        rules = DEFAULT_RULES.replace(**PIPELINE_RULES_OVERRIDE)
        m = pp_microbatches or 4 * stages   # bubble = (S-1)/(M+S-1) ~ 15%
        api = get_model(cfg)
        abstract_params = jax.eval_shape(
            lambda: api.init_params(jax.random.key(0), shape.seq_len))
        with mesh, use_rules(rules):
            param_sh = shard_params(abstract_params, api.param_specs(), mesh,
                                    rules)
            abstract_opt = jax.eval_shape(opt_lib.init_opt_state,
                                          abstract_params)
            opt_sh = opt_lib.opt_state_specs(param_sh)
            tc = TrainConfig(microbatches=1)
            loss = lambda p, b: pipelined_loss_fn(p, cfg, b, mesh, m)
            step = opt_lib.make_train_step(loss, tc)
            batch = api.batch_spec(shape)
            batch_sh = _batch_shardings(api, shape, mesh, rules, batch)
            fn = jax.jit(step, in_shardings=(param_sh, opt_sh, batch_sh),
                         out_shardings=(param_sh, opt_sh,
                                        NamedSharding(mesh, P())),
                         donate_argnums=(0, 1))
            t0 = time.perf_counter()
            compiled = fn.lower(abstract_params, abstract_opt, batch).compile()
            t1 = time.perf_counter()
            result = analysis.analyze_compiled(
                compiled, chips=mesh.size,
                model_flops=model_flops_for(cfg, shape))
        return {**base, "status": "ok", "compile_s": round(t1 - t0, 2),
                "chips": mesh.size, "pp_microbatches": m, **result}
    except Exception as e:  # noqa: BLE001
        return {**base, "status": "fail", "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:]}

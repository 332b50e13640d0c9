"""GPipe-style pipeline parallelism over a 'stage' mesh axis.

Layer-stacked params shard their layer dim across stages (one rule change:
``layers -> "stage"``); activations flow stage-to-stage with
``lax.ppermute`` inside a tick scan (M + S - 1 ticks for M microbatches on
S stages — the classic GPipe schedule with its bubble).  The shard_map is
*manual only over 'stage'* (``axis_names={'stage'}``): data/model axes stay
in GSPMD-auto mode, so FSDP/TP compose with PP unchanged.

Embedding and the LM head run outside the pipeline (data-parallel); only the
transformer blocks are staged.  Dense + MoE-free archs only (MoE dispatch
inside a manual axis needs a bespoke all-to-all; documented limitation).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer


def _stage_shard_map(fn, mesh: Mesh, in_specs, out_specs):
    """shard_map manual over the 'stage' axis only (the rest stay auto)."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names={"stage"}, check_vma=False,
    )


def pipeline_applicable(cfg: ModelConfig, num_stages: int) -> bool:
    return (
        cfg.family in ("dense", "vlm")
        and cfg.num_experts == 0
        and cfg.num_layers % num_stages == 0
    )


def pipelined_loss_fn(
    params: Dict,
    cfg: ModelConfig,
    batch: Dict,
    mesh: Mesh,
    microbatches: int,
) -> jnp.ndarray:
    """Cross-entropy loss with the block stack pipelined over 'stage'."""
    s_stages = mesh.shape["stage"]
    assert pipeline_applicable(cfg, s_stages), "arch not pipeline-applicable"
    tokens, labels = batch["tokens"], batch["labels"]
    b, seq = tokens.shape
    m = microbatches
    assert b % m == 0, "global batch must divide into microbatches"
    mb = b // m

    # embedding outside the pipeline (data-parallel, table vocab-sharded)
    x = L.embed_tokens(params["embed"], tokens, cfg)      # (B, S, D)
    x = x.reshape(m, mb, seq, cfg.d_model)
    positions = jnp.arange(seq)[None, :]

    block = functools.partial(transformer._block, cfg=cfg, positions=positions)
    if cfg.remat != "none":
        block = jax.checkpoint(block)

    def stage_fn(blocks_local, x_all):
        """Manual over 'stage': blocks_local is this stage's (L/S, ...)."""
        stage_id = jax.lax.axis_index("stage")
        perm = [(i, (i + 1) % s_stages) for i in range(s_stages)]
        state = jnp.zeros((mb, seq, cfg.d_model), x_all.dtype)
        outputs = jnp.zeros((m, mb, seq, cfg.d_model), x_all.dtype)

        def apply_local(xin):
            def body(c, blk):
                out, _aux = block(c, blk)
                return out, None

            y, _ = jax.lax.scan(body, xin, blocks_local)
            return y

        def tick(carry, t):
            state, outputs = carry
            prev = jax.lax.ppermute(state, "stage", perm)
            m_in = t - stage_id                      # this tick's microbatch
            inject = x_all[jnp.clip(t, 0, m - 1)]
            xin = jnp.where(stage_id == 0, inject, prev)
            active = (m_in >= 0) & (m_in < m)
            out = jnp.where(active, apply_local(xin), xin)
            # the last stage banks each finished microbatch
            slot = jnp.clip(m_in, 0, m - 1)
            banked = jax.lax.dynamic_update_index_in_dim(
                outputs, out, slot, axis=0
            )
            outputs = jnp.where((stage_id == s_stages - 1) & active,
                                banked, outputs)
            return (out, outputs), None

        (_, outputs), _ = jax.lax.scan(
            tick, (state, outputs), jnp.arange(m + s_stages - 1)
        )
        return outputs

    outputs = _stage_shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(P("stage"), P()),
        out_specs=P("stage"),
    )(params["blocks"], x)
    final = outputs[-m:]                              # last stage's bank
    hidden = final.reshape(b, seq, cfg.d_model)
    hidden = L.rms_norm(hidden, params["ln_f"], cfg.norm_eps)
    logits = L.lm_logits(params["embed"], hidden, cfg)
    return L.cross_entropy_loss(logits, labels, batch.get("loss_mask"))


def pipeline_param_specs(cfg: ModelConfig) -> Dict:
    """Param specs with the layer dim staged (rules map layers -> stage)."""
    return transformer.param_specs(cfg)


PIPELINE_RULES_OVERRIDE = {"layers": "stage"}

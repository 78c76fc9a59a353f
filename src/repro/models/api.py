"""Family-dispatched model API used by the launcher, dry-run, and tests."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import moe as moe_mod
from . import transformer, vlm, whisper


def init_model(key: Optional[jax.Array], cfg: ModelConfig,
               abstract: bool = False):
    """Returns (params, logical_specs).  abstract=True gives shape trees
    (no allocation) for dry-run lowering."""
    if cfg.family == "audio":
        return whisper.init_whisper(key, cfg, abstract=abstract)
    return transformer.init_lm(key, cfg, abstract=abstract)


def init_placed(key: jax.Array, cfg: ModelConfig, shardings):
    """Initialise params under jit with ``shardings`` as the output
    shardings: each device builds only its own shards, so no leaf ever
    lands whole on one device (call under the bundle's trace context)."""
    return jax.jit(lambda k: init_model(k, cfg)[0],
                   out_shardings=shardings)(key)


def build_moe_plan(cfg: ModelConfig, tokens_per_dp_shard: int, mesh,
                   store=None, hier_leader_perm=None):
    """One plan-backed EP dispatch plan per (config geometry, mesh).

    This is the model-INIT half of the persistent MoE dispatch: the backing
    ``AlltoallvPlan`` is built (or warm-started from the plan ``store`` —
    None means the process default, i.e. the launchers' ``--plan-store``
    flag) here, once, and every jitted step replays it.
    ``hier_leader_perm`` overrides the hierarchical exchange's per-group
    leader assignment (``runtime.leader`` re-elections); None keeps the
    round-robin default."""
    if cfg.moe is None:
        return None
    dtype = jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32
    return moe_mod.MoEDispatchPlan.build(
        cfg.moe, tokens_per_dp_shard, mesh,
        d_model=cfg.d_model, dtype=dtype, store=store,
        hier_leader_perm=hier_leader_perm)


def model_loss(params, cfg: ModelConfig, batch: dict, *,
               moe_plan=None, remat: bool = True):
    """Family-dispatched training loss: (scalar, metrics dict)."""
    if cfg.family == "audio":
        return whisper.whisper_loss(params, cfg, batch, remat=remat)
    if cfg.family == "vlm":
        return vlm.vlm_loss(params, cfg, batch, moe_plan=moe_plan, remat=remat)
    return transformer.lm_loss(params, cfg, batch, moe_plan=moe_plan, remat=remat)


def batch_spec(cfg: ModelConfig, batch_size: int, seq_len: int,
               dtype=jnp.int32) -> dict:
    """ShapeDtypeStructs for one training batch (dry-run input_specs)."""
    specs = {}
    if cfg.family == "audio":
        # frame-embedding stub: encoder sees seq_len frames, decoder
        # trains on max_seq tokens
        specs["frames"] = jax.ShapeDtypeStruct(
            (batch_size, seq_len, cfg.d_model), jnp.bfloat16)
        specs["tokens"] = jax.ShapeDtypeStruct(
            (batch_size, min(cfg.max_seq, 448)), dtype)
    elif cfg.family == "vlm":
        n_img = cfg.frontend_len
        specs["patches"] = jax.ShapeDtypeStruct(
            (batch_size, n_img, cfg.frontend_dim), jnp.bfloat16)
        specs["tokens"] = jax.ShapeDtypeStruct(
            (batch_size, seq_len - n_img), dtype)
    else:
        specs["tokens"] = jax.ShapeDtypeStruct((batch_size, seq_len), dtype)
    return specs

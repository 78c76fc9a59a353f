"""Step builders: train / prefill / decode bundles per (arch x shape x mesh).

A ``StepBundle`` packages the jitted step function, its argument
ShapeDtypeStructs, and the axis-rule context it must be traced under.  The
same bundles serve three consumers:

  * launch/train.py & serve.py — compile + run (reduced or full configs),
  * launch/dryrun.py — ``bundle.lower().compile()`` on the 512-device mesh
    with abstract params (the multi-pod dry-run),
  * roofline — reads cost/memory analysis off the compiled artifact.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import api as model_api
from repro.models import moe as moe_mod
from repro.models import transformer, whisper
from repro.parallel.sharding import (DECODE_RULES, DEFAULT_RULES,
                                     LONG_CONTEXT_RULES, axis_rules,
                                     batch_ways, resolve, specs_to_shardings)
from repro.train import grad as grad_util
from repro.train import optimizer as opt_mod
from repro.train import schedule as sched_mod


@dataclasses.dataclass
class StepBundle:
    name: str
    mesh: Mesh
    rules: dict
    jitted: Any
    arg_specs: tuple
    meta: dict

    def lower(self):
        with axis_rules(self.rules, self.mesh):
            return self.jitted.lower(*self.arg_specs)

    def compile(self):
        return self.lower().compile()

    def trace_context(self):
        return axis_rules(self.rules, self.mesh)


def _rep(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _batch_shardings(cfg: ModelConfig, mesh, batch_abs: dict) -> dict:
    axes = {"tokens": ("batch", "seq"),
            "frames": ("batch", "seq", "embed"),
            "patches": ("batch", "seq", None)}
    return {k: NamedSharding(mesh, resolve(axes[k], batch_abs[k].shape))
            for k in batch_abs}


def _moe_tokens_per_shard(cfg: ModelConfig, shape: ShapeConfig, mesh) -> int:
    # batch_ways, not dp_size: a rule profile may shard batch over fewer
    # axes than pod x data (hier_ep puts experts on pod), and undercounting
    # tokens here would undersize the MoE dispatch capacity and silently
    # drop routed tokens.
    b_loc = max(shape.global_batch // batch_ways(shape.global_batch, mesh), 1)
    if shape.kind == "decode":
        return b_loc
    seq = shape.seq_len
    if cfg.family == "vlm":
        seq = shape.seq_len  # image tokens + (text - 1) ~ seq
    return b_loc * max(seq - 1, 1)


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------


def _n_ways(sharding: NamedSharding, mesh) -> int:
    n = 1
    for axes in (sharding.spec or []):
        if axes is None:
            continue
        for a in (axes,) if isinstance(axes, str) else axes:
            n *= int(mesh.shape[a])
    return n


def make_train_bundle(
    cfg: ModelConfig,
    shape: ShapeConfig,
    mesh: Mesh,
    *,
    sched: Optional[sched_mod.ScheduleConfig] = None,
    adamw: Optional[opt_mod.AdamWConfig] = None,
    zero1: bool = True,
    remat: bool = True,
    clip_norm: float = 1.0,
    n_micro: int = 1,
    rules: Optional[dict] = None,
    fsdp_threshold_bytes: float = 3 * 2**30,
    grad_compression: bool = False,
    grad_sync: str = "default",
    hier_leader_perm=None,
) -> StepBundle:
    sched = sched or sched_mod.ScheduleConfig()
    adamw = adamw or opt_mod.AdamWConfig(
        master_weights=(cfg.param_dtype != "float32"))
    rules = dict(rules or DEFAULT_RULES)

    with axis_rules(rules, mesh):
        params_abs, logical_specs = model_api.init_model(None, cfg, abstract=True)
        param_sh = specs_to_shardings(logical_specs, mesh, params_abs)

        # FSDP: when TP-only leaves >3 GiB of weights per chip, also shard
        # params over the data axes (per-layer all-gather inside the scan).
        tp_bytes = sum(
            a.size * a.dtype.itemsize / _n_ways(s, mesh)
            for a, s in zip(jax.tree.leaves(params_abs), jax.tree.leaves(param_sh)))
        dp_axes = tuple(rules.get("batch") or ("pod", "data"))
        fsdp = tp_bytes > fsdp_threshold_bytes
        if fsdp:
            param_sh = opt_mod.opt_state_shardings(
                logical_specs, params_abs, mesh, adamw, zero1=True,
                dp_axes=dp_axes)["m"]

        opt_abs = jax.eval_shape(partial(opt_mod.init_opt_state, cfg=adamw,
                                         grad_err=grad_compression),
                                 params_abs)
        opt_sh = opt_mod.opt_state_shardings(logical_specs, params_abs, mesh,
                                             adamw, zero1=zero1,
                                             dp_axes=dp_axes,
                                             grad_err=grad_compression)
        grad_sh = opt_sh["m"] if (zero1 or fsdp) else param_sh
        batch_abs = model_api.batch_spec(cfg, shape.global_batch, shape.seq_len)
        batch_sh = _batch_shardings(cfg, mesh, batch_abs)
        moe_plan = model_api.build_moe_plan(
            cfg, _moe_tokens_per_shard(cfg, shape, mesh), mesh,
            hier_leader_perm=hier_leader_perm)

        # Compressed DP gradient sync runs at TP-only sharding (every leaf
        # DP-replicated) so the int8 mean-reduce over the data axes sees
        # whole replicas; clip + AdamW then constrain back to the ZeRO
        # shardings as before.  grad_sync="persistent_rs" swaps the DP wire
        # for the plan-backed RS+AG pair (train/grad.py), composing with
        # the error-feedback int8 path when grad_compression is also on.
        if grad_sync not in ("default", "persistent_rs"):
            raise ValueError(f"unknown grad_sync {grad_sync!r}")
        comp_sync = rs_sync = None
        if grad_sync == "persistent_rs" or grad_compression:
            from repro.parallel.sharding import specs_to_pspecs
            pspecs = specs_to_pspecs(logical_specs, params_abs)
            if grad_sync == "persistent_rs":
                rs_sync = grad_util.persistent_rs_sync(
                    mesh, pspecs, dp_axes, error_feedback=grad_compression)
            else:
                comp_sync = grad_util.compressed_sync(mesh, pspecs, dp_axes)

        def train_step(params, opt_state, batch, step):
            lr = sched_mod.lr_at(sched, step)

            def loss_fn(p, b):
                return model_api.model_loss(p, cfg, b, moe_plan=moe_plan,
                                            remat=remat)

            def constrain(g):
                # ZeRO-2: reduce-scatter grads to the optimizer's sharding
                return jax.tree.map(jax.lax.with_sharding_constraint, g, grad_sh)

            loss, metrics, grads = grad_util.accumulate_grads(
                loss_fn, params, batch, n_micro, constrain=constrain)
            new_err = None
            if comp_sync is not None:
                grads, new_err = comp_sync(grads, opt_state["grad_err"])
                grads = constrain(grads)
            elif rs_sync is not None:
                if grad_compression:
                    grads, new_err = rs_sync(grads, opt_state["grad_err"])
                else:
                    grads = rs_sync(grads)
                grads = constrain(grads)
            grads, gn = grad_util.clip_by_global_norm(grads, clip_norm)
            new_params, new_opt = opt_mod.adamw_update(grads, opt_state,
                                                       params, lr, adamw)
            if new_err is not None:
                # adamw_update rebuilds the state dict from its own keys;
                # re-attach the fresh EF residual so it checkpoints with
                # the rest of the optimizer state.
                new_opt["grad_err"] = new_err
            metrics = dict(metrics, grad_norm=gn, lr=lr)
            return new_params, new_opt, metrics

        jitted = jax.jit(
            train_step,
            in_shardings=(param_sh, opt_sh, batch_sh, _rep(mesh)),
            out_shardings=(param_sh, opt_sh, _rep(mesh)),
            donate_argnums=(0, 1),
        )

    step_abs = jax.ShapeDtypeStruct((), jnp.int32)
    return StepBundle(
        name=f"train:{cfg.name}:{shape.name}",
        mesh=mesh, rules=rules, jitted=jitted,
        arg_specs=(params_abs, opt_abs, batch_abs, step_abs),
        meta={"cfg": cfg, "shape": shape, "moe_plan": moe_plan,
              "param_shardings": param_sh, "opt_shardings": opt_sh,
              "batch_shardings": batch_sh, "logical_specs": logical_specs,
              "sched": sched, "adamw": adamw,
              "grad_compression": grad_compression,
              "grad_sync": grad_sync,
              # Everything needed to rebuild this bundle mid-run (online
              # re-plan, device-loss recovery): make_train_bundle(cfg,
              # shape, mesh, **bundle_kwargs) reproduces it.
              "bundle_kwargs": {"sched": sched, "adamw": adamw,
                                "zero1": zero1, "remat": remat,
                                "clip_norm": clip_norm, "n_micro": n_micro,
                                "rules": rules,
                                "fsdp_threshold_bytes": fsdp_threshold_bytes,
                                "grad_compression": grad_compression,
                                "grad_sync": grad_sync,
                                "hier_leader_perm": hier_leader_perm}},
    )


# ---------------------------------------------------------------------------
# Serve: decode
# ---------------------------------------------------------------------------


def make_decode_bundle(
    cfg: ModelConfig,
    shape: ShapeConfig,
    mesh: Mesh,
    *,
    rules: Optional[dict] = None,
) -> StepBundle:
    """One new token against a KV cache / recurrent state of shape.seq_len.

    ``decode_step(params, caches, tokens, index, experts_touched)`` returns
    ``(next tokens, caches, experts_touched + this step's count)``: the
    count (experts with a kept assignment, summed over MoE layers; 0
    without MoE) stays on the device, so a caller sums a whole generation
    with no host sync per token."""
    if rules is None:
        rules = LONG_CONTEXT_RULES if shape.name == "long_500k" else DECODE_RULES
    rules = dict(rules)
    b = max(shape.global_batch // 1, 1)
    cache_dtype = jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32

    with axis_rules(rules, mesh):
        params_abs, logical_specs = model_api.init_model(None, cfg, abstract=True)
        param_sh = specs_to_shardings(logical_specs, mesh, params_abs)
        moe_plan = model_api.build_moe_plan(
            cfg, _moe_tokens_per_shard(cfg, shape, mesh), mesh)

        if cfg.family == "audio":
            self_len = min(cfg.max_seq, 448)
            caches_abs = jax.eval_shape(lambda: whisper.init_dec_caches(
                cfg, b, self_len, shape.seq_len, cache_dtype))
            cache_logical = whisper.dec_cache_logical_specs(cfg)
            cache_sh = specs_to_shardings(cache_logical, mesh, caches_abs)

            def decode_step(params, caches, tokens, index, experts_touched):
                logits, new_caches = whisper.decode(
                    params, cfg, tokens, None, caches=caches,
                    cache_index=index, remat=False)
                nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), -1)
                return nxt.astype(jnp.int32)[:, None], new_caches, experts_touched
        else:
            caches_abs = transformer.cache_shape_specs(cfg, b, shape.seq_len,
                                                       cache_dtype)
            cache_logical = transformer.cache_logical_specs(cfg)
            cache_sh = specs_to_shardings(cache_logical, mesh, caches_abs)

            def decode_step(params, caches, tokens, index, experts_touched):
                logits, aux, new_caches = transformer.forward(
                    params, cfg, tokens, moe_plan=moe_plan, caches=caches,
                    cache_index=index, remat=False)
                nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), -1)
                return (nxt.astype(jnp.int32)[:, None], new_caches,
                        experts_touched + aux[2])

        tok_sh = NamedSharding(mesh, resolve(("batch", None)))
        jitted = jax.jit(
            decode_step,
            in_shardings=(param_sh, cache_sh, tok_sh, _rep(mesh), _rep(mesh)),
            out_shardings=(tok_sh, cache_sh, _rep(mesh)),
            donate_argnums=(1,),
        )

    tok_abs = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    idx_abs = jax.ShapeDtypeStruct((), jnp.int32)
    touched_abs = jax.ShapeDtypeStruct((), jnp.float32)
    return StepBundle(
        name=f"decode:{cfg.name}:{shape.name}",
        mesh=mesh, rules=rules, jitted=jitted,
        arg_specs=(params_abs, caches_abs, tok_abs, idx_abs, touched_abs),
        meta={"cfg": cfg, "shape": shape, "moe_plan": moe_plan,
              "param_shardings": param_sh, "cache_shardings": cache_sh,
              "logical_specs": logical_specs, "cache_dtype": cache_dtype},
    )


# ---------------------------------------------------------------------------
# Serve: prefill
# ---------------------------------------------------------------------------


def make_prefill_bundle(
    cfg: ModelConfig,
    shape: ShapeConfig,
    mesh: Mesh,
    *,
    rules: Optional[dict] = None,
) -> StepBundle:
    """Full-sequence prefill producing last-token logits + primed caches."""
    rules = dict(rules or DEFAULT_RULES)
    b = shape.global_batch
    s = shape.seq_len
    cache_dtype = jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32

    with axis_rules(rules, mesh):
        params_abs, logical_specs = model_api.init_model(None, cfg, abstract=True)
        param_sh = specs_to_shardings(logical_specs, mesh, params_abs)
        moe_plan = model_api.build_moe_plan(
            cfg, max(b // batch_ways(b, mesh), 1) * s, mesh)

        if cfg.family == "audio":
            self_len = min(cfg.max_seq, 448)
            prompt = 8

            def prefill(params, frames, tokens):
                enc = whisper.encode(params, cfg, frames, remat=True)
                caches = whisper.init_dec_caches(cfg, b, self_len, s, cache_dtype)
                caches = whisper.prime_cross_caches(params, cfg, enc, caches)
                logits, caches = whisper.decode(
                    params, cfg, tokens, None, caches=caches,
                    cache_index=jnp.int32(0), remat=True)
                return logits[:, -1], caches

            frames_abs = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
            tok_abs = jax.ShapeDtypeStruct((b, prompt), jnp.int32)
            arg_specs = (params_abs, frames_abs, tok_abs)
            in_sh = (param_sh,
                     NamedSharding(mesh, resolve(("batch", "seq", "embed"),
                                                 frames_abs.shape)),
                     NamedSharding(mesh, resolve(("batch", None), tok_abs.shape)))
            caches_abs = jax.eval_shape(lambda: whisper.init_dec_caches(
                cfg, b, self_len, s, cache_dtype))
            cache_sh = specs_to_shardings(whisper.dec_cache_logical_specs(cfg),
                                          mesh, caches_abs)
        else:
            text = s - cfg.frontend_len if cfg.family == "vlm" else s

            def prefill(params, *inputs):
                if cfg.family == "vlm":
                    patches, tokens = inputs
                    from repro.models import vlm
                    extra = vlm.project_patches(params["projector"], patches)
                else:
                    (tokens,) = inputs
                    extra = None
                caches = transformer.init_caches(cfg, b, s, cache_dtype)
                logits, _, caches = transformer.forward(
                    params, cfg, tokens, moe_plan=moe_plan, caches=caches,
                    cache_index=jnp.int32(0), extra_embeds=extra, remat=True)
                return logits[:, -1], caches

            tok_abs = jax.ShapeDtypeStruct((b, text), jnp.int32)
            if cfg.family == "vlm":
                patches_abs = jax.ShapeDtypeStruct(
                    (b, cfg.frontend_len, cfg.frontend_dim), jnp.bfloat16)
                arg_specs = (params_abs, patches_abs, tok_abs)
                in_sh = (param_sh,
                         NamedSharding(mesh, resolve(("batch", "seq", None),
                                                     patches_abs.shape)),
                         NamedSharding(mesh, resolve(("batch", "seq"),
                                                     tok_abs.shape)))
            else:
                arg_specs = (params_abs, tok_abs)
                in_sh = (param_sh, NamedSharding(mesh, resolve(("batch", "seq"),
                                                               tok_abs.shape)))
            caches_abs = transformer.cache_shape_specs(cfg, b, s, cache_dtype)
            cache_sh = specs_to_shardings(transformer.cache_logical_specs(cfg),
                                          mesh, caches_abs)

        jitted = jax.jit(
            prefill,
            in_shardings=in_sh,
            out_shardings=(NamedSharding(mesh, resolve(("batch", "vocab"),
                                                       (b, cfg.vocab_size))),
                           cache_sh),
        )

    return StepBundle(
        name=f"prefill:{cfg.name}:{shape.name}",
        mesh=mesh, rules=rules, jitted=jitted, arg_specs=arg_specs,
        meta={"cfg": cfg, "shape": shape, "moe_plan": moe_plan,
              "param_shardings": param_sh, "cache_shardings": cache_sh,
              "logical_specs": logical_specs},
    )


def make_bundle(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, **kw) -> StepBundle:
    """Shape-kind dispatch: train_* -> train, prefill_* -> prefill,
    decode_*/long_* -> decode."""
    if shape.kind == "train":
        return make_train_bundle(cfg, shape, mesh, **kw)
    if shape.kind == "prefill":
        return make_prefill_bundle(cfg, shape, mesh, **kw)
    return make_decode_bundle(cfg, shape, mesh, **kw)

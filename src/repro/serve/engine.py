"""Batched serving engine: prefill -> decode with persistent caches.

The decode step is the jitted bundle (caches donated, so the KV buffers are
reused epoch-over-epoch just like the paper's persistent windows).

``generate`` runs each host step under a ``TRACER`` span, so a
``jax.profiler`` capture shows them on the caller's thread on the device
trace's clock: ``serve.generate`` holds ``serve.prefill`` (dispatch and
block), ``serve.grow_caches``, ``serve.first_token`` (argmax and the first
fetch), then per decode token ``serve.decode_dispatch`` and
``serve.token_fetch``.  Each call adds its decode steps and the experts its
steps touched to the process-wide ``serve.decode_steps`` and
``serve.experts_touched`` counters (``repro.obs.counters``)."""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.ckpt.reshard import put_tree
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch import steps as steps_mod
from repro.models import api as model_api
from repro.models import transformer, whisper
from repro.obs.counters import COUNTERS
from repro.obs.spans import TRACER


@dataclasses.dataclass
class ServeStats:
    prefill_seconds: float
    decode_seconds_per_token: float
    tokens_generated: int


class ServeEngine:
    """Prefill+decode for decoder-only and enc-dec families."""

    def __init__(self, cfg: ModelConfig, mesh, batch: int, prompt_len: int,
                 max_seq: int, params=None, seed: int = 0, plan_store=None):
        """``plan_store`` (a directory path, a store URL —
        ``fsremote://…`` / ``tiered:local=…,remote=…``, see
        ``planstore.parse_store_url`` — or a ``repro.planstore.PlanStore``)
        becomes the PROCESS-default plan store (a deliberate global side
        effect — it outlives this engine and is seen by every subsequent
        ``alltoallv_init``, including other engines constructed with
        ``plan_store=None``; pass ``store=`` explicitly at call sites that
        must not share it).  With it set, any persistent-plan dispatch path
        in this process warm-starts from artifacts of previous serving
        replicas: autotune sweeps and table bakes are skipped.  That
        includes the built-in MoE dispatch — the prefill and decode bundles
        below build plan-backed EP dispatch plans whose backing
        ``AlltoallvPlan``s consult the store at INIT (``self.moe_plan``
        exposes the decode bundle's plan for inspection)."""
        if prompt_len > max_seq:
            raise ValueError(
                f"prompt_len {prompt_len} exceeds max_seq {max_seq}: the "
                f"decode caches are sized max_seq, so the prefill prefix "
                f"would not fit (growing them would need negative padding)")
        self.cfg = cfg
        self.mesh = mesh
        self.batch = batch
        self.prompt_len = prompt_len
        self.max_seq = max_seq
        if plan_store is not None:
            from repro import planstore
            self.plan_store = planstore.configure(plan_store)
        else:
            self.plan_store = None
        shape_p = ShapeConfig("serve_prefill", "prefill", prompt_len, batch)
        shape_d = ShapeConfig("serve_decode", "decode", max_seq, batch)
        self.prefill_bundle = steps_mod.make_prefill_bundle(cfg, shape_p, mesh)
        self.decode_bundle = steps_mod.make_decode_bundle(cfg, shape_d, mesh)
        # EP dispatch plan ownership (None for non-MoE families): the
        # decode bundle's plan-backed MoE dispatch plan, built above after
        # the store was configured, so its INIT saw the warm tier.
        self.moe_plan = self.decode_bundle.meta.get("moe_plan")
        # The decode steps' running experts-touched total starts here.
        self._no_experts = jax.device_put(
            np.float32(0), NamedSharding(mesh, PartitionSpec()))
        with self.decode_bundle.trace_context():
            shardings = self.decode_bundle.meta["param_shardings"]
            if params is None:
                self.params = model_api.init_placed(
                    jax.random.key(seed), cfg, shardings)
            else:
                self.params = put_tree(params, shardings)

    def generate(self, prompts: np.ndarray, n_tokens: int,
                 frames: Optional[np.ndarray] = None):
        """prompts: [B, prompt_len] int32. Returns (tokens [B, n], stats)."""
        cfg = self.cfg
        prompt_len = int(prompts.shape[1])
        if prompt_len + n_tokens > self.max_seq:
            raise ValueError(
                f"prompt_len {prompt_len} + n_tokens {n_tokens} exceeds "
                f"max_seq {self.max_seq}: decode would write past the KV "
                f"caches — raise max_seq or generate fewer tokens")
        with TRACER.span("serve.generate", "execute"):
            with TRACER.span("serve.prefill", "execute"):
                t0 = time.perf_counter()
                with self.prefill_bundle.trace_context():
                    if cfg.family == "audio":
                        logits, caches = self.prefill_bundle.jitted(
                            self.params, jnp.asarray(frames),
                            jnp.asarray(prompts))
                    else:
                        logits, caches = self.prefill_bundle.jitted(
                            self.params, jnp.asarray(prompts))
                jax.block_until_ready(logits)
                t_prefill = time.perf_counter() - t0

            # prefill caches were sized for the prompt; decode caches are
            # sized max_seq — copy the primed prefix in.
            with TRACER.span("serve.grow_caches", "execute"):
                caches = self._grow_caches(caches)
            with TRACER.span("serve.first_token", "execute"):
                next_tok = jnp.argmax(logits.astype(jnp.float32),
                                      -1).astype(jnp.int32)[:, None]
                out = [np.asarray(next_tok)]
            index = prompts.shape[1]

            touched = self._no_experts
            t0 = time.perf_counter()
            with self.decode_bundle.trace_context():
                for i in range(n_tokens - 1):
                    with TRACER.span("serve.decode_dispatch", "execute"):
                        next_tok, caches, touched = self.decode_bundle.jitted(
                            self.params, caches, next_tok,
                            jnp.int32(index + i), touched)
                    with TRACER.span("serve.token_fetch", "execute"):
                        out.append(np.asarray(next_tok))
            t1 = time.perf_counter()
            t_decode = (t1 - t0) / max(n_tokens - 1, 1)
            if n_tokens > 1:
                COUNTERS.add("serve.decode_steps", n_tokens - 1)
                COUNTERS.add("serve.experts_touched", round(float(touched)))
        tokens = np.concatenate(out, axis=1)
        return tokens, ServeStats(t_prefill, t_decode, tokens.size)

    def _grow_caches(self, prefill_caches):
        """Pad prefill-sized caches out to the decode bundle's cache shapes."""
        with self.decode_bundle.trace_context():
            target = self.decode_bundle.arg_specs[1]

            def grow(src, tgt):
                if src.shape == tgt.shape:
                    return src
                pads = [(0, t - s) for s, t in zip(src.shape, tgt.shape)]
                if any(p < 0 for _, p in pads):
                    # Belt and braces: __init__ validates prompt_len <=
                    # max_seq, so a negative pad here means the bundles
                    # disagree about cache geometry — fail with the shapes,
                    # not a cryptic jnp.pad error.
                    raise ValueError(
                        f"prefill cache shape {src.shape} exceeds decode "
                        f"cache shape {tgt.shape}")
                return jnp.pad(src, pads)

            grown = jax.tree.map(grow, prefill_caches, target)
            return put_tree(grown, self.decode_bundle.meta["cache_shardings"])

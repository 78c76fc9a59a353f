"""Training loop: checkpoint/restart fault tolerance, straggler detection,
auto-resume, deterministic data replay — plus online re-planning: step
wall times feed the EP dispatch plan's EXECUTE telemetry ring, and on
sustained skew (or a forced ``replan_at`` step) the variant decision is
re-measured in a sandbox and the step bundle rebuilt against the fresh
verdict between steps."""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.ckpt.manager import CheckpointManager
from repro.ckpt.reshard import put_tree
from repro.core._exec_stats import EXEC_TELEMETRY
from repro.data.pipeline import DataPipeline
from repro.models import api as model_api
from repro.obs.spans import TRACER
from repro.runtime.fault import RetryPolicy, run_with_recovery
from repro.runtime.straggler import PlanSkewMonitor, StragglerDetector
from repro.train import optimizer as opt_mod

log = logging.getLogger("repro.train")


@dataclasses.dataclass
class TrainerConfig:
    n_steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    ckpt_keep: int = 3
    async_ckpt: bool = True
    log_every: int = 10
    max_restarts: int = 3
    seed: int = 0
    # Online re-planning of the EP dispatch plan (plan-backed MoE only):
    # replan=True arms the skew monitor; replan_at forces one re-plan
    # after that step completes (deterministic trigger for CI/chaos runs).
    replan: bool = False
    replan_at: Optional[int] = None
    replan_threshold: float = 1.75
    replan_iters: int = 4
    # Per-rank epoch timing: probe each device shard's readiness after the
    # step and feed the (digest, rank) rank rings (skew attribution).
    rank_timing: bool = True


class Trainer:
    """Owns device state + the recovery discipline around a StepBundle."""

    def __init__(self, bundle, tcfg: TrainerConfig, chaos=None):
        self.bundle = bundle
        self.tcfg = tcfg
        self.chaos = chaos
        self.cfg = bundle.meta["cfg"]
        self.shape = bundle.meta["shape"]
        self.mesh = bundle.mesh
        # The trainer owns the EP dispatch plan for reporting: with a
        # plan-backed MoE dispatch the backing AlltoallvPlan was built (or
        # warm-started from the plan store) during bundle construction.
        self.moe_plan = bundle.meta.get("moe_plan")
        if self.moe_plan is not None and getattr(self.moe_plan, "a2a", None) \
                is not None:
            log.info("EP dispatch plan-backed: variant=%s warm=%s "
                     "overlap_chunks=%d",
                     self.moe_plan.variant, self.moe_plan.a2a.warm_loaded,
                     self.moe_plan.overlap_chunks)
        self.pipe = DataPipeline(self.cfg, self.shape.seq_len,
                                 self.shape.global_batch, self.mesh,
                                 seed=1234 + tcfg.seed)
        self.ckpt = (CheckpointManager(tcfg.ckpt_dir, keep=tcfg.ckpt_keep,
                                       async_save=tcfg.async_ckpt)
                     if tcfg.ckpt_dir else None)
        self.straggler = StragglerDetector()
        self.params = None
        self.opt_state = None
        self.start_step = 0
        self.history: list[dict] = []
        self.replan_events: list[dict] = []
        self.recoveries: list[dict] = []
        self._skew: Optional[PlanSkewMonitor] = None
        if tcfg.replan:
            self._arm_skew_monitor()

    def _backing_a2a(self):
        return getattr(self.moe_plan, "a2a", None) \
            if self.moe_plan is not None else None

    def _arm_skew_monitor(self) -> None:
        a2a = self._backing_a2a()
        if a2a is None:
            return
        self._skew = PlanSkewMonitor(
            EXEC_TELEMETRY.ring(a2a.signature.digest),
            threshold=self.tcfg.replan_threshold,
            window=4, sustain=2, warmup=4,
            digest=a2a.signature.digest)

    # -- state management ----------------------------------------------------
    def init_state(self) -> None:
        with self.bundle.trace_context():
            self.params = model_api.init_placed(
                jax.random.key(self.tcfg.seed), self.cfg,
                self.bundle.meta["param_shardings"])
            self.opt_state = opt_mod.init_opt_state(
                self.params, self.bundle.meta["adamw"],
                grad_err=self.bundle.meta.get("grad_compression", False))

    def try_resume(self) -> bool:
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return False
        self._restore()
        return True

    def _restore(self) -> int:
        step, trees, extras = self.ckpt.load()
        with self.bundle.trace_context():
            self.params = put_tree(trees["params"],
                                   self.bundle.meta["param_shardings"])
            self.opt_state = put_tree(trees["opt"],
                                      self.bundle.meta["opt_shardings"])
        self.pipe.load_state_dict(extras.get("data", {"step": step}))
        self.start_step = step
        log.info("restored checkpoint at step %d", step)
        return step

    def _save(self, step: int) -> None:
        if self.ckpt is None:
            return
        self.ckpt.save(step, {"params": self.params, "opt": self.opt_state},
                       extras={"data": self.pipe.state_dict(), "step": step})

    # -- driving -------------------------------------------------------------
    def _run_one(self, step: int) -> dict:
        if self.chaos is not None:
            # Inside the recovery try-block: injected faults exercise the
            # real restart path, and stalls land inside the timed region so
            # the straggler/skew monitors see them.
            self.chaos.step_hook(step)
        self.straggler.start()
        t_step0 = time.perf_counter()
        # Resolve batch shardings under the bundle's rule profile (a
        # non-default profile, e.g. hier_ep, maps "batch" differently).
        with self.bundle.trace_context():
            batch = self.pipe.batch_at(step)
        self.params, self.opt_state, metrics = self.bundle.jitted(
            self.params, self.opt_state, batch, jnp.int32(step))
        rank_seconds = self._probe_rank_times(metrics, t_step0)
        if self.chaos is not None and rank_seconds:
            # rank_slow, attribution side: inflate the slowed ranks' samples
            # so the skew monitor blames the right rank.
            rank_seconds = self.chaos.scale_rank_times(step, rank_seconds)
        if self.chaos is not None:
            # rank_slow, wall-time side: stall by the slow ranks' share of
            # the work done so far this step (pre-stall, so no feedback
            # loop through the EMA) — the full factor while a slowed rank
            # carries leader slabs, the member share once demoted.
            self.chaos.maybe_rank_stall(step, self._carrying_ranks(),
                                        time.perf_counter() - t_step0)
        jax.block_until_ready(metrics)
        t_step1 = time.perf_counter()
        if TRACER.enabled:
            TRACER.emit_span("train_step", "execute", t_step0, t_step1,
                             {"step": step})
        report = self.straggler.stop(step)
        if report is not None:
            log.warning("straggler step %d: %.3fs (%.1fx EMA %.3fs)",
                        report.step, report.seconds, report.ratio,
                        report.ema_seconds)
        a2a = self._backing_a2a()
        if a2a is not None and self.straggler.last_seconds is not None:
            # The EP exchange runs embedded in the jitted step, so the plan
            # cannot self-time; the step wall time is the epoch-level
            # signal the skew monitor watches (attribution to the exchange
            # vs compute is the monitor's job, not the recorder's).
            # Anchor the epoch span at t_step1: the straggler window opened
            # before t_step0 and closed after it, so [t_end - seconds,
            # t_end] then strictly contains the train_step span — proper
            # nesting instead of spilling past it by the stop-to-here gap.
            a2a.record_epoch(self.straggler.last_seconds, t_end=t_step1)
            if rank_seconds:
                a2a.record_epoch_ranks(rank_seconds)
        out = {k: float(v) for k, v in metrics.items()}
        self._maybe_replan(step)
        if (step + 1) % self.tcfg.ckpt_every == 0 or \
                (self.straggler.should_checkpoint_early()
                 and self.ckpt is not None):
            self._save(step + 1)
        return out

    def _probe_rank_times(self, metrics, t0: float) -> "dict[int, float] | None":
        """Per-rank step-completion probe for the rank rings.

        Blocks on each addressable device shard of one metrics array in
        turn, recording when each becomes ready relative to dispatch.  The
        probe is a skyline: a shard that finished before an earlier one is
        charged the earlier one's wait, so values are upper bounds — but a
        straggling device still stands out, which is all the skew monitor's
        rank attribution needs.  On a single-host CPU mesh the times are
        near-uniform; the signal gets honest exactly where it matters
        (real multi-device backends with async dispatch)."""
        if not self.tcfg.rank_timing or self._backing_a2a() is None:
            return None
        try:
            arr = next(iter(metrics.values()))
            out: dict[int, float] = {}
            for shard in arr.addressable_shards:
                jax.block_until_ready(shard.data)
                out[int(shard.device.id)] = time.perf_counter() - t0
            return out
        except (AttributeError, TypeError, StopIteration):
            return None     # non-array metrics (tests with stub bundles)

    def _carrying_ranks(self) -> "set[int] | None":
        """Ranks carrying inter-group leader slabs under the live hierarchy
        schedule (src or dst of any stage-2 put).  None means every rank
        gates the epoch — flat variants, or no plan-backed dispatch."""
        a2a = self._backing_a2a()
        sched = getattr(a2a, "hier_schedule", None) if a2a is not None else None
        if sched is None:
            return None
        return {int(r) for rnd in sched.round_perms
                for pair in rnd for r in pair}

    # -- online re-planning --------------------------------------------------
    def _maybe_replan(self, step: int) -> None:
        a2a = self._backing_a2a()
        if a2a is None:
            return
        forced = (self.tcfg.replan_at is not None
                  and step == self.tcfg.replan_at
                  and not any(ev.get("kind") == "forced"
                              for ev in self.replan_events))
        skew = self._skew.observe() if self._skew is not None else None
        if not forced and skew is None:
            return
        if not forced and self._try_leader_rebake(step, skew):
            return
        from repro import planstore
        from repro.core import global_plan_cache
        from repro.core.autotune import decision_signature
        from repro.runtime import replan as replan_mod
        if forced:
            reason = {"kind": "forced", "step": step}
        else:
            reason = {"kind": "sustained_skew", "step": step,
                      "ratio": skew.ratio, "baseline_s": skew.baseline}
        error_tol = getattr(self.cfg.moe, "codec_tol", None) \
            if getattr(self.cfg, "moe", None) is not None else None
        TRACER.instant("replan_trigger", "runtime",
                       digest=a2a.signature.digest, kind=reason["kind"],
                       step=step)
        t0 = time.perf_counter()
        store = planstore.default_store()
        prev_variant = self.moe_plan.variant
        try:
            choice = replan_mod.reautotune(
                a2a, self.mesh, store=store, iters=self.tcfg.replan_iters,
                embeddable=True, error_tol=error_tol,
                annotate={"replan": {**reason,
                                     "prev_variant": prev_variant}})
        except Exception as err:  # noqa: BLE001 — a faulting autotuner must not kill training
            log.warning("re-plan autotune faulted (%s); degrading EP "
                        "dispatch decision to fence", err)
            choice = {"variant": "fence", "codec": "identity",
                      "degraded": str(err), "replan": reason}
        # Seed the live decision tier so the bundle rebuild (and any other
        # replica reading the store) resolves instantly from this verdict.
        live = global_plan_cache()
        live.auto_choices[decision_signature(
            a2a.spec, self.mesh, embeddable=True,
            error_tol=error_tol)] = choice
        swapped = False
        if choice["variant"] != prev_variant and \
                getattr(self.cfg.moe, "a2a_variant", None) == "auto":
            old_digest = a2a.signature.digest
            self._rebuild_bundle()
            new_a2a = self._backing_a2a()
            swapped = new_a2a is not None and \
                new_a2a.signature.digest != old_digest
            if swapped:
                # _rebuild_bundle already freed the old plan and re-anchored
                # the incoming plan's rank rings.
                EXEC_TELEMETRY.record_swap(
                    old=old_digest, new=new_a2a.signature.digest,
                    reason=reason, variant_from=prev_variant,
                    variant_to=self.moe_plan.variant)
                TRACER.instant("plan_hot_swap", "runtime",
                               old=old_digest,
                               new=new_a2a.signature.digest,
                               variant_from=prev_variant,
                               variant_to=self.moe_plan.variant,
                               kind=reason["kind"])
        elif self._skew is not None:
            self._skew.reset()   # incumbent confirmed: fresh baseline
        ev = {**reason, "variant_from": prev_variant,
              "variant_to": choice["variant"], "swapped": swapped,
              "seconds": time.perf_counter() - t0}
        self.replan_events.append(ev)
        log.warning("re-plan at step %d: %s -> %s (swapped=%s, %.2fs)",
                    step, prev_variant, choice["variant"], swapped,
                    ev["seconds"])

    def _try_leader_rebake(self, step: int, skew) -> bool:
        """Ladder rung 0: demote the blamed rank out of leadership.

        Hierarchy plans with a ``worst_rank`` attribution get a cheap
        health-weighted leader re-election first (``runtime.leader``):
        host-side schedule bake + recompile, zero measurement bursts.  The
        full sandbox re-autotune only runs when re-election is ineligible
        or the cost model says it cannot lower the bottleneck."""
        a2a = self._backing_a2a()
        worst = getattr(skew, "worst_rank", None)
        if a2a is None or a2a.spec.variant != "fence_hierarchy" \
                or worst is None:
            return False
        from repro.runtime import leader as leader_mod
        health = leader_mod.rank_health(a2a.signature.digest, a2a.p)
        perm = leader_mod.choose_leader_perm(
            a2a.send_counts, a2a.p_outer, a2a.p_inner, health,
            exclude=(int(worst),))
        if perm == a2a.hier_schedule.leader_perm:
            return False
        cur_cost = leader_mod.permutation_cost(
            a2a.send_counts, a2a.p_outer, a2a.p_inner,
            a2a.hier_schedule.leader_perm, health)
        new_cost = leader_mod.permutation_cost(
            a2a.send_counts, a2a.p_outer, a2a.p_inner, perm, health)
        if new_cost >= cur_cost:
            return False
        reason = {"kind": "leader_rebake", "step": step,
                  "ratio": skew.ratio, "baseline_s": skew.baseline,
                  "worst_rank": int(worst),
                  "worst_rank_ratio": skew.worst_rank_ratio}
        t0 = time.perf_counter()
        old_digest = a2a.signature.digest
        prev_variant = self.moe_plan.variant
        # Persist the election in bundle_kwargs so recovery rebuilds (and
        # any later re-plan's rebuild) keep the demotion.
        self.bundle.meta["bundle_kwargs"]["hier_leader_perm"] = perm
        self._rebuild_bundle()
        new_a2a = self._backing_a2a()
        if new_a2a is None or new_a2a.signature.digest == old_digest:
            return False     # identity election resolved back: escalate
        EXEC_TELEMETRY.record_swap(
            old=old_digest, new=new_a2a.signature.digest, reason=reason,
            variant_from=prev_variant, variant_to=self.moe_plan.variant)
        TRACER.instant("leader_rebake", "runtime", old=old_digest,
                       new=new_a2a.signature.digest, worst_rank=int(worst),
                       leader_perm=[list(r) for r in perm])
        ev = {**reason, "variant_from": prev_variant,
              "variant_to": self.moe_plan.variant, "swapped": True,
              "leader_perm": [list(r) for r in perm],
              "seconds": time.perf_counter() - t0}
        self.replan_events.append(ev)
        log.warning("leader re-bake at step %d: demoted rank %d "
                    "(%s -> %s, %.2fs)", step, int(worst), old_digest[:12],
                    new_a2a.signature.digest[:12], ev["seconds"])
        return True

    def _rebuild_bundle(self) -> None:
        """Rebuild the step bundle in place (same cfg/shape/mesh): the
        path a changed variant decision — or a device-loss-class failure —
        takes to refresh compiled state between steps.  Params/opt state
        survive untouched; only the jitted program and the EP dispatch
        plan are rebuilt.  When the rebuild lands on a *different* backing
        plan (changed variant or leader perm), the replaced plan's window
        slots are released and the incoming plan's per-rank rings are
        re-anchored — stale samples from the old schedule must not blame a
        now-demoted rank."""
        from repro.launch import steps as steps_mod
        old_a2a = self._backing_a2a()
        kw = dict(self.bundle.meta.get("bundle_kwargs") or {})
        self.bundle = steps_mod.make_train_bundle(
            self.cfg, self.shape, self.mesh, **kw)
        self.moe_plan = self.bundle.meta.get("moe_plan")
        new_a2a = self._backing_a2a()
        if old_a2a is not None and new_a2a is not None \
                and new_a2a is not old_a2a:
            old_a2a.free()
            EXEC_TELEMETRY.reset_rank_rings(new_a2a.signature.digest)
        if self._skew is not None:
            self._arm_skew_monitor()

    def close(self) -> None:
        """Teardown: drain the async checkpoint writer.  The trainer's
        re-plans run synchronously inside ``_maybe_replan`` (no background
        thread to join — the ``ReplanManager.close()`` analogue for
        manager-driven loops), so this is idempotent and safe to call
        after a faulted run."""
        if self.ckpt is not None:
            self.ckpt.wait()

    def run(self, failure_hook: Optional[Callable[[int], None]] = None) -> dict:
        if self.params is None and not self.try_resume():
            self.init_state()
            self._save(0)

        def on_metrics(step: int, metrics: dict):
            self.history.append({"step": step, **metrics})
            if step % self.tcfg.log_every == 0:
                log.info("step %d  %s", step,
                         "  ".join(f"{k}={v:.4f}" for k, v in metrics.items()))

        def rebuild_plans(err: Exception):
            # Device-loss class: the plan's window + compiled executable
            # are device state the checkpoint does not cover.
            if self._backing_a2a() is not None:
                self._rebuild_bundle()

        def on_recovery(step: int, err: Exception, kind: str):
            self.recoveries.append({"step": step, "kind": kind,
                                    "error": str(err)})

        final = run_with_recovery(
            self._run_one,
            restore=self._restore,
            start_step=self.start_step,
            n_steps=self.tcfg.n_steps - self.start_step,
            policy=RetryPolicy(max_restarts=self.tcfg.max_restarts),
            failure_hook=failure_hook,
            on_metrics=on_metrics,
            rebuild_plans=rebuild_plans,
            on_recovery=on_recovery,
        )
        if self.ckpt is not None:
            self._save(final)
        self.close()
        return {"final_step": final,
                "last_metrics": self.history[-1] if self.history else {},
                "stragglers": len(self.straggler.flagged),
                "recoveries": self.recoveries,
                "replans": self.replan_events,
                "chaos": dict(self.chaos.injected)
                if self.chaos is not None else None,
                "ep_dispatch": self.ep_dispatch_report()}

    def ep_dispatch_report(self) -> dict | None:
        """INIT provenance of the EP dispatch plan (None for non-MoE runs):
        whether it is plan-backed, which variant won, and whether the
        backing plan warm-started from the store — the observable half of
        the ``--plan-store`` contract the CI warm-EP job asserts on."""
        if self.moe_plan is None:
            return None
        a2a = getattr(self.moe_plan, "a2a", None)
        return {
            "plan_backed": a2a is not None,
            "variant": self.moe_plan.variant,
            "codec": self.moe_plan.codec,
            "overlap_chunks": self.moe_plan.overlap_chunks,
            "warm_loaded": bool(a2a.warm_loaded) if a2a is not None else False,
            "auto_choice": getattr(a2a, "auto_choice", None)
            if a2a is not None else None,
        }

"""End-to-end driver: train a reduced OLMoE with expert-parallel MoE
dispatch running through the persistent alltoallv engine, on a
(data=2, model=4) mesh of host devices, with checkpointing + auto-resume.

    PYTHONPATH=src python examples/train_moe_ep.py [n_steps]
"""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import sys
import tempfile

from repro.configs import ShapeConfig, get_reduced
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh
from repro.train import ScheduleConfig, Trainer, TrainerConfig


def main(n_steps: int = 60):
    cfg = get_reduced("olmoe-1b-7b")       # 8 experts, top-2, persistent a2a
    shape = ShapeConfig("moe_ep", "train", seq_len=256, global_batch=8)
    mesh = make_mesh((2, 4), ("data", "model"))   # DP=2, TP/EP=4

    bundle = steps_mod.make_train_bundle(
        cfg, shape, mesh,
        sched=ScheduleConfig(kind="wsd", peak_lr=3e-3, warmup_steps=6,
                             total_steps=n_steps, decay_steps=n_steps // 5))
    plan = bundle.meta["moe_plan"]
    print(f"MoE dispatch plan: EP={plan.ep_size}, {plan.e_local} experts/shard, "
          f"capacity={plan.capacity}, variant={plan.variant}, "
          f"plan_backed={plan.plan_backed}"
          + (f" (warm={plan.a2a.warm_loaded})" if plan.plan_backed else ""))

    with tempfile.TemporaryDirectory() as ckpt_dir:
        trainer = Trainer(bundle, TrainerConfig(
            n_steps=n_steps, ckpt_dir=ckpt_dir, ckpt_every=20, log_every=10))
        result = trainer.run()
        print(f"\nfinished at step {result['final_step']}; "
              f"last: {result['last_metrics']}")
        first = trainer.history[0]["nll"]
        last = trainer.history[-1]["nll"]
        print(f"nll {first:.3f} -> {last:.3f}")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 60)

"""The paper in miniature: persistent RMA-style alltoallv on 8 ranks.

Builds an irregular (hugetrace-like) communication pattern, runs the
non-persistent baseline and the persistent fence / lock / hierarchy plans,
validates every byte against the numpy oracle, and prints the break-even
analysis (paper Eq. 1-3).

    PYTHONPATH=src python examples/persistent_alltoallv.py
"""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import alltoallv_init, breakeven, metadata as md, reference
from repro.core.baseline import make_nonpersistent
from repro.launch.mesh import make_host_mesh, make_mesh


def main():
    p, feature = 8, 128
    rng = np.random.default_rng(7)
    counts = rng.integers(0, 64, size=(p, p))
    counts[:, 5] *= 4                      # one hot receiver (skew)
    print("count matrix (rows=senders):")
    print(counts)

    send_rows = md.round_up(md.max_total_send(counts), 8)
    recv_rows = md.round_up(md.max_total_recv(counts), 8)
    bufs = reference.make_testbufs(counts, (feature,), np.float32, send_rows)
    expect = reference.alltoallv_global(bufs, counts, recv_rows)
    rc = md.recv_counts(counts)

    mesh = make_host_mesh(p)
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, feature)),
                       NamedSharding(mesh, P("x")))

    def validate(out, label):
        got = np.asarray(out).reshape(p, recv_rows, feature)
        for r in range(p):
            n = int(rc[r].sum())
            np.testing.assert_allclose(got[r, :n], expect[r, :n], rtol=1e-6)
        print(f"  {label:24s} validated element-wise")

    # ---- INIT (one-time) + START/WAIT (per-iteration) ----
    plans = {}
    for variant in ("fence", "lock"):
        t0 = time.perf_counter()
        plan = alltoallv_init(counts, (feature,), jnp.float32, mesh,
                              axis="x", variant=variant)
        plan.compile()
        print(f"INIT {variant}: host metadata {plan.init_host_seconds*1e6:.0f} us, "
              f"compile {plan.init_compile_seconds:.2f} s")
        validate(plan.wait(plan.start(x)), f"{variant}_persistent")
        plans[variant] = plan

    mesh2 = make_mesh((2, 4), ("node", "core"))
    x2 = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, feature)),
                        NamedSharding(mesh2, P(("node", "core"))))
    plan_h = alltoallv_init(counts, (feature,), jnp.float32, mesh2,
                            axis=("node", "core"), variant="fence_hierarchy")
    validate(plan_h.wait(plan_h.start(x2)), "fence_hierarchy")

    base = make_nonpersistent(mesh, axis="x", p=p,
                              capacity=plans["fence"].capacity,
                              send_rows=send_rows, recv_rows=recv_rows,
                              feature_shape=(feature,), dtype=jnp.float32)
    cnts = jax.device_put(jnp.asarray(counts.reshape(-1), jnp.int32),
                          NamedSharding(mesh, P("x")))
    validate(base(x, cnts), "nonpersistent baseline")

    # ---- break-even (Eq. 1-3) ----
    print("\nbreak-even analysis:")
    for variant, plan in plans.items():
        be = breakeven.measure(lambda: plan.start(x), lambda: base(x, cnts),
                               t_init=plan.init_host_seconds, iters=30)
        print(f"  {variant:6s}: T_MPI={be.t_mpi*1e6:8.1f} us  "
              f"T_persist={be.t_persist*1e6:8.1f} us  "
              f"savings={be.savings_pct:5.1f}%  N_breakeven={be.n_breakeven}")


if __name__ == "__main__":
    main()

"""Closed loop of persistent alltoallv epochs, as an HPC code calls them.

Set-up: the mesh (one rank per chip), `alltoallv_init` with the engine's
defaults for everything but counts, row shape and dtype (timed as the
plan's INIT), `payloads` distinct send buffers made on the device from the
seed, and one epoch of each.  The window: epoch after epoch, each
`plan.start(x)` then `plan.wait`, rotating through the payloads, until
`--seconds` have passed.  A seeded sample of epochs is copied on the device
as it completes, and the last epoch is kept; once the window has closed,
every valid row of each kept output is compared bit for bit with a plain
numpy alltoallv of the same payload.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import harness, patterns
from chipbench import weights as W
from chipbench.refs import alltoallv as ref


def run(spec: harness.Spec) -> harness.Outcome:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import alltoallv_init
    from repro.launch.mesh import make_mesh

    cfg, tr = spec.config, spec.traffic
    p = int(cfg["ranks"])
    dtype = jnp.dtype(cfg["dtype"])
    row_bytes = int(cfg["row_lanes"]) * dtype.itemsize
    counts = patterns.counts(tr, p, row_bytes)
    mesh = make_mesh((p,), ("x",))

    t0 = time.perf_counter()
    plan = alltoallv_init(counts, (int(cfg["row_lanes"]),), dtype, mesh, axis="x")
    init_s = time.perf_counter() - t0

    n_pay = int(tr["payloads"])
    sharding = NamedSharding(mesh, P("x"))
    shape = plan.global_send_shape
    make = jax.jit(lambda: [W.uniform_jnp(spec.seed, W.stream_id("payload", m),
                                          shape, 1.0).astype(dtype)
                            for m in range(n_pay)],
                   out_shardings=[sharding] * n_pay)
    xs = make()
    copy = jax.jit(lambda a: jnp.array(a, copy=True))
    for x in xs:
        out = plan.wait(plan.start(x))
    jax.block_until_ready(copy(out))
    setup_s = time.perf_counter() - spec.t_start

    rng = np.random.default_rng([spec.seed, 2])
    gap = float(tr["sample_gap"])
    next_snap = int(rng.geometric(1.0 / gap))
    snaps = []
    times = []
    annotate = spec.trace_dir is not None
    k = 0
    with spec.window():
        tw0 = time.perf_counter()
        while True:
            t1 = time.perf_counter()
            if annotate:
                with jax.profiler.TraceAnnotation("epoch"):
                    out = plan.wait(plan.start(xs[k % n_pay]))
            else:
                out = plan.wait(plan.start(xs[k % n_pay]))
            t2 = time.perf_counter()
            times.append(t2 - t1)
            if k == next_snap and len(snaps) < int(tr["max_samples"]):
                snaps.append((k, copy(out)))
                next_snap += int(rng.geometric(1.0 / gap))
            k += 1
            if t2 - tw0 >= spec.seconds:
                break
        window_s = time.perf_counter() - tw0
    snaps.append((k - 1, out))
    peak = harness.memory_peak(mesh.devices.flat)

    send = [np.asarray(x).reshape((p, plan.send_rows) + plan.spec.feature_shape)
            for x in xs]
    expected = [ref.alltoallv(s, counts) for s in send]
    bad_total = failed = 0
    for epoch, snap in snaps:
        recv = np.asarray(snap).reshape((p, plan.recv_rows) + plan.spec.feature_shape)
        bad = ref.mismatches(recv, expected[epoch % n_pay])
        bad_total += bad
        failed += bad > 0
    checks = [harness.Check("mismatched_elements", bad_total, 0, bad_total == 0)]
    metrics = {"epoch_us": window_s / k * 1e6,
               "epoch_p95_us": float(np.quantile(times, 0.95)) * 1e6,
               "setup_s": setup_s}
    layer = {"epochs": k, "counts": counts, "row_bytes": row_bytes,
             "init_s": init_s, "variant": plan.spec.variant,
             "answers_checked": len(snaps)}
    return harness.Outcome(metrics, k, failed, checks, layer, peak)

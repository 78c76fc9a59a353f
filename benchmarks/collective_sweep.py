"""Benchmark: plan-backed allgatherv / reduce-scatter vs raw XLA collectives.

The generalized exchange engine serves three families off one plan core;
this sweep measures the two new ones against the collectives a framework
would otherwise emit, on a ragged counts vector with one hot rank — the
regime the plans exist for (a raw collective must pad every rank to the
hot rank's capacity; the plan's baked tables pack/unpack around it).

  * allgatherv: persistent fence / lock / fence_hierarchy epochs vs one
    raw ``jax.lax.all_gather`` over the same padded bucket.
  * reduce-scatter: persistent fence / lock epochs (reduction fused into
    unpack) vs one raw ``jax.lax.psum_scatter`` over uniform blocks.

Rows sweep 1 KiB -> 8 KiB.  On the CPU shared-memory transport the wire is
effectively free, so deltas track op-dispatch structure rather than
bandwidth — the derived column reports the ratio, not a gated saving.

    python collective_sweep.py [iters] [--json]
"""

import argparse

from _util import Csv, set_host_devices

N_RANKS = 8
P_OUTER, P_INNER = 2, 4
JSON_OUT = "experiments/bench/BENCH_collective_sweep.json"


def ragged_counts(p, seed=5):
    """Ragged with one hot rank: the padding gate for raw collectives."""
    import numpy as np
    rng = np.random.default_rng(seed)
    c = rng.integers(8, 48, p).astype(np.int64)
    c[0] += 64
    return c


def main(iters=30, out="experiments/bench/collective_sweep.csv",
         json_out=None):
    set_host_devices(N_RANKS)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map
    from repro.core import (PlanCache, allgatherv_init, breakeven,
                            metadata as md, patterns, reduce_scatter_init)
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((P_OUTER, P_INNER), ("o", "i"))
    axes = ("o", "i")
    counts = ragged_counts(N_RANKS)
    csv = Csv(out)
    rng = np.random.default_rng(0)

    ag_pat = patterns.get("allgatherv")
    rs_pat = patterns.get("reduce_scatter")
    sc_ag = ag_pat.expand_counts(counts)
    sc_rs = rs_pat.expand_counts(counts)
    cap = md.global_capacity(sc_ag, md.TILE_ROWS)      # same for both: max(c)
    ag_send = ag_pat.send_rows(sc_ag, md.TILE_ROWS)    # == cap (one bucket)
    rs_send = rs_pat.send_rows(sc_rs, md.TILE_ROWS)    # ~ sum(c)

    for feature in (256, 1024, 2048):                  # 1 KiB .. 8 KiB rows
        row_bytes = feature * 4
        cache = PlanCache()

        # --- allgatherv: plans vs one raw all_gather ---------------------
        xg = jax.device_put(
            jnp.asarray(rng.standard_normal((N_RANKS * ag_send, feature)),
                        jnp.float32), NamedSharding(mesh, P(axes)))
        ag_plans = {
            v: allgatherv_init(counts, (feature,), jnp.float32, mesh,
                               axis=axes, variant=v, cache=cache).compile()
            for v in ("fence", "lock", "fence_hierarchy")}

        def ag_raw(t):
            return jax.lax.all_gather(t, axes, axis=0, tiled=True)

        raw_ag = jax.jit(shard_map(ag_raw, mesh=mesh, in_specs=P(axes),
                                   out_specs=P(axes), check_vma=False))
        arms = {v: (lambda p=p_: p.start(xg)) for v, p_ in ag_plans.items()}
        arms["raw"] = lambda: raw_ag(xg)
        times = breakeven.measure_arms(arms, iters=iters, warmup=3, bursts=6)
        for v in ("fence", "lock", "fence_hierarchy"):
            csv.row(f"collective_sweep/allgatherv_{v}/{row_bytes}B",
                    times[v] * 1e6,
                    f"ratio_vs_raw={times[v] / times['raw']:.2f};"
                    "note=cpu_shared_mem_transport_opbound")
        csv.row(f"collective_sweep/allgatherv_raw/{row_bytes}B",
                times["raw"] * 1e6, f"bucket_rows={cap}")

        # --- reduce-scatter: plans vs one raw psum_scatter ---------------
        xr = jax.device_put(
            jnp.asarray(rng.standard_normal((N_RANKS * rs_send, feature)),
                        jnp.float32), NamedSharding(mesh, P(axes)))
        # The raw baseline pads every destination block to the hot rank's
        # capacity (uniform blocks are all psum_scatter can route).
        xu = jax.device_put(
            jnp.asarray(rng.standard_normal(
                (N_RANKS * N_RANKS * cap, feature)), jnp.float32),
            NamedSharding(mesh, P(axes)))
        rs_plans = {
            v: reduce_scatter_init(counts, (feature,), jnp.float32, mesh,
                                   axis=axes, variant=v, cache=cache).compile()
            for v in ("fence", "lock")}

        def rs_raw(t):
            return jax.lax.psum_scatter(t, axes, scatter_dimension=0,
                                        tiled=True)

        raw_rs = jax.jit(shard_map(rs_raw, mesh=mesh, in_specs=P(axes),
                                   out_specs=P(axes), check_vma=False))
        arms = {v: (lambda p=p_: p.start(xr)) for v, p_ in rs_plans.items()}
        arms["raw"] = lambda: raw_rs(xu)
        times = breakeven.measure_arms(arms, iters=iters, warmup=3, bursts=6)
        for v in ("fence", "lock"):
            csv.row(f"collective_sweep/reduce_scatter_{v}/{row_bytes}B",
                    times[v] * 1e6,
                    f"ratio_vs_raw={times[v] / times['raw']:.2f};"
                    "note=cpu_shared_mem_transport_opbound")
        csv.row(f"collective_sweep/reduce_scatter_raw/{row_bytes}B",
                times["raw"] * 1e6,
                f"padded_rows={N_RANKS * cap};real_rows={int(counts.sum())}")
    csv.save()
    if json_out:
        csv.save_json(json_out)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("iters", nargs="?", type=int, default=20)
    ap.add_argument("--json", action="store_true",
                    help=f"also write {JSON_OUT}")
    args = ap.parse_args()
    main(iters=args.iters, json_out=JSON_OUT if args.json else None)

"""Distributed-optimization benchmark: wire compression.

Two sections:

  * int8 gradient all-reduce: fp32 psum against the int8 error-feedback
    compressed psum (``parallel/compression.py``) on a DP mesh — wall time
    plus the wire-byte reduction (4x for fp32 payloads) and the
    quantization error bound.

  * wire-codec exchange sweep (``parallel/wirecodec``): a persistent
    fence-variant alltoallv per codec (identity / bf16 / int8) across a
    per-peer payload sweep, all arms through the shared interleaved
    min-of-bursts estimator, then an Eq.3-style linear transport fit per
    codec (``core.breakeven.size_fits``): ``t(s) = alpha + beta*s`` with
    the fitted crossover payload against identity.  On this host the
    exchange is a shared-memory memcpy, so the fit honestly reports no
    finite crossover (``beta_codec > beta_identity``: the encode/decode
    passes cost more than the bytes they remove) — the same fit run on a
    byte-bound interconnect yields the payload beyond which the codec
    wins, which is the number ``variant="auto"`` acts on per host.
"""

import argparse

from _util import Csv, set_host_devices, time_call

N_RANKS = 8
JSON_OUT = "experiments/bench/BENCH_compression.json"
# Per-peer payload sweep for the codec section (KiB; rows x 256 feat x 4B).
CODEC_PEER_KIB = (16, 64, 256, 1024)
CODEC_ARMS = (("identity", None), ("bf16", 0.004), ("int8", 0.004))


def main(iters=20, n_elems=1 << 20, out="experiments/bench/compression.csv",
         json_out=None):
    set_host_devices(N_RANKS)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map
    from repro.launch.mesh import make_host_mesh
    from repro.parallel import compression

    mesh = make_host_mesh(N_RANKS)
    rng = np.random.default_rng(0)
    g = jax.device_put(
        jnp.asarray(rng.standard_normal((N_RANKS, n_elems)) * 1e-3, jnp.float32),
        NamedSharding(mesh, P("x")))

    def plain(x):
        return jax.lax.psum(x, "x") / N_RANKS

    def comp(x):
        out, _ = compression.compressed_psum(x, "x")
        return out

    f_plain = jax.jit(shard_map(plain, mesh=mesh, in_specs=P("x"),
                                    out_specs=P("x"), check_vma=False))
    f_comp = jax.jit(shard_map(comp, mesh=mesh, in_specs=P("x"),
                                   out_specs=P("x"), check_vma=False))

    csv = Csv(out)
    t0 = time_call(lambda: f_plain(g), iters)
    csv.row("compression/psum_fp32", t0 * 1e6, f"wire_bytes={n_elems*4}")
    t1 = time_call(lambda: f_comp(g), iters)
    err = float(jnp.max(jnp.abs(f_comp(g) - f_plain(g))))
    scale = float(jnp.max(jnp.abs(g)) / 127.0)
    csv.row("compression/psum_int8_ef", t1 * 1e6,
            f"wire_bytes={n_elems};max_err={err:.2e};quant_step={scale:.2e}")

    # --- wire-codec exchange sweep + Eq.3 transport fits ------------------
    from repro.core import api as core_api, breakeven
    from repro.parallel import wirecodec

    d = 256
    per_codec = {name: {} for name, _ in CODEC_ARMS}
    for peer_kib in CODEC_PEER_KIB:
        rows_per_peer = peer_kib * 1024 // (d * 4)
        counts = np.full((N_RANKS, N_RANKS), rows_per_peer, np.int64)
        rows = rows_per_peer * N_RANKS
        x = jax.device_put(
            jnp.asarray(rng.standard_normal((N_RANKS * rows, d)),
                        jnp.float32),
            NamedSharding(mesh, P("x", None)))
        arms = {}
        for codec, tol in CODEC_ARMS:
            plan = core_api.alltoallv_init(
                counts, (d,), jnp.float32, mesh, axis="x", variant="fence",
                codec=codec, error_tol=tol, store=False)
            plan.wait(plan.start(x)).block_until_ready()
            arms[codec] = (lambda p=plan, xx=x: p.wait(p.start(xx)))
        times = breakeven.measure_arms(arms, iters=max(iters // 2, 4),
                                       warmup=2, bursts=3)
        t_id = times["identity"]
        for codec, _ in CODEC_ARMS:
            c = wirecodec.get(codec)
            per_codec[codec][float(peer_kib)] = times[codec]
            csv.row(f"compression/codec_sweep/{codec}/kib{peer_kib}",
                    times[codec] * 1e6,
                    f"peer_kib={peer_kib};wire_kib={peer_kib/c.ratio:.1f};"
                    f"rel_err_bound={c.rel_error:g};"
                    f"saving_vs_identity={100*(t_id-times[codec])/t_id:.1f}%")
    for codec, fit in breakeven.size_fits(per_codec).items():
        cross = fit["crossover_kib_vs_identity"]
        csv.row(f"compression/codec_fit/{codec}",
                fit["alpha_s"] * 1e6,
                f"beta_us_per_kib={fit['beta_s_per_kib']*1e6:.3f};"
                f"crossover_kib_vs_identity="
                f"{'none' if cross is None else f'{cross:.0f}'};"
                f"note=alpha_us_value;transport=xla_cpu_shared_mem")
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

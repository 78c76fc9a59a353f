"""Multi-device correctness cases, executed via subprocess:

    python -m repro.testing.dist_cases <case> [--devices N]

The device count must be fixed before jax initializes, so pytest never sets
it in-process (smoke tests keep seeing 1 device); tests spawn this module
instead.  Each case asserts internally and prints ``CASE_OK <name>``.
"""

import os
import sys

# --- device count BEFORE any jax import -----------------------------------
_n = 8
for i, a in enumerate(sys.argv):
    if a == "--devices" and i + 1 < len(sys.argv):
        _n = int(sys.argv[i + 1])
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + f" --xla_force_host_platform_device_count={_n}")
# The TPU interpreter runs a kernel as host callbacks, one blocked thread per
# device, and a callback's operands are read through the CPU runtime's thread
# pool.  With no more pool threads than devices, a device waiting on a peer's
# semaphore can starve the peer of the thread it needs to start the kernel
# (the fused hierarchy kernel on a (2, 4) grid hangs at 8 threads and passes
# at 9), so give the pool spare threads.
os.environ.setdefault("PJRT_NPROC", str(max(os.cpu_count() or 1, 2 * _n)))

import jax                    # noqa: E402
import jax.numpy as jnp       # noqa: E402
import numpy as np            # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

CASES = {}


def case(fn):
    CASES[fn.__name__] = fn
    return fn


def _setup_pattern(p, seed=0, max_count=13, feature=(4,)):
    from repro.core import metadata as md, reference
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, max_count, size=(p, p))
    send_rows = max(md.round_up(md.max_total_send(counts), 8), 8)
    recv_rows = max(md.round_up(md.max_total_recv(counts), 8), 8)
    bufs = reference.make_testbufs(counts, feature, np.float32, send_rows)
    expect = reference.alltoallv_global(bufs, counts, recv_rows)
    rc = md.recv_counts(counts)
    return counts, bufs, expect, rc, send_rows, recv_rows


def _check(got, expect, rc, p):
    for r in range(p):
        n = int(rc[r].sum())
        np.testing.assert_allclose(got[r, :n], expect[r, :n], rtol=1e-6)


@case
def alltoallv_variants():
    """fence / lock(ring+pairwise) / hierarchy / baseline vs numpy oracle."""
    from repro.core import alltoallv_init, metadata as md
    from repro.core.baseline import make_nonpersistent
    from repro.launch.mesh import make_host_mesh, make_mesh

    p = len(jax.devices())
    counts, bufs, expect, rc, send_rows, recv_rows = _setup_pattern(p)
    mesh = make_host_mesh(p)
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P("x")))

    for variant, kw in [("fence", {}), ("lock", {}),
                        ("lock", {"lock_schedule": "pairwise"})]:
        plan = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x",
                              variant=variant, **kw)
        got = np.asarray(plan.wait(plan.start(x))).reshape(p, recv_rows, 4)
        _check(got, expect, rc, p)

    plan0 = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x")
    exe = make_nonpersistent(mesh, axis="x", p=p, capacity=plan0.capacity,
                             send_rows=send_rows, recv_rows=recv_rows,
                             feature_shape=(4,), dtype=jnp.float32)
    cnts = jax.device_put(jnp.asarray(counts.reshape(-1), jnp.int32),
                          NamedSharding(mesh, P("x")))
    got = np.asarray(jax.block_until_ready(exe(x, cnts))).reshape(p, recv_rows, 4)
    _check(got, expect, rc, p)

    if p % 2 == 0:
        mesh2 = make_mesh((2, p // 2), ("o", "i"))
        x2 = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                            NamedSharding(mesh2, P(("o", "i"))))
        plan = alltoallv_init(counts, (4,), jnp.float32, mesh2, axis=("o", "i"),
                              variant="fence_hierarchy")
        got = np.asarray(plan.wait(plan.start(x2))).reshape(p, recv_rows, 4)
        _check(got, expect, rc, p)


@case
def alltoallv_dtypes_and_features():
    """Shape/dtype sweep for the fence engine."""
    from repro.core import alltoallv_init, metadata as md, reference
    from repro.launch.mesh import make_host_mesh

    p = len(jax.devices())
    mesh = make_host_mesh(p)
    for seed, feature, dtype in [(1, (8,), np.float32), (2, (3, 5), np.float32),
                                 (3, (16,), np.float16), (4, (), np.float32)]:
        rng = np.random.default_rng(seed)
        counts = rng.integers(0, 9, size=(p, p))
        send_rows = max(md.round_up(md.max_total_send(counts), 8), 8)
        recv_rows = max(md.round_up(md.max_total_recv(counts), 8), 8)
        bufs = reference.make_testbufs(counts, feature, dtype, send_rows)
        expect = reference.alltoallv_global(bufs, counts, recv_rows)
        rc = md.recv_counts(counts)
        x = jax.device_put(jnp.asarray(bufs.reshape((p * send_rows,) + feature)),
                           NamedSharding(mesh, P("x")))
        plan = alltoallv_init(counts, feature, bufs.dtype, mesh, axis="x")
        got = np.asarray(plan.wait(plan.start(x))).reshape((p, recv_rows) + feature)
        for r in range(p):
            n = int(rc[r].sum())
            np.testing.assert_allclose(got[r, :n], expect[r, :n], rtol=1e-2)


@case
def plan_and_window_reuse():
    """Plan cache hits, window reuse across epochs, re-INIT on size change."""
    from repro.core import PlanCache, AlltoallvSpec
    from repro.core.api import alltoallv_init
    from repro.core.plan import AlltoallvPlan
    from repro.launch.mesh import make_host_mesh

    p = len(jax.devices())
    mesh = make_host_mesh(p)
    cache = PlanCache()
    counts = np.arange(p * p, dtype=np.int64).reshape(p, p) % 7 + 1
    plan1 = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x", cache=cache)
    plan2 = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x", cache=cache)
    assert plan1 is plan2 and cache.hits == 1 and cache.misses == 1

    x = jax.device_put(jnp.zeros(plan1.global_send_shape, jnp.float32),
                       NamedSharding(mesh, P("x")))
    g0 = plan1.window.generation
    for _ in range(3):
        plan1.wait(plan1.start(x))
    assert plan1.window.generation == max(g0, 1), "window must be reused"

    # same total_recv_bytes, different pattern -> new plan, same window obj
    counts2 = np.roll(counts, 1, axis=1)
    plan3 = alltoallv_init(counts2, (4,), jnp.float32, mesh, axis="x", cache=cache)
    assert plan3 is not plan1
    assert plan3.window is plan1.window, "window cached by recv bytes"

    # changed sizes -> new window
    plan4 = alltoallv_init(counts * 2, (4,), jnp.float32, mesh, axis="x",
                           cache=cache)
    assert plan4.window is not plan1.window


@case
def ragged_backend_lowers():
    """ragged_all_to_all traces + lowers (XLA:CPU cannot execute it)."""
    from repro.core import AlltoallvPlan, AlltoallvSpec
    from repro.launch.mesh import make_host_mesh

    p = len(jax.devices())
    mesh = make_host_mesh(p)
    counts = np.random.default_rng(0).integers(0, 13, size=(p, p))
    spec = AlltoallvSpec(send_counts=counts, feature_shape=(4,),
                         dtype=jnp.float32, axis=("x",), variant="ragged")
    plan = AlltoallvPlan(spec, mesh)
    fn = shard_map(plan.shard_fn, mesh=mesh, in_specs=(P("x"), P("x")),
                       out_specs=P("x"), check_vma=False)
    xs = jax.ShapeDtypeStruct(plan.global_send_shape, jnp.float32,
                              sharding=NamedSharding(mesh, P("x")))
    ws = jax.ShapeDtypeStruct(plan.global_recv_shape, jnp.float32,
                              sharding=NamedSharding(mesh, P("x")))
    txt = jax.jit(fn).lower(xs, ws).as_text()
    assert "ragged_all_to_all" in txt


@case
def rma_kernels():
    """Pallas remote-DMA fence/lock kernels vs oracle (TPU interpret mode)."""
    from repro.kernels import ops, ref
    from repro.launch.mesh import make_host_mesh

    p = len(jax.devices())
    mesh = make_host_mesh(p)
    rng = np.random.default_rng(0)
    for cap, feat in [(8, 100), (16, 128)]:
        packed_all = rng.standard_normal((p, p * cap, feat)).astype(np.float32)
        want = ref.a2a_bucketed_ref(packed_all, p, cap)
        xg = jax.device_put(jnp.asarray(packed_all.reshape(p * p * cap, feat)),
                            NamedSharding(mesh, P("x")))
        for variant in ("fence", "lock"):
            f = shard_map(
                lambda t: ops.rma_alltoallv(t, variant=variant, p=p,
                                            capacity=cap, axis="x",
                                            mesh_axes=("x",)),
                mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False)
            got = np.asarray(f(xg)).reshape(p, p * cap, feat)
            np.testing.assert_allclose(got, want, rtol=1e-6)


@case
def pallas_pack_in_plan():
    """Persistent plan with pack_impl='pallas' matches the oracle."""
    from repro.core import alltoallv_init, metadata as md
    from repro.launch.mesh import make_host_mesh

    p = len(jax.devices())
    counts, bufs, expect, rc, send_rows, recv_rows = _setup_pattern(p, seed=5,
                                                                    max_count=9)
    mesh = make_host_mesh(p)
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P("x")))
    plan = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x",
                          variant="fence", pack_impl="pallas")
    got = np.asarray(plan.wait(plan.start(x))).reshape(p, recv_rows, 4)
    _check(got, expect, rc, p)


@case
def embedded_plan_parity():
    """plan.embed() — the epoch body hosted inside a foreign shard_map —
    produces the same bytes as the standalone START path for every
    (variant, pack_impl) combination on a ragged (non-identity) pattern,
    with padding zeroed (embedded plans have no window to write through)."""
    from repro.core import alltoallv_init
    from repro.launch.mesh import make_host_mesh

    p = len(jax.devices())
    counts, bufs, expect, rc, send_rows, recv_rows = _setup_pattern(p, seed=31,
                                                                    max_count=11)
    mesh = make_host_mesh(p)
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P("x")))
    for variant, impl in [("fence", "jnp"), ("fence", "pallas"),
                          ("fence", "fused"), ("lock", "jnp"),
                          ("lock", "pallas")]:
        plan = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x",
                              variant=variant, pack_impl=impl)
        assert not plan.identity_maps
        want = np.asarray(plan.wait(plan.start(x))).reshape(p, recv_rows, 4)
        fn = shard_map(plan.embed(), mesh=mesh, in_specs=P("x"),
                       out_specs=P("x"), check_vma=False)
        got = np.asarray(jax.jit(fn)(x)).reshape(p, recv_rows, 4)
        for r in range(p):
            n = int(rc[r].sum())
            np.testing.assert_array_equal(got[r, :n], want[r, :n],
                                          err_msg=f"{variant}/{impl}")
            assert not np.abs(got[r, n:]).any(), (variant, impl)

    if p % 2 == 0:
        from repro.launch.mesh import make_mesh
        mesh2 = make_mesh((2, p // 2), ("o", "i"))
        x2 = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                            NamedSharding(mesh2, P(("o", "i"))))
        plan = alltoallv_init(counts, (4,), jnp.float32, mesh2,
                              axis=("o", "i"), variant="fence_hierarchy")
        want = np.asarray(plan.wait(plan.start(x2))).reshape(p, recv_rows, 4)
        fn = shard_map(plan.embed(), mesh=mesh2, in_specs=P(("o", "i")),
                       out_specs=P(("o", "i")), check_vma=False)
        got = np.asarray(jax.jit(fn)(x2)).reshape(p, recv_rows, 4)
        for r in range(p):
            n = int(rc[r].sum())
            np.testing.assert_array_equal(got[r, :n], want[r, :n],
                                          err_msg="fence_hierarchy")


@case
def moe_dispatch_distributed():
    """persistent_a2a (plan-backed) == nonpersistent_a2a == gspmd on a
    (data, model) mesh."""
    import dataclasses

    from repro.configs.base import MoEConfig
    from repro.launch.mesh import make_mesh
    from repro.models import moe as moe_mod
    from repro.parallel.sharding import DEFAULT_RULES, ParamFactory, axis_rules

    mesh = make_mesh((2, 4), ("data", "model"))
    d_model, tokens = 64, 256
    base = MoEConfig(n_experts=8, top_k=2, d_expert=32, capacity_factor=8.0)
    with axis_rules(DEFAULT_RULES, mesh):
        f = ParamFactory(jax.random.key(0), jnp.float32)
        moe_mod.init_moe(f.scope("moe"), d_model, base)
        params = f.params["moe"]
        x = jax.device_put(
            jnp.asarray(np.random.default_rng(0).standard_normal(
                (2, tokens // 2, d_model)), jnp.float32),
            NamedSharding(mesh, P("data", None, None)))
        outs = {}
        for dispatch in ("gspmd", "persistent_a2a", "nonpersistent_a2a"):
            mcfg = dataclasses.replace(base, dispatch=dispatch)
            plan = moe_mod.MoEDispatchPlan.build(mcfg, tokens // 2, mesh,
                                                 d_model=d_model,
                                                 dtype=jnp.float32)
            assert plan.plan_backed == (dispatch == "persistent_a2a")
            y, aux = jax.jit(lambda xx, m=mcfg, pl=plan:
                             moe_mod.apply_moe(params, xx, m, pl))(x)
            outs[dispatch] = np.asarray(y)
        np.testing.assert_allclose(outs["persistent_a2a"], outs["gspmd"],
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(outs["persistent_a2a"],
                                   outs["nonpersistent_a2a"],
                                   rtol=2e-4, atol=2e-5)


@case
def moe_ragged_tail_combine():
    """Pin the post-combine gather-then-slice semantics (moe.py): when the
    per-shard token count is NOT divisible by the EP size, the EP chunks
    carry trailing routing padding and the combine all_gather truncates it
    with a host-static slice.  125 tokens/shard over ep=4 -> t_loc=32,
    3 pad rows; every dispatch path must agree."""
    import dataclasses

    from repro.configs.base import MoEConfig
    from repro.launch.mesh import make_mesh
    from repro.models import moe as moe_mod
    from repro.parallel.sharding import DEFAULT_RULES, ParamFactory, axis_rules

    mesh = make_mesh((2, 4), ("data", "model"))
    d_model, tokens = 64, 250                 # 125/shard, not divisible by 4
    base = MoEConfig(n_experts=8, top_k=2, d_expert=32, capacity_factor=8.0)
    with axis_rules(DEFAULT_RULES, mesh):
        f = ParamFactory(jax.random.key(0), jnp.float32)
        moe_mod.init_moe(f.scope("moe"), d_model, base)
        params = f.params["moe"]
        x = jax.device_put(
            jnp.asarray(np.random.default_rng(0).standard_normal(
                (2, tokens // 2, d_model)), jnp.float32),
            NamedSharding(mesh, P("data", None, None)))
        outs = {}
        for dispatch in ("gspmd", "persistent_a2a", "nonpersistent_a2a"):
            mcfg = dataclasses.replace(base, dispatch=dispatch)
            plan = moe_mod.MoEDispatchPlan.build(mcfg, tokens // 2, mesh,
                                                 d_model=d_model,
                                                 dtype=jnp.float32)
            assert plan.ep_size * plan.tokens_per_shard > tokens // 2, \
                "case must exercise a ragged tail (EP chunks carry padding)"
            y, aux = jax.jit(lambda xx, m=mcfg, pl=plan:
                             moe_mod.apply_moe(params, xx, m, pl))(x)
            outs[dispatch] = np.asarray(y)
        np.testing.assert_allclose(outs["persistent_a2a"], outs["gspmd"],
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(outs["persistent_a2a"],
                                   outs["nonpersistent_a2a"],
                                   rtol=2e-4, atol=2e-5)


def _routed_moe_setup(pattern, d_model, tokens, n_experts, seed=0):
    """MoE params + inputs whose *routing* follows a controlled pattern.

    The router weight is (scaled) identity over the first ``n_experts``
    feature dims, so spiking ``x[t, pref(t)]`` steers token t to expert
    pref(t): ``dense`` spreads tokens uniformly, ``banded`` sends each
    token block to its own expert neighborhood (banded peer counts),
    ``skewed`` funnels 70% of tokens to expert 0 (hot-receiver skew).
    """
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((tokens, d_model)) * 0.1).astype(np.float32)
    if pattern == "dense":
        pref = rng.integers(0, n_experts, tokens)
    elif pattern == "banded":
        pref = ((np.arange(tokens) * n_experts) // tokens
                + rng.integers(0, 2, tokens)) % n_experts
    elif pattern == "skewed":
        pref = np.where(rng.random(tokens) < 0.7, 0,
                        rng.integers(0, n_experts, tokens))
    else:
        raise ValueError(pattern)
    x[np.arange(tokens), pref] += 4.0
    router = (rng.standard_normal((d_model, n_experts)) * 0.05).astype(np.float32)
    router[:n_experts, :n_experts] += 5.0 * np.eye(n_experts, dtype=np.float32)
    return x, router


@case
def moe_plan_backed_parity():
    """Plan-backed persistent dispatch vs the gspmd oracle under controlled
    dense / banded / skewed routing, on both (2, 4) and (4, 2)
    (data, model) meshes — and bit-identical to the table-free
    persistent path (the embedded identity plan compiles to the same
    exchange)."""
    import dataclasses

    from repro.configs.base import MoEConfig
    from repro.launch.mesh import make_mesh
    from repro.models import moe as moe_mod
    from repro.parallel.sharding import DEFAULT_RULES, ParamFactory, axis_rules

    d_model, tokens, e = 64, 256, 8
    # capacity_factor large enough that neither router drops under the 70%
    # skew pattern: gspmd routes globally (capacity C*ep) while persistent
    # routes per EP chunk (capacity C each) — with drops the two
    # implementations legitimately keep different tokens, so drop-free
    # capacity keeps this a parity test of the *exchange*.
    base = MoEConfig(n_experts=e, top_k=2, d_expert=32, capacity_factor=16.0)
    for shape in [(2, 4), (4, 2)]:
        mesh = make_mesh(shape, ("data", "model"))
        with axis_rules(DEFAULT_RULES, mesh):
            f = ParamFactory(jax.random.key(0), jnp.float32)
            moe_mod.init_moe(f.scope("moe"), d_model, base)
            params = f.params["moe"]
            for pattern in ("dense", "banded", "skewed"):
                xnp, router = _routed_moe_setup(pattern, d_model,
                                                tokens, e, seed=3)
                params = dict(params, router=jnp.asarray(router))
                x = jax.device_put(
                    jnp.asarray(xnp.reshape(shape[0], tokens // shape[0],
                                            d_model)),
                    NamedSharding(mesh, P("data", None, None)))
                outs = {}
                for name, dispatch, kw in [
                        ("gspmd", "gspmd", {}),
                        ("plan_backed", "persistent_a2a",
                         {"d_model": d_model, "dtype": jnp.float32}),
                        ("table_free", "persistent_a2a",
                         {"plan_backed": False})]:
                    mcfg = dataclasses.replace(base, dispatch=dispatch)
                    plan = moe_mod.MoEDispatchPlan.build(
                        mcfg, tokens // shape[0], mesh, **kw)
                    y, _ = jax.jit(lambda xx, m=mcfg, pl=plan:
                                   moe_mod.apply_moe(params, xx, m, pl))(x)
                    outs[name] = np.asarray(y)
                assert plan.ep_size == shape[1]
                np.testing.assert_allclose(
                    outs["plan_backed"], outs["gspmd"], rtol=2e-4, atol=2e-5,
                    err_msg=f"{pattern} mesh={shape}")
                np.testing.assert_array_equal(
                    outs["plan_backed"], outs["table_free"],
                    err_msg=f"{pattern} mesh={shape}")


@case
def moe_overlap_invariance():
    """The chunked dispatch->FFN->combine pipeline is BIT-identical across
    overlap depths (the chunks partition the capacity axis and the expert
    FFN is row-independent), and each depth's backing plan is a uniform
    identity-map pattern with the chunk geometry."""
    import dataclasses

    from repro.configs.base import MoEConfig
    from repro.launch.mesh import make_mesh
    from repro.models import moe as moe_mod
    from repro.parallel.sharding import DEFAULT_RULES, ParamFactory, axis_rules

    mesh = make_mesh((2, 4), ("data", "model"))
    d_model, tokens = 64, 256
    base = MoEConfig(n_experts=8, top_k=2, d_expert=32, capacity_factor=8.0,
                     dispatch="persistent_a2a")
    with axis_rules(DEFAULT_RULES, mesh):
        f = ParamFactory(jax.random.key(0), jnp.float32)
        moe_mod.init_moe(f.scope("moe"), d_model, base)
        params = f.params["moe"]
        x = jax.device_put(
            jnp.asarray(np.random.default_rng(7).standard_normal(
                (2, tokens // 2, d_model)), jnp.float32),
            NamedSharding(mesh, P("data", None, None)))
        outs = {}
        for k in (1, 2, 4):
            plan = moe_mod.MoEDispatchPlan.build(
                base, tokens // 2, mesh, d_model=d_model, dtype=jnp.float32,
                overlap_chunks=k)
            assert plan.overlap_chunks == k, (k, plan.overlap_chunks)
            assert plan.plan_backed and plan.a2a.identity_maps
            assert plan.a2a.p == plan.ep_size
            assert plan.a2a.capacity == plan.chunk_peer_rows
            y, _ = jax.jit(lambda xx, pl=plan:
                           moe_mod.apply_moe(params, xx, base, pl))(x)
            outs[k] = np.asarray(y)
        np.testing.assert_array_equal(outs[1], outs[2])
        np.testing.assert_array_equal(outs[1], outs[4])
    print("overlap invariance: depths bit-identical, cap =", plan.capacity)


@case
def moe_planstore_warm_start():
    """The ROADMAP '--plan-store dead flag' contract, closed: a second
    process's EP dispatch INIT (emulated with a fresh PlanCache + fresh
    store handle over the same directory) is warm — store hits > 0, ZERO
    autotune measurement bursts, ZERO host-side table bakes — and resolves
    to the same autotuned variant with an identical dispatch result."""
    import dataclasses
    import tempfile

    from repro.configs.base import MoEConfig
    from repro.core import INIT_STATS, PlanCache
    from repro.launch.mesh import make_mesh
    from repro.models import moe as moe_mod
    from repro.parallel.sharding import DEFAULT_RULES, ParamFactory, axis_rules
    from repro.planstore import PlanStore

    mesh = make_mesh((2, 4), ("data", "model"))
    d_model, tokens = 64, 256
    base = MoEConfig(n_experts=8, top_k=2, d_expert=32, capacity_factor=8.0,
                     dispatch="persistent_a2a", a2a_variant="auto")
    # "auto" on a REAL persistent EP exchange demands the plan-backed form
    # (there is a pattern to measure and no way to resolve it table-free).
    with axis_rules(DEFAULT_RULES, mesh):
        try:
            moe_mod.MoEDispatchPlan.build(base, tokens // 2, mesh,
                                          plan_backed=False)
            raise AssertionError("a2a_variant='auto' without plan backing "
                                 "must raise on a live EP exchange")
        except ValueError:
            pass
    with tempfile.TemporaryDirectory() as d, axis_rules(DEFAULT_RULES, mesh):
        f = ParamFactory(jax.random.key(0), jnp.float32)
        moe_mod.init_moe(f.scope("moe"), d_model, base)
        params = f.params["moe"]
        x = jax.device_put(
            jnp.asarray(np.random.default_rng(0).standard_normal(
                (2, tokens // 2, d_model)), jnp.float32),
            NamedSharding(mesh, P("data", None, None)))

        # --- process 1: cold EP INIT (autotunes, bakes, publishes) -------
        INIT_STATS.reset()
        plan = moe_mod.MoEDispatchPlan.build(
            base, tokens // 2, mesh, d_model=d_model, dtype=jnp.float32,
            store=PlanStore(d), cache=PlanCache(), autotune_iters=4)
        s1 = INIT_STATS.as_dict()
        assert plan.plan_backed and plan.variant in ("fence", "lock")
        assert s1["autotune_bursts"] > 0, s1
        assert s1["table_bakes"] > 0, s1
        assert s1["store_puts"] > 0 and s1["warm_inits"] == 0, s1
        bk = plan.a2a.auto_choice["breakeven"]
        assert bk["sweep_seconds"] > 0 and bk["t_best"] <= bk["t_second"]
        y1, _ = jax.jit(lambda xx, pl=plan:
                        moe_mod.apply_moe(params, xx, base, pl))(x)

        # --- process 2: warm EP INIT (fresh in-memory tiers, same disk) --
        INIT_STATS.reset()
        plan2 = moe_mod.MoEDispatchPlan.build(
            base, tokens // 2, mesh, d_model=d_model, dtype=jnp.float32,
            store=PlanStore(d), cache=PlanCache(), autotune_iters=4)
        s2 = INIT_STATS.as_dict()
        assert s2["autotune_bursts"] == 0, s2
        assert s2["table_bakes"] == 0, s2
        assert s2["store_hits"] > 0 and s2["warm_inits"] >= 1, s2
        assert plan2.a2a.warm_loaded and plan2.variant == plan.variant
        assert plan2.a2a.auto_choice["variant"] == \
            plan.a2a.auto_choice["variant"]
        y2, _ = jax.jit(lambda xx, pl=plan2:
                        moe_mod.apply_moe(params, xx, base, pl))(x)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    print("moe planstore warm-start:", s2)


@case
def compression_distributed():
    """int8 EF psum ~= fp32 psum within quantization error bound."""
    from repro.launch.mesh import make_host_mesh
    from repro.parallel import compression

    p = len(jax.devices())
    mesh = make_host_mesh(p)
    rng = np.random.default_rng(0)
    g = jax.device_put(jnp.asarray(rng.standard_normal((p, 4096)), jnp.float32),
                       NamedSharding(mesh, P("x")))

    def plain(x):
        return jax.lax.psum(x, "x") / p

    def comp(x):
        out, err = compression.compressed_psum(x, "x")
        return out, err

    f0 = jax.jit(shard_map(plain, mesh=mesh, in_specs=P("x"),
                               out_specs=P("x"), check_vma=False))
    f1 = jax.jit(shard_map(comp, mesh=mesh, in_specs=P("x"),
                               out_specs=(P("x"), P("x")), check_vma=False))
    want = np.asarray(f0(g))
    got, err = f1(g)
    # per-rank quant step bounds the error of the mean
    step = float(jnp.max(jnp.abs(g))) / 127.0
    assert float(jnp.max(jnp.abs(np.asarray(got) - want))) <= step, \
        "compressed mean outside quantization bound"
    assert float(jnp.max(jnp.abs(err))) <= step / 2 + 1e-7


@case
def elastic_reshard():
    """Checkpoint saved under one sharding restores under another."""
    import tempfile

    from repro.ckpt.manager import CheckpointManager
    from repro.ckpt.reshard import put_tree
    from repro.launch.mesh import make_host_mesh, make_mesh

    p = len(jax.devices())
    mesh_a = make_host_mesh(p)          # 1-D
    mesh_b = make_mesh((2, p // 2), ("data", "model"))
    tree = {"w": jnp.arange(64 * 8, dtype=jnp.float32).reshape(64, 8),
            "b": jnp.ones((8,), jnp.float32)}
    placed = put_tree(tree, {"w": NamedSharding(mesh_a, P("x")),
                             "b": NamedSharding(mesh_a, P())})
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(7, {"params": placed}, extras={"note": "reshard"})
        step, trees, extras = mgr.load()
        assert step == 7 and extras["note"] == "reshard"
        re = put_tree(trees["params"],
                      {"w": NamedSharding(mesh_b, P("data", "model")),
                       "b": NamedSharding(mesh_b, P("model"))})
        np.testing.assert_array_equal(np.asarray(re["w"]), np.asarray(tree["w"]))
        assert re["w"].sharding.spec == P("data", "model")


@case
def ulysses_attention_matches_local():
    """Sequence-parallel (Ulysses) attention == single-device attention."""
    from repro.launch.mesh import make_mesh
    from repro.models import ulysses
    from repro.parallel.sharding import use_mesh

    mesh = make_mesh((4,), ("model",))
    b, s, h, d = 2, 32, 4, 8
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    q, k, v = mk(), mk(), mk()
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    want = np.asarray(ulysses._attend(q, k, v, pos, True))
    with use_mesh(mesh):
        plan = ulysses.UlyssesPlan.build(h, d, mesh, axis="model")
        assert plan.p == 4
        qs = jax.device_put(q, NamedSharding(mesh, P(None, "model")))
        ks = jax.device_put(k, NamedSharding(mesh, P(None, "model")))
        vs = jax.device_put(v, NamedSharding(mesh, P(None, "model")))
        got = np.asarray(ulysses.ulysses_attention(qs, ks, vs, pos, plan))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@case
def hierarchical_psum():
    """Pod-aware reduce == flat psum mean."""
    from repro.launch.mesh import make_mesh
    from repro.parallel.collectives import flat_psum_mean, hierarchical_psum_mean

    mesh = make_mesh((2, 4), ("pod", "data"))
    x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 16, 32)),
                    jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P(("pod", "data"))))

    def hier(t):
        return hierarchical_psum_mean(t, inner_axis="data", outer_axis="pod",
                                      scatter_dim=1)

    def hier_plan(t):
        # the plan-backed RS+AG pair (persistent plans over "data")
        return hierarchical_psum_mean(t, inner_axis="data", outer_axis="pod",
                                      scatter_dim=1, mesh=mesh)

    def flat(t):
        return flat_psum_mean(t, ("pod", "data"))

    fh = jax.jit(shard_map(hier, mesh=mesh, in_specs=P(("pod", "data")),
                               out_specs=P(("pod", "data")), check_vma=False))
    fp = jax.jit(shard_map(hier_plan, mesh=mesh, in_specs=P(("pod", "data")),
                               out_specs=P(("pod", "data")), check_vma=False))
    ff = jax.jit(shard_map(flat, mesh=mesh, in_specs=P(("pod", "data")),
                               out_specs=P(("pod", "data")), check_vma=False))
    np.testing.assert_allclose(np.asarray(fh(xs)), np.asarray(ff(xs)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(fp(xs)), np.asarray(ff(xs)),
                               rtol=1e-5, atol=1e-6)
    # the hierarchical schedule really reduce-scatters: check HLO
    txt = jax.jit(shard_map(hier, mesh=mesh, in_specs=P(("pod", "data")),
                                out_specs=P(("pod", "data")),
                                check_vma=False)).lower(xs).compile().as_text()
    assert "reduce-scatter" in txt or "all-to-all" in txt


@case
def allgatherv_plan_parity():
    """Plan-backed allgatherv (fence / lock / fence_hierarchy) vs the
    pattern's numpy oracle on ragged counts (one empty rank, one hot
    rank), on both the flat and the (2, p//2) grouped mesh."""
    from repro.core import allgatherv_init, metadata as md, patterns
    from repro.launch.mesh import make_host_mesh, make_mesh

    p = len(jax.devices())
    pat = patterns.get("allgatherv")
    counts = np.asarray([0, 29] + [7] * (p - 2), np.int64)[:p]
    sc = pat.expand_counts(counts)
    send_rows = pat.send_rows(sc, md.TILE_ROWS)
    recv_rows = pat.recv_rows(sc, md.TILE_ROWS)
    rng = np.random.default_rng(11)
    bufs = np.zeros((p, send_rows, 4), np.float32)
    for i in range(p):
        bufs[i, : counts[i]] = rng.standard_normal((counts[i], 4))
    expect = pat.reference(bufs, counts, recv_rows)
    n = int(counts.sum())

    mesh = make_host_mesh(p)
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P("x")))
    for variant in ("fence", "lock"):
        plan = allgatherv_init(counts, (4,), jnp.float32, mesh, axis="x",
                               variant=variant)
        assert plan.spec.collective == "allgatherv"
        got = np.asarray(plan.wait(plan.start(x))).reshape(p, recv_rows, 4)
        np.testing.assert_array_equal(got[:, :n], expect[:, :n])

    if p % 2 == 0:
        mesh2 = make_mesh((2, p // 2), ("o", "i"))
        x2 = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                            NamedSharding(mesh2, P(("o", "i"))))
        plan = allgatherv_init(counts, (4,), jnp.float32, mesh2,
                               axis=("o", "i"), variant="fence_hierarchy")
        got = np.asarray(plan.wait(plan.start(x2))).reshape(p, recv_rows, 4)
        np.testing.assert_array_equal(got[:, :n], expect[:, :n])
    print("allgatherv plan parity: ok")


@case
def reduce_scatter_grad_parity():
    """Plan-backed reduce-scatter vs ``jax.lax.psum_scatter`` — BIT
    comparison on integer-valued float payloads (order-independent sums),
    plus an exact ragged-counts check against the pattern oracle."""
    from repro.core import metadata as md, patterns, reduce_scatter_init
    from repro.launch.mesh import make_host_mesh

    p = len(jax.devices())
    pat = patterns.get("reduce_scatter")
    mesh = make_host_mesh(p)
    rng = np.random.default_rng(7)

    # --- uniform tile-aligned counts: bit-compare vs lax.psum_scatter ----
    c = 2 * md.TILE_ROWS
    bufs = rng.integers(-64, 64, (p, p * c, 4)).astype(np.float32)
    x = jax.device_put(jnp.asarray(bufs.reshape(p * p * c, 4)),
                       NamedSharding(mesh, P("x")))
    for variant in ("fence", "lock"):
        plan = reduce_scatter_init(np.full(p, c, np.int64), (4,), jnp.float32,
                                   mesh, axis="x", variant=variant)
        assert plan.spec.collective == "reduce_scatter"
        got = np.asarray(plan.wait(plan.start(x)))

        def ps(t):
            return jax.lax.psum_scatter(t, "x", scatter_dimension=0,
                                        tiled=True)

        ref = np.asarray(jax.jit(shard_map(
            ps, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
            check_vma=False))(x))
        np.testing.assert_array_equal(got, ref)   # bitwise: integer floats

    # --- ragged counts: exact vs the pattern's numpy oracle --------------
    counts = np.asarray([5, 0, 21] + [9] * (p - 3), np.int64)[:p]
    sc = pat.expand_counts(counts)
    send_rows = pat.send_rows(sc, md.TILE_ROWS)
    recv_rows = pat.recv_rows(sc, md.TILE_ROWS)
    bufs = np.zeros((p, send_rows, 4), np.float32)
    tot = int(counts.sum())
    bufs[:, :tot] = rng.integers(-32, 32, (p, tot, 4)).astype(np.float32)
    expect = pat.reference(bufs, counts, recv_rows)
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P("x")))
    plan = reduce_scatter_init(counts, (4,), jnp.float32, mesh, axis="x")
    got = np.asarray(plan.wait(plan.start(x))).reshape(p, recv_rows, 4)
    for j in range(p):
        np.testing.assert_array_equal(got[j, : counts[j]],
                                      expect[j, : counts[j]])
    print("reduce-scatter grad parity: ok")


@case
def gatherv_planstore_warm_start():
    """A second process (fresh cache, same store dir) building the same
    allgatherv plan performs zero autotune bursts and zero table bakes —
    the collective-keyed artifact round-trips through the store."""
    import tempfile

    from repro.core import INIT_STATS, PlanCache, allgatherv_init, \
        metadata as md, patterns
    from repro.launch.mesh import make_host_mesh
    from repro.planstore import PlanStore

    p = len(jax.devices())
    pat = patterns.get("allgatherv")
    counts = np.asarray([3, 17] + [11] * (p - 2), np.int64)[:p]  # non-identity
    sc = pat.expand_counts(counts)
    send_rows = pat.send_rows(sc, md.TILE_ROWS)
    recv_rows = pat.recv_rows(sc, md.TILE_ROWS)
    rng = np.random.default_rng(23)
    bufs = np.zeros((p, send_rows, 4), np.float32)
    for i in range(p):
        bufs[i, : counts[i]] = rng.standard_normal((counts[i], 4))
    expect = pat.reference(bufs, counts, recv_rows)
    n = int(counts.sum())
    mesh = make_host_mesh(p)
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P("x")))

    with tempfile.TemporaryDirectory() as d:
        INIT_STATS.reset()
        plan = allgatherv_init(counts, (4,), jnp.float32, mesh, axis="x",
                               variant="auto", cache=PlanCache(),
                               store=PlanStore(d), autotune_iters=4)
        assert INIT_STATS.table_bakes > 0 and INIT_STATS.autotune_bursts > 0
        assert INIT_STATS.store_puts > 0 and INIT_STATS.warm_inits == 0
        assert plan.signature.collective == "allgatherv"
        got = np.asarray(plan.wait(plan.start(x))).reshape(p, recv_rows, 4)
        np.testing.assert_array_equal(got[:, :n], expect[:, :n])

        INIT_STATS.reset()
        plan2 = allgatherv_init(counts, (4,), jnp.float32, mesh, axis="x",
                                variant="auto", cache=PlanCache(),
                                store=PlanStore(d), autotune_iters=4)
        assert INIT_STATS.autotune_bursts == 0, INIT_STATS.as_dict()
        assert INIT_STATS.table_bakes == 0, INIT_STATS.as_dict()
        assert INIT_STATS.warm_inits >= 1 and INIT_STATS.store_hits >= 1
        assert plan2.warm_loaded and plan2.spec.variant == plan.spec.variant
        got2 = np.asarray(plan2.wait(plan2.start(x))).reshape(p, recv_rows, 4)
        np.testing.assert_array_equal(got2[:, :n], expect[:, :n])
    print("gatherv planstore warm-start:", INIT_STATS.as_dict())


def _banded_counts(p, width=1, base=11, seed=3):
    """Sparse ring-banded pattern: counts only within ``width`` ring hops."""
    rng = np.random.default_rng(seed)
    c = np.zeros((p, p), np.int64)
    for i in range(p):
        for d in range(-width, width + 1):
            c[i, (i + d) % p] = rng.integers(1, base)
    return c


@case
def sparse_lock_elision():
    """Zero-capacity lock rounds are skipped and the output is identical to
    both the numpy oracle and the unelided (full-capacity) exchange."""
    from repro.core import alltoallv_init, metadata as md, reference
    from repro.core.baseline import make_nonpersistent
    from repro.launch.mesh import make_host_mesh

    p = len(jax.devices())
    counts = _banded_counts(p, width=1)
    send_rows = max(md.round_up(md.max_total_send(counts), 8), 8)
    recv_rows = max(md.round_up(md.max_total_recv(counts), 8), 8)
    bufs = reference.make_testbufs(counts, (4,), np.float32, send_rows)
    expect = reference.alltoallv_global(bufs, counts, recv_rows)
    rc = md.recv_counts(counts)
    mesh = make_host_mesh(p)
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P("x")))

    plan = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x",
                          variant="lock")
    if p > 3:
        # ring width 1 -> only offsets {1, p-1} carry data
        assert plan.lock_rounds_active == 2, plan.lock_rounds_active
        assert plan.lock_rounds_active < plan.lock_rounds_total
    got = np.asarray(plan.wait(plan.start(x))).reshape(p, recv_rows, 4)
    _check(got, expect, rc, p)

    # Unelided exchange (non-persistent: every round at global capacity)
    exe = make_nonpersistent(mesh, axis="x", p=p, capacity=plan.capacity,
                             send_rows=send_rows, recv_rows=recv_rows,
                             feature_shape=(4,), dtype=jnp.float32,
                             variant="lock")
    cnts = jax.device_put(jnp.asarray(counts.reshape(-1), jnp.int32),
                          NamedSharding(mesh, P("x")))
    full = np.asarray(jax.block_until_ready(exe(x, cnts))).reshape(
        p, recv_rows, 4)
    for r in range(p):
        n = int(rc[r].sum())
        np.testing.assert_array_equal(got[r, :n], full[r, :n])


@case
def hierarchy_local_elision():
    """All-local pattern: the outer-stage collective is elided at INIT and
    the result still matches the oracle (and the lowered program has fewer
    all-to-alls than the remote-needed plan)."""
    from repro.core import alltoallv_init, metadata as md, reference
    from repro.launch.mesh import make_mesh

    p = len(jax.devices())
    assert p % 2 == 0
    p_outer, p_inner = 2, p // 2
    rng = np.random.default_rng(4)
    counts = np.zeros((p, p), np.int64)
    for g in range(p_outer):          # only within-outer-group traffic
        lo, hi = g * p_inner, (g + 1) * p_inner
        counts[lo:hi, lo:hi] = rng.integers(0, 9, (p_inner, p_inner))
    send_rows = max(md.round_up(md.max_total_send(counts), 8), 8)
    recv_rows = max(md.round_up(md.max_total_recv(counts), 8), 8)
    bufs = reference.make_testbufs(counts, (4,), np.float32, send_rows)
    expect = reference.alltoallv_global(bufs, counts, recv_rows)
    rc = md.recv_counts(counts)

    mesh = make_mesh((p_outer, p_inner), ("o", "i"))
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P(("o", "i"))))
    plan = alltoallv_init(counts, (4,), jnp.float32, mesh, axis=("o", "i"),
                          variant="fence_hierarchy")
    assert plan.hierarchy_remote_needed is False
    got = np.asarray(plan.wait(plan.start(x))).reshape(p, recv_rows, 4)
    _check(got, expect, rc, p)

    # The elided program must lower strictly fewer all-to-alls than the same
    # pattern with one cross-group row (which forces the remote stage).
    counts_x = counts.copy()
    counts_x[0, p_inner] = 1          # one row crossing the outer boundary
    plan_x = alltoallv_init(counts_x, (4,), jnp.float32, mesh,
                            axis=("o", "i"), variant="fence_hierarchy")
    assert plan_x.hierarchy_remote_needed is True
    import re
    def n_a2a(pl_):   # op definitions, robust to sync/async HLO spellings
        txt = pl_.compile()._compiled.as_text()
        return len(re.findall(r"%all-to-all(?:-start)?[.\d]* = ", txt))
    n_local, n_cross = n_a2a(plan), n_a2a(plan_x)
    assert n_local < n_cross, (n_local, n_cross)


@case
def fused_pack_fence():
    """pack_impl='fused' (fused gather+put kernel, TPU interpret mode here)
    matches the oracle."""
    from repro.core import alltoallv_init
    from repro.launch.mesh import make_host_mesh

    p = len(jax.devices())
    counts, bufs, expect, rc, send_rows, recv_rows = _setup_pattern(p, seed=9,
                                                                    max_count=9)
    mesh = make_host_mesh(p)
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P("x")))
    plan = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x",
                          variant="fence", pack_impl="fused")
    got = np.asarray(plan.wait(plan.start(x))).reshape(p, recv_rows, 4)
    _check(got, expect, rc, p)


@case
def pipelined_epochs():
    """start_pipelined alternates window slots; every epoch's output is
    correct and slots really double-buffer (distinct device buffers)."""
    from repro.core import alltoallv_init, metadata as md, reference
    from repro.launch.mesh import make_host_mesh

    p = len(jax.devices())
    counts, bufs, expect, rc, send_rows, recv_rows = _setup_pattern(p, seed=11)
    mesh = make_host_mesh(p)
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P("x")))
    plan = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x")

    # Pipeline: epoch k+1 dispatches before epoch k's output is consumed.
    # The exposure rule: epoch k's output (slot k%2) is donated to epoch
    # k+2, so each output must be read before two further starts.
    prev = plan.start_pipelined(x)
    for _ in range(3):
        cur = plan.start_pipelined(x)          # in flight alongside prev
        got = np.asarray(plan.wait(prev)).reshape(p, recv_rows, 4)
        _check(got, expect, rc, p)
        prev = cur
    got = np.asarray(plan.wait(prev)).reshape(p, recv_rows, 4)
    _check(got, expect, rc, p)
    assert len(plan.window._slots) == 2, "double buffering must use 2 slots"


@case
def hier_combined_parity():
    """Leader-combined hierarchy vs oracle AND vs the flat fence plan on
    dense / banded / skewed patterns, over both (2, P/2) and (P/2, 2)
    factorizations; the instrumented cross-group put counter must scale as
    O((P/g)^2) (flat fence posts P*(P-1) puts)."""
    from repro.core import alltoallv_init, metadata as md, reference
    from repro.launch.mesh import make_mesh

    p = len(jax.devices())
    assert p % 2 == 0
    rng = np.random.default_rng(21)
    dense = rng.integers(1, 13, (p, p))
    banded = _banded_counts(p, width=1)
    skewed = rng.integers(0, 4, (p, p))
    skewed[:, p - 1] *= 9
    skewed[0, :] *= 5

    for p_outer in dict.fromkeys((2, p // 2)):   # distinct factorizations only
        p_inner = p // p_outer
        mesh = make_mesh((p_outer, p_inner), ("o", "i"))
        for name, counts in [("dense", dense), ("banded", banded),
                             ("skewed", skewed)]:
            send_rows = max(md.round_up(md.max_total_send(counts), 8), 8)
            recv_rows = max(md.round_up(md.max_total_recv(counts), 8), 8)
            bufs = reference.make_testbufs(counts, (4,), np.float32, send_rows)
            expect = reference.alltoallv_global(bufs, counts, recv_rows)
            rc = md.recv_counts(counts)
            x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                               NamedSharding(mesh, P(("o", "i"))))

            plan_h = alltoallv_init(counts, (4,), jnp.float32, mesh,
                                    axis=("o", "i"), variant="fence_hierarchy")
            got = np.asarray(plan_h.wait(plan_h.start(x))).reshape(p, recv_rows, 4)
            _check(got, expect, rc, p)

            # vs the flat fence plan on the same linearized axis pair
            plan_f = alltoallv_init(counts, (4,), jnp.float32, mesh,
                                    axis=("o", "i"), variant="fence")
            flat = np.asarray(plan_f.wait(plan_f.start(x))).reshape(p, recv_rows, 4)
            for r in range(p):
                n = int(rc[r].sum())
                np.testing.assert_array_equal(got[r, :n], flat[r, :n],
                                              err_msg=f"{name} p_outer={p_outer}")

            # instrumented counter: combined message count is O((P/g)^2)
            assert plan_h.cross_group_puts <= p_outer * (p_outer - 1), \
                (name, p_outer, plan_h.cross_group_puts)
            assert plan_h.cross_group_puts < p * (p - 1)
            if name == "dense":
                assert plan_h.cross_group_puts == p_outer * (p_outer - 1)

    # Fused leader stage (Pallas kernel, TPU interpret mode) on the (2, P/2)
    # grid of all devices and on a (2, 2) grid of the first four (the
    # geometry of a four-chip host).
    from jax.sharding import Mesh
    for pf, grid in dict.fromkeys([(p, (2, p // 2)), (4, (2, 2))]):
        mesh = Mesh(np.array(jax.devices()[:pf]).reshape(grid), ("o", "i"))
        sub = dense[:pf, :pf]
        send_rows = max(md.round_up(md.max_total_send(sub), 8), 8)
        recv_rows = max(md.round_up(md.max_total_recv(sub), 8), 8)
        bufs = reference.make_testbufs(sub, (4,), np.float32, send_rows)
        expect = reference.alltoallv_global(bufs, sub, recv_rows)
        x = jax.device_put(jnp.asarray(bufs.reshape(pf * send_rows, 4)),
                           NamedSharding(mesh, P(("o", "i"))))
        plan_fh = alltoallv_init(sub, (4,), jnp.float32, mesh,
                                 axis=("o", "i"), variant="fence_hierarchy",
                                 pack_impl="fused")
        got = np.asarray(plan_fh.wait(plan_fh.start(x))).reshape(
            pf, recv_rows, 4)
        _check(got, expect, md.recv_counts(sub), pf)


@case
def auto_variant_dispatch():
    """variant="auto" measures fence/lock/hierarchy at INIT, returns a
    correct plan, records per-candidate timings, and caches the decision
    per PatternSignature (a second init is a pure cache hit)."""
    from repro.core import PlanCache, alltoallv_init, metadata as md, reference
    from repro.launch.mesh import make_host_mesh, make_mesh

    p = len(jax.devices())
    counts, bufs, expect, rc, send_rows, recv_rows = _setup_pattern(p, seed=13)
    cache = PlanCache()

    # 1-D mesh: candidates are fence/lock
    mesh = make_host_mesh(p)
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P("x")))
    plan = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x",
                          variant="auto", cache=cache, autotune_iters=6)
    from repro.core.autotune import ragged_alltoall_executes
    flat_cands = {"fence", "lock"} | (
        {"ragged"} if ragged_alltoall_executes() else set())
    assert set(plan.auto_choice["times"]) == flat_cands
    assert plan.spec.variant == plan.auto_choice["variant"]
    got = np.asarray(plan.wait(plan.start(x))).reshape(p, recv_rows, 4)
    _check(got, expect, rc, p)
    plan2 = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x",
                           variant="auto", cache=cache)
    assert plan2 is plan and len(cache.auto_choices) == 1

    # grouped mesh: hierarchy joins the candidate set
    if p % 2 == 0:
        mesh2 = make_mesh((2, p // 2), ("o", "i"))
        x2 = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                            NamedSharding(mesh2, P(("o", "i"))))
        plan3 = alltoallv_init(counts, (4,), jnp.float32, mesh2,
                               axis=("o", "i"), variant="auto", cache=cache,
                               autotune_iters=6)
        assert set(plan3.auto_choice["times"]) == {"fence", "lock",
                                                   "fence_hierarchy"}
        got = np.asarray(plan3.wait(plan3.start(x2))).reshape(p, recv_rows, 4)
        _check(got, expect, rc, p)


@case
def auto_ragged_candidate():
    """ragged joins the variant="auto" candidate set exactly when
    the backend can execute lax.ragged_all_to_all: excluded (and never
    measured) on CPU, included when the gate passes."""
    from repro.core import AlltoallvSpec, PlanCache, alltoallv_init, autotune
    from repro.launch.mesh import make_host_mesh

    p = len(jax.devices())
    counts, bufs, expect, rc, send_rows, recv_rows = _setup_pattern(p, seed=17)
    mesh = make_host_mesh(p)
    spec = AlltoallvSpec(send_counts=counts, feature_shape=(4,),
                         dtype=jnp.float32, axis=("x",))

    cands = autotune.candidate_variants(spec, mesh)
    assert ("ragged" in cands) == autotune.ragged_alltoall_executes()

    # End-to-end: auto measures exactly the candidate set for this host —
    # on a CPU container that means ragged was *not* measured.
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P("x")))
    plan = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x",
                          variant="auto", cache=PlanCache(), autotune_iters=4)
    assert set(plan.auto_choice["times"]) == set(cands)
    got = np.asarray(plan.wait(plan.start(x))).reshape(p, recv_rows, 4)
    _check(got, expect, rc, p)

    # Force the gate: with executability faked, the candidate fold-in logic
    # includes ragged on a single axis and keeps it off grouped specs (the
    # ragged spec takes one mesh axis).
    orig = autotune.ragged_alltoall_executes
    autotune.ragged_alltoall_executes = lambda: True
    try:
        assert "ragged" in autotune.candidate_variants(spec, mesh)
        if p % 2 == 0:
            from repro.launch.mesh import make_mesh
            mesh2 = make_mesh((2, p // 2), ("o", "i"))
            spec2 = AlltoallvSpec(send_counts=counts, feature_shape=(4,),
                                  dtype=jnp.float32, axis=("o", "i"))
            assert "ragged" not in autotune.candidate_variants(spec2, mesh2)
    finally:
        autotune.ragged_alltoall_executes = orig


@case
def planstore_warm_start():
    """Cross-process warm-start (emulated by discarding every in-memory
    tier): a second INIT of an identical pattern against the store the
    first run populated performs zero autotune measurement bursts and zero
    host-side table bakes, and its output matches the oracle."""
    import tempfile

    from repro.core import INIT_STATS, PlanCache, alltoallv_init
    from repro.launch.mesh import make_mesh
    from repro.planstore import PlanStore
    from repro.planstore.schema import store_key

    p = len(jax.devices())
    assert p % 2 == 0, "warm-start case needs an even device count"
    counts, bufs, expect, rc, send_rows, recv_rows = _setup_pattern(p, seed=21)
    mesh = make_mesh((2, p // 2), ("o", "i"))
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P(("o", "i"))))

    with tempfile.TemporaryDirectory() as d:
        # --- run 1: cold (populates the store) ---------------------------
        INIT_STATS.reset()
        plan = alltoallv_init(counts, (4,), jnp.float32, mesh,
                              axis=("o", "i"), variant="auto",
                              cache=PlanCache(), store=PlanStore(d),
                              autotune_iters=4)
        assert INIT_STATS.table_bakes > 0
        assert INIT_STATS.autotune_bursts > 0
        assert INIT_STATS.store_puts > 0 and INIT_STATS.warm_inits == 0
        got = np.asarray(plan.wait(plan.start(x))).reshape(p, recv_rows, 4)
        _check(got, expect, rc, p)

        # --- run 2: warm (fresh cache + fresh store handle, same disk) ---
        INIT_STATS.reset()
        plan2 = alltoallv_init(counts, (4,), jnp.float32, mesh,
                               axis=("o", "i"), variant="auto",
                               cache=PlanCache(), store=PlanStore(d),
                               autotune_iters=4)
        assert INIT_STATS.autotune_bursts == 0, INIT_STATS.as_dict()
        assert INIT_STATS.table_bakes == 0, INIT_STATS.as_dict()
        assert INIT_STATS.warm_inits >= 1 and INIT_STATS.store_hits >= 1
        assert plan2.spec.variant == plan.spec.variant
        assert plan2.warm_loaded
        got2 = np.asarray(plan2.wait(plan2.start(x))).reshape(p, recv_rows, 4)
        _check(got2, expect, rc, p)

        # --- stale-environment store: jax-version mismatch = cold INIT ---
        stale = PlanStore(d, jax_ver="0.0.0-other")
        sig = plan2.signature
        assert stale.path_for(sig) != PlanStore(d).path_for(sig)
        assert store_key(sig) != store_key(sig, jax_ver="0.0.0-other")
        INIT_STATS.reset()
        plan3 = alltoallv_init(counts, (4,), jnp.float32, mesh,
                               axis=("o", "i"),
                               variant=plan.spec.variant,
                               cache=PlanCache(), store=stale)
        assert not plan3.warm_loaded and INIT_STATS.table_bakes > 0
    print("planstore warm-start:", INIT_STATS.as_dict())


@case
def planstore_fleet_prewarm():
    """Fleet-shared store end to end: INIT requests captured on one "dryrun
    host" (``core.capture_init_requests``), prewarmed host-side into a
    remote-semantics store (``planstore.prewarm``), then a "fresh replica"
    — empty local cache tiered in front of that remote — performs a fully
    warm INIT for the prewarmed pattern: zero autotune bursts, zero table
    bakes, store hits > 0, output matches the oracle.  The promotion also
    leaves the local tier serving memmapped entries with the remote down."""
    import tempfile

    from repro.core import (INIT_STATS, PlanCache, alltoallv_init,
                            capture_init_requests)
    from repro.launch.mesh import make_mesh
    from repro.planstore import FsRemoteBackend, PlanStore, TieredPlanStore
    from repro.planstore import prewarm as pw

    p = len(jax.devices())
    assert p % 2 == 0, "fleet-prewarm case needs an even device count"
    counts, bufs, expect, rc, send_rows, recv_rows = _setup_pattern(p, seed=33)
    mesh = make_mesh((2, p // 2), ("o", "i"))
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P(("o", "i"))))

    with tempfile.TemporaryDirectory() as remote_dir, \
            tempfile.TemporaryDirectory() as local_dir:
        # --- "dryrun host": capture the request, no store involved -------
        with capture_init_requests() as reqs:
            alltoallv_init(counts, (4,), jnp.float32, mesh, axis=("o", "i"),
                           variant="auto", cache=PlanCache(), store=False,
                           autotune_iters=4)
        assert len(reqs) == 1 and reqs[0]["variant"] == "auto"

        # --- "deploy host": prewarm the remote store from the records ----
        report = pw.prewarm(
            reqs, PlanStore(FsRemoteBackend(remote_dir, latency_ms=0.2)),
            autotune_iters=4)
        assert report["prewarmed"] and not report["skipped"]
        assert report["store"]["puts"] > 0

        # --- "fresh replica": empty local cache, remote-only artifacts ---
        INIT_STATS.reset()
        tiered = TieredPlanStore(PlanStore(local_dir),
                                 PlanStore(FsRemoteBackend(remote_dir)))
        plan = alltoallv_init(counts, (4,), jnp.float32, mesh,
                              axis=("o", "i"), variant="auto",
                              cache=PlanCache(), store=tiered,
                              autotune_iters=4)
        assert INIT_STATS.autotune_bursts == 0, INIT_STATS.as_dict()
        assert INIT_STATS.table_bakes == 0, INIT_STATS.as_dict()
        assert plan.warm_loaded and INIT_STATS.store_hits > 0
        assert tiered.promotions >= 1
        got = np.asarray(plan.wait(plan.start(x))).reshape(p, recv_rows, 4)
        _check(got, expect, rc, p)

        # --- tier promotion: local cache now serves memmaps, remote down -
        down = TieredPlanStore(
            PlanStore(local_dir),
            PlanStore(FsRemoteBackend(remote_dir, fail_rate=1.0)))
        art = down.get(plan.signature)
        assert art is not None and down.remote_errors == 0
        tables = art.index_tables or art.hier_schedule
        first = next(t for t in (getattr(tables, "pack_src", None),
                                 getattr(tables, "s1_src", None))
                     if t is not None)
        assert isinstance(first, np.memmap)
    print("planstore fleet prewarm:", INIT_STATS.as_dict())


@case
def gspmd_gather_miscompile_guard():
    """Regression for the "gspmd = data_axis_size x a2a" defect.

    An older jax's GSPMD miscompiled a gather whose operand dim 0 is
    model-sharded while the indices are data-sharded — the partial-gather
    reduction was applied over the data axis as well, multiplying every
    element by data_axis_size — and the MoE gspmd path replicated expert
    outputs before the combine gather to dodge it.  The installed jax
    partitions that gather correctly, so the guard is gone: this case pins
    the minimal pattern to the right values and asserts mesh invariance of
    the unguarded layer."""
    import dataclasses

    from repro.configs.base import MoEConfig
    from repro.launch.mesh import make_mesh
    from repro.models import moe as moe_mod
    from repro.parallel.sharding import DEFAULT_RULES, ParamFactory, axis_rules

    # --- the once-miscompiled pattern: gather from a model-sharded operand
    # with data-sharded indices, feeding a weighted per-token combine (the
    # MoE combine shape).
    mesh = make_mesh((2, 4), ("data", "model"))
    t, k, d = 256, 2, 64
    rng = np.random.default_rng(1)
    h = rng.standard_normal((2048, d)).astype(np.float32)
    idx = rng.integers(0, 2056, size=(t * k,)).astype(np.int32)
    wgt = rng.random((t * k,)).astype(np.float32)

    def combine(hh, ii, ww):
        hh = jax.lax.with_sharding_constraint(
            hh, NamedSharding(mesh, P("model", None)))
        padded = jnp.concatenate([hh, jnp.zeros((8, d), hh.dtype)], axis=0)
        out = padded[ii] * ww[:, None]
        return out.reshape(t, k, d).sum(axis=1)

    got = np.asarray(jax.jit(combine)(
        jnp.asarray(h),
        jax.device_put(jnp.asarray(idx), NamedSharding(mesh, P("data"))),
        jax.device_put(jnp.asarray(wgt), NamedSharding(mesh, P("data")))))
    padded = np.concatenate([h, np.zeros((8, d), np.float32)])
    want = (padded[idx] * wgt[:, None]).reshape(t, k, d).sum(axis=1)
    np.testing.assert_allclose(got, want, atol=1e-5)

    # --- the gspmd MoE layer must be mesh-invariant ---------------------
    d_model, tokens = 64, 256
    base = MoEConfig(n_experts=8, top_k=2, d_expert=32, capacity_factor=8.0,
                     dispatch="gspmd")
    xnp = np.random.default_rng(0).standard_normal(
        (2, tokens // 2, d_model)).astype(np.float32)
    outs = {}
    for shape in [(2, 4), (1, 8)]:
        mesh_s = make_mesh(shape, ("data", "model"))
        with axis_rules(DEFAULT_RULES, mesh_s):
            f = ParamFactory(jax.random.key(0), jnp.float32)
            moe_mod.init_moe(f.scope("moe"), d_model, base)
            params = f.params["moe"]
            x = jax.device_put(jnp.asarray(xnp),
                               NamedSharding(mesh_s, P("data", None, None)))
            plan = moe_mod.MoEDispatchPlan.build(base, tokens // shape[0], mesh_s)
            y, _ = jax.jit(lambda xx, pl=plan:
                           moe_mod.apply_moe(params, xx, base, pl))(x)
            outs[shape] = np.asarray(y)
    np.testing.assert_allclose(outs[(2, 4)], outs[(1, 8)], rtol=2e-4, atol=2e-5)


@case
def moe_hier_dispatch():
    """MoE expert parallelism spanning a (pod, model) axis pair *via the
    first-class launch profile* (``sharding.HIER_EP_RULES``, the
    ``--rules hier_ep`` registry entry — no test-local rule table): the
    dispatch plan derives its EP axis pair from the active experts rule,
    and flat-fence EP, leader-combined hierarchical EP (plan-backed,
    INIT-baked two-stage tables), and gspmd all agree."""
    import dataclasses

    from repro.configs.base import MoEConfig
    from repro.launch.mesh import make_mesh
    from repro.models import moe as moe_mod
    from repro.parallel.sharding import (HIER_EP_RULES, RULE_PROFILES,
                                         ParamFactory, axis_rules)

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    assert RULE_PROFILES["hier_ep"] is HIER_EP_RULES
    d_model, tokens = 64, 256
    base = MoEConfig(n_experts=8, top_k=2, d_expert=32, capacity_factor=8.0)
    with axis_rules(HIER_EP_RULES, mesh):
        f = ParamFactory(jax.random.key(0), jnp.float32)
        moe_mod.init_moe(f.scope("moe"), d_model, base)
        params = f.params["moe"]
        x = jax.device_put(
            jnp.asarray(np.random.default_rng(0).standard_normal(
                (2, tokens // 2, d_model)), jnp.float32),
            NamedSharding(mesh, P("data", None, None)))
        outs = {}
        for name, dispatch, variant in [("gspmd", "gspmd", "fence"),
                                        ("flat", "persistent_a2a", "fence"),
                                        ("hier", "persistent_a2a",
                                         "fence_hierarchy")]:
            mcfg = dataclasses.replace(base, dispatch=dispatch,
                                       a2a_variant=variant)
            # EP axis pair comes from the profile's experts rule, not a
            # hier_axes override.
            plan = moe_mod.MoEDispatchPlan.build(
                mcfg, tokens // 2, mesh, d_model=d_model, dtype=jnp.float32)
            assert plan.ep_size == 4 and plan.axis == ("pod", "model")
            assert plan.hier_axes == ("pod", "model")
            if dispatch == "persistent_a2a":
                assert plan.plan_backed
                assert plan.a2a.spec.variant == variant
                if name == "hier":
                    assert plan.a2a.hier_schedule is not None
            y, aux = jax.jit(lambda xx, m=mcfg, pl=plan:
                             moe_mod.apply_moe(params, xx, m, pl))(x)
            outs[name] = np.asarray(y)
        np.testing.assert_allclose(outs["flat"], outs["gspmd"],
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(outs["hier"], outs["flat"],
                                   rtol=2e-4, atol=2e-5)

        # Fused leader stage inside the embedded plan (Pallas kernel, TPU
        # interpret mode here) is bit-identical to the jnp path.
        mcfg = dataclasses.replace(base, dispatch="persistent_a2a",
                                   a2a_variant="fence_hierarchy")
        plan_f = moe_mod.MoEDispatchPlan.build(
            mcfg, tokens // 2, mesh, d_model=d_model, dtype=jnp.float32,
            pack_impl="fused")
        assert plan_f.a2a.spec.pack_impl == "fused"
        y_f, _ = jax.jit(lambda xx, m=mcfg, pl=plan_f:
                         moe_mod.apply_moe(params, xx, m, pl))(x)
        np.testing.assert_array_equal(np.asarray(y_f), outs["hier"])


@case
def ulysses_hier_attention():
    """Ulysses attention with the sequence spanning a (pod, model) pair and
    the head exchange routed through the leader-combined schedule matches
    single-device attention."""
    from repro.launch.mesh import make_mesh
    from repro.models import ulysses
    from repro.parallel.sharding import use_mesh

    mesh = make_mesh((2, 2), ("pod", "model"))
    b, s, h, d = 2, 32, 4, 8
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    q, k, v = mk(), mk(), mk()
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    want = np.asarray(ulysses._attend(q, k, v, pos, True))
    with use_mesh(mesh):
        plan = ulysses.UlyssesPlan.build(h, d, mesh, axis=("pod", "model"),
                                         hier=True)
        assert plan.p == 4 and plan.hier
        spec = NamedSharding(mesh, P(None, ("pod", "model")))
        got = np.asarray(ulysses.ulysses_attention(
            jax.device_put(q, spec), jax.device_put(k, spec),
            jax.device_put(v, spec), pos, plan))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@case
def production_mesh_mini():
    """Mini production dry-run: reduced configs lower+compile on a
    (pod, data, model) mesh with every axis > 1."""
    from repro.configs import SHAPES, ShapeConfig, get_reduced
    from repro.launch import steps as steps_mod
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    for arch in ("olmoe-1b-7b", "jamba-v0.1-52b"):
        cfg = get_reduced(arch)
        shape = ShapeConfig("train_mini", "train", 256, 8)
        c = steps_mod.make_train_bundle(cfg, shape, mesh).compile()
        assert c.cost_analysis() is not None
        d_shape = ShapeConfig("decode_mini", "decode", 256, 8)
        c = steps_mod.make_decode_bundle(cfg, d_shape, mesh).compile()
        assert c.cost_analysis() is not None


@case
def moe_codec_dispatch_parity():
    """Compressed EP dispatch parity: the fused wire path (encode before
    the capacity scatter, decode folded into the FFN/combine gathers)
    stays within the codec's declared tolerance of the uncompressed
    plan-backed output under controlled dense / banded / skewed routing on
    both (2, 4) and (4, 2) meshes — and codec=identity is bit-identical
    to the default plan-backed path AND to the table-free exchange (the
    pre-codec behavior, regression-pinned)."""
    import dataclasses

    from repro.configs.base import MoEConfig
    from repro.launch.mesh import make_mesh
    from repro.models import moe as moe_mod
    from repro.parallel.sharding import DEFAULT_RULES, ParamFactory, axis_rules

    d_model, tokens, e = 64, 256, 8
    base = MoEConfig(n_experts=e, top_k=2, d_expert=32, capacity_factor=16.0)
    for shape in [(2, 4), (4, 2)]:
        mesh = make_mesh(shape, ("data", "model"))
        with axis_rules(DEFAULT_RULES, mesh):
            f = ParamFactory(jax.random.key(0), jnp.float32)
            moe_mod.init_moe(f.scope("moe"), d_model, base)
            params = f.params["moe"]
            for pattern in ("dense", "banded", "skewed"):
                xnp, router = _routed_moe_setup(pattern, d_model,
                                                tokens, e, seed=5)
                params = dict(params, router=jnp.asarray(router))
                x = jax.device_put(
                    jnp.asarray(xnp.reshape(shape[0], tokens // shape[0],
                                            d_model)),
                    NamedSharding(mesh, P("data", None, None)))
                outs = {}
                for name, mkw, kw in [
                        ("plain", {}, {"d_model": d_model,
                                       "dtype": jnp.float32}),
                        ("identity", {"wire_codec": "identity"},
                         {"d_model": d_model, "dtype": jnp.float32}),
                        ("table_free", {}, {"plan_backed": False}),
                        ("int8", {"wire_codec": "int8", "codec_tol": 0.01},
                         {"d_model": d_model, "dtype": jnp.float32}),
                        ("bf16", {"wire_codec": "bf16", "codec_tol": 4e-3},
                         {"d_model": d_model, "dtype": jnp.float32})]:
                    mcfg = dataclasses.replace(
                        base, dispatch="persistent_a2a", **mkw)
                    plan = moe_mod.MoEDispatchPlan.build(
                        mcfg, tokens // shape[0], mesh, **kw)
                    y, _ = jax.jit(lambda xx, m=mcfg, pl=plan:
                                   moe_mod.apply_moe(params, xx, m, pl))(x)
                    outs[name] = np.asarray(y)
                tag = f"{pattern} mesh={shape}"
                # identity codec: bit-identical to the pre-codec paths.
                np.testing.assert_array_equal(outs["identity"],
                                              outs["plain"], err_msg=tag)
                np.testing.assert_array_equal(outs["identity"],
                                              outs["table_free"],
                                              err_msg=tag)
                # lossy codecs: within a small multiple of the declared
                # per-hop bound (two wire hops + FFN products compound).
                # The bound is relative to the encoded ROW max — the
                # dispatched hidden rows (max |x|), not the combined
                # output, set the error scale.
                scale = np.abs(xnp).max()
                for name, mult in (("int8", 4), ("bf16", 4)):
                    c_err = {"int8": 0.5 / 127, "bf16": 2.0 ** -8}[name]
                    np.testing.assert_allclose(
                        outs[name], outs["plain"],
                        atol=mult * c_err * scale, rtol=0,
                        err_msg=f"{tag} codec={name}")
    print("codec dispatch parity: dense/banded/skewed x (2,4)/(4,2) OK")


@case
def codec_planstore_warm_start():
    """variant="auto" with a lossy tolerance sweeps (variant, codec) arms,
    persists the winning pair to the plan store, and a second process's
    INIT (emulated: fresh cache + fresh store handle on the same disk)
    replays the decision warm — zero measurement bursts, zero table bakes,
    same (variant, codec)."""
    import tempfile

    from repro.core import INIT_STATS, PlanCache, alltoallv_init
    from repro.launch.mesh import make_host_mesh
    from repro.planstore import PlanStore

    p = len(jax.devices())
    counts, bufs, expect, rc, send_rows, recv_rows = _setup_pattern(p, seed=29)
    mesh = make_host_mesh(p)
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P("x")))
    tol = 0.004            # admits bf16 + int8 (not fp8)

    with tempfile.TemporaryDirectory() as d:
        # --- run 1: cold — measures every (variant, codec) arm -----------
        INIT_STATS.reset()
        plan = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x",
                              variant="auto", error_tol=tol,
                              cache=PlanCache(), store=PlanStore(d),
                              autotune_iters=4)
        arms = set(plan.auto_choice["times"])
        assert any("@int8" in a for a in arms), arms
        assert any("@bf16" in a for a in arms), arms
        assert "codec_fits" in plan.auto_choice
        assert plan.auto_choice["codec"] == plan.spec.codec
        assert INIT_STATS.autotune_bursts > 0 and INIT_STATS.store_puts > 0
        got = np.asarray(plan.wait(plan.start(x))).reshape(p, recv_rows, 4)
        if plan.spec.codec == "identity":
            _check(got, expect, rc, p)

        # --- run 2: warm — decision replayed, nothing re-measured --------
        INIT_STATS.reset()
        plan2 = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x",
                               variant="auto", error_tol=tol,
                               cache=PlanCache(), store=PlanStore(d),
                               autotune_iters=4)
        assert INIT_STATS.autotune_bursts == 0, INIT_STATS.as_dict()
        assert INIT_STATS.table_bakes == 0, INIT_STATS.as_dict()
        assert INIT_STATS.warm_inits >= 1
        assert plan2.spec.variant == plan.spec.variant
        assert plan2.spec.codec == plan.spec.codec
        assert plan2.auto_choice["codec"] == plan.auto_choice["codec"]

        # --- a different tolerance is a different decision key -----------
        INIT_STATS.reset()
        plan3 = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x",
                               variant="auto", error_tol=None,
                               cache=PlanCache(), store=PlanStore(d),
                               autotune_iters=4)
        assert plan3.spec.codec == "identity"
        assert set(plan3.auto_choice["times"]) != arms or len(arms) == len(
            set(plan3.auto_choice["times"]))
    print("codec warm start:", plan.spec.variant, plan.spec.codec)


@case
def replan_hot_swap():
    """Self-healing loop, end to end: injected sustained skew (chaos epoch
    stalls) trips the PlanSkewMonitor, a background re-autotune re-measures
    the decision and CAS-merges it — with re-plan provenance — into the
    plan store, and an operator-forced hot swap to the runner-up variant
    is bit-identical on the same inputs, releases the old plan's window
    slots, and lands in EXEC_TELEMETRY's swap log."""
    import tempfile
    import time

    from repro.core import EXEC_TELEMETRY, INIT_STATS, PlanCache, alltoallv_init
    from repro.core.autotune import _candidate_spec, decision_signature
    from repro.launch.mesh import make_mesh
    from repro.planstore import PlanStore
    from repro.runtime import chaos as chaos_mod
    from repro.runtime import replan as replan_mod
    from repro.runtime.straggler import PlanSkewMonitor

    p = len(jax.devices())
    assert p % 4 == 0, "needs a (2, p//2) grouped mesh"
    mesh = make_mesh((2, p // 2), ("outer", "inner"))
    counts, bufs, expect, rc, send_rows, recv_rows = _setup_pattern(p, seed=9)
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P(("outer", "inner"))))

    with tempfile.TemporaryDirectory() as d:
        EXEC_TELEMETRY.reset()
        store, cache = PlanStore(d), PlanCache()
        plan = alltoallv_init(counts, (4,), jnp.float32, mesh,
                              axis=("outer", "inner"), variant="auto",
                              cache=cache, store=store, autotune_iters=2)
        spec0 = plan.spec
        base = np.asarray(plan.wait(plan.start(x))).reshape(p, recv_rows, 4)
        _check(base, expect, rc, p)
        sweeps0 = INIT_STATS.autotune_sweeps

        # The driver times whole epochs itself (including the injected
        # stall); the plan's internal dispatch timing would not see it.
        plan.record_starts = False
        monitor = PlanSkewMonitor(EXEC_TELEMETRY.ring(plan.signature.digest),
                                  threshold=1.6, window=4, sustain=2,
                                  warmup=6)
        mgr = replan_mod.ReplanManager(plan, mesh, cache, store=store,
                                       monitor=monitor, iters=2,
                                       background=True)
        # Degraded host: every epoch from #6 on stalls (sustained, not a
        # one-off spike — the first stalled window alone must NOT trigger).
        inj = chaos_mod.ChaosInjector(seed=0, stall_steps=range(6, 10_000),
                                      stall_seconds=0.03)
        deadline = time.time() + 300
        for e in range(10_000):
            t0 = time.perf_counter()
            inj.maybe_stall(e)
            cur = mgr.plan
            got = np.asarray(cur.wait(cur.start(x))).reshape(p, recv_rows, 4)
            cur.record_epoch(time.perf_counter() - t0)
            mgr.observe()
            np.testing.assert_array_equal(got, base)   # bit-identical always
            if e == 9:   # one full hot window consumed: sustain=2 not met yet
                assert mgr.replans_completed == 0 and mgr.events == []
            if mgr.replans_completed >= 1:
                break
            assert time.time() < deadline, "re-plan never completed"
        assert inj.injected["stall"] > 0
        # The background sweep really re-measured (not a cache/store read).
        assert INIT_STATS.autotune_sweeps > sweeps0
        sig = decision_signature(spec0, mesh)
        fresh = cache.auto_choices[sig]
        assert fresh["replan"]["kind"] == "sustained_skew", fresh
        assert fresh["replan"]["ratio"] > 1.6
        assert fresh["replan"]["prev_variant"] == spec0.variant
        # ...and the verdict was CAS-merged into the store for the fleet.
        stored = store.get_auto(sig)
        assert stored is not None and stored["replan"] == fresh["replan"]

        # Deterministic swap half: force the runner-up variant in (a real
        # re-measure may rightly confirm the incumbent — the stall slows
        # every candidate equally on one host).
        live = mgr.plan
        times = {v.partition("@")[0]: t for v, t in
                 live.auto_choice["times"].items()}
        runner = min((v for v in times if v != live.spec.variant),
                     key=times.get)
        alt = cache.get(_candidate_spec(spec0, runner), mesh, store=store)
        old = mgr.plan
        assert mgr.force_swap(alt, reason="operator")
        assert mgr.plan is alt
        assert len(old.window._slots) == 0, "old plan's window slots leaked"
        assert old._compiled is None
        got = np.asarray(alt.wait(alt.start(x))).reshape(p, recv_rows, 4)
        np.testing.assert_array_equal(got, base)       # swap is bit-identical
        swap = EXEC_TELEMETRY.swaps[-1]
        assert swap["variant_to"] == runner and swap["new"] == \
            alt.signature.digest
        assert any(ev["event"] == "swap" for ev in mgr.events)
    print("replan_hot_swap:", spec0.variant, "->", runner,
          "replans:", mgr.replans_completed, "events:",
          [(ev["event"], ev["kind"]) for ev in mgr.events])


@case
def leader_rebake_recovery():
    """Skew-adaptive leader re-election, end to end: a deterministic 3x
    single-rank slowdown (chaos ``rank_slow``) on a carrying leader trips
    the skew monitor, whose rank attribution names the slow rank; ladder
    rung 0 re-elects leaders around it — one hierarchy-schedule re-bake,
    zero autotune bursts, zero index-table bakes beyond it — the demoted
    rank leaves the carrying set, every epoch (before, across, and after
    the hot swap) stays bit-identical to the dense oracle, and the
    post-rebake steady p50 recovers to within 15% of the pre-injection
    baseline.  The old plan's window slots are freed, the new digest's
    rank rings are re-anchored, and the recovered baseline re-arms the
    ladder at rung 0."""
    import time

    from repro.core import EXEC_TELEMETRY, INIT_STATS, PlanCache, alltoallv_init
    from repro.runtime import chaos as chaos_mod
    from repro.runtime import replan as replan_mod
    from repro.launch.mesh import make_mesh
    from repro.runtime.straggler import PlanSkewMonitor

    p = len(jax.devices())
    assert p % 4 == 0, "needs a (2, p//2) grouped mesh"
    mesh = make_mesh((2, p // 2), ("outer", "inner"))
    counts, bufs, expect, rc, send_rows, recv_rows = _setup_pattern(p, seed=11)
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P(("outer", "inner"))))

    EXEC_TELEMETRY.reset()
    cache = PlanCache()
    plan = alltoallv_init(counts, (4,), jnp.float32, mesh,
                          axis=("outer", "inner"), variant="fence_hierarchy",
                          cache=cache)
    base = np.asarray(plan.wait(plan.start(x))).reshape(p, recv_rows, 4)
    _check(base, expect, rc, p)
    bursts0, bakes0 = INIT_STATS.autotune_bursts, INIT_STATS.table_bakes

    def carrying(pl):
        return {int(r) for rnd in pl.hier_schedule.round_perms
                for pair in rnd for r in pair}

    slow = min(carrying(plan))          # a round-robin leader (group 0, role 0)
    # Injection starts the epoch after the monitor's warmup baseline is
    # earned, so the baseline is clean and every post-warmup window is hot.
    inj = chaos_mod.ChaosInjector(seed=0, rank_slow={slow: 3.0},
                                  rank_slow_from=6, rank_slow_weight=0.05)
    monitor = PlanSkewMonitor(EXEC_TELEMETRY.ring(plan.signature.digest),
                              threshold=1.6, window=4, sustain=2, warmup=6,
                              digest=plan.signature.digest)
    mgr = replan_mod.ReplanManager(plan, mesh, cache, monitor=monitor,
                                   background=False)

    def run_epoch(e):
        """One driver-timed epoch: exchange, chaos stall, telemetry feed."""
        cur = mgr.plan
        cur.record_starts = False       # the driver times whole epochs
        t0 = time.perf_counter()
        got = np.asarray(cur.wait(cur.start(x))).reshape(p, recv_rows, 4)
        work = time.perf_counter() - t0
        extra = inj.maybe_rank_stall(e, carrying(cur), work)
        cur.record_epoch(work + extra)
        # Per-rank signal: uniform shard times, chaos-inflated on the slow
        # rank — exactly what the trainer's shard probe would observe.
        for r, t in inj.scale_rank_times(
                e, {r: work for r in range(p)}).items():
            EXEC_TELEMETRY.record_rank(cur.signature.digest, r, t)
        np.testing.assert_array_equal(got, base)   # bit-identical always
        return work + extra

    pre_p50 = None
    deadline = time.time() + 300
    for e in range(10_000):
        run_epoch(e)
        if e == 5:    # last clean epoch: the pre-injection baseline
            pre_p50 = EXEC_TELEMETRY.ring(
                plan.signature.digest).summary()["p50_s"]
        mgr.observe()
        if mgr.replans_completed >= 1:
            break
        assert time.time() < deadline, "leader re-bake never installed"
    assert inj.injected["rank_slow"] > 0 and pre_p50 is not None

    # Rung 0 and nothing above it: a leader re-bake, not a sweep.
    assert mgr.leader_rebakes == 1
    ev = mgr.events[-1]
    assert ev["event"] == "swap" and ev["kind"] == "leader_rebake"
    assert ev["worst_rank"] == slow, ev
    new = mgr.plan
    assert new.spec.variant == "fence_hierarchy"
    assert new.spec.hier_leader_perm is not None
    assert slow not in carrying(new), "slow rank still carries slabs"
    assert INIT_STATS.autotune_bursts == bursts0, "re-bake ran a sweep"
    assert INIT_STATS.table_bakes == bakes0 + 1, \
        "re-bake re-baked more than the hierarchy schedule"
    # Old plan released; incoming digest's rank rings re-anchored.
    assert len(plan.window._slots) == 0, "old plan's window slots leaked"
    assert plan._compiled is None
    assert EXEC_TELEMETRY.rank_summary(new.signature.digest) == {}
    swap = EXEC_TELEMETRY.swaps[-1]
    assert swap["reason"]["kind"] == "leader_rebake"
    assert swap["new"] == new.signature.digest

    # Steady state on the re-elected schedule: the slow host still exists
    # but no longer gates the epoch.  Skip the first post-swap epochs (the
    # new executable's compile) before sampling.
    steady = []
    e0 = e + 1
    for e2 in range(e0, e0 + 14):
        dt = run_epoch(e2)
        if e2 >= e0 + 3:
            steady.append(dt)
        mgr.observe()
    post_p50 = float(np.median(steady))
    assert post_p50 <= 1.15 * pre_p50, \
        f"post-rebake p50 {post_p50:.6f}s vs baseline {pre_p50:.6f}s"
    # The earned baseline shows recovery: the ladder re-arms at rung 0.
    assert any(ev["event"] == "recovered" for ev in mgr.events), mgr.events
    assert mgr._ladder_stage == 0
    mgr.close()                         # teardown: idempotent, leak-free
    mgr.close()
    print("leader_rebake_recovery: slow rank", slow, "->",
          [list(r) for r in new.spec.hier_leader_perm],
          f"p50 {pre_p50 * 1e3:.2f}ms -> {post_p50 * 1e3:.2f}ms,",
          "events:", [ev["event"] for ev in mgr.events])


@case
def elastic_resume():
    """Elastic-mesh resume, end to end: INIT requests captured on the full
    mesh are resharded onto a shrunk mesh (reshard_plans publishes the new
    geometry's artifacts), the checkpoint restores onto the new mesh via
    load_to_mesh, and a fresh replica's rebuild of EVERY plan is warm —
    zero autotune bursts, zero table bakes — with the resharded exchange
    verified against the dense oracle."""
    import os
    import tempfile

    from repro.ckpt.manager import CheckpointManager
    from repro.ckpt.reshard import load_to_mesh, mesh_axis_sizes, put_tree
    from repro.core import (INIT_STATS, PlanCache, alltoallv_init,
                            capture_init_requests, metadata as md, reference)
    from repro.launch.mesh import make_host_mesh, make_mesh
    from repro.planstore import PlanStore, prewarm
    from repro.runtime import replan as replan_mod

    p = len(jax.devices())
    assert p % 2 == 0
    mesh_a = make_host_mesh(p)
    counts, bufs, expect, rc, send_rows, recv_rows = _setup_pattern(p, seed=2)

    with tempfile.TemporaryDirectory() as d:
        store = PlanStore(os.path.join(d, "store"))
        cache = PlanCache()
        with capture_init_requests() as reqs:
            alltoallv_init(counts, (4,), jnp.float32, mesh_a, axis="x",
                           variant="fence", cache=cache, store=store)
            alltoallv_init(counts, (4,), jnp.float32, mesh_a, axis="x",
                           variant="lock", lock_schedule="pairwise",
                           cache=cache, store=store)
            alltoallv_init(counts, (4,), jnp.float32, mesh_a, axis="x",
                           variant="auto", cache=cache, store=store,
                           autotune_iters=2)
        assert len(reqs) == 3
        params = {"w": jnp.arange(64 * p, dtype=jnp.float32).reshape(64, p)}
        mgr = CheckpointManager(os.path.join(d, "ckpt"))
        mgr.save(5, {"params": put_tree(
            params, {"w": NamedSharding(mesh_a, P("x"))})},
            extras={"mesh": mesh_axis_sizes(mesh_a)})

        # --- the pod is lost: p//2 devices remain ------------------------
        mesh_b = make_mesh((p // 2,), ("x",))
        # The geometry stamp is what an elastic launcher compares to detect
        # the change (saved both beside the requests and in ckpt extras).
        assert mgr.load()[2]["mesh"] != mesh_axis_sizes(mesh_b)
        # Deploy-side prewarm: project + replay every captured request.
        report = replan_mod.reshard_plans(list(reqs), mesh_b, store=store,
                                          autotune_iters=2)
        assert not report["skipped"] and len(report["resharded"]) == 3, report
        # Every replayed row carries the geometry it was projected from, so
        # a prewarm report distinguishes resharded plans from native ones.
        for row in report["resharded"]:
            assert row["resharded_from"]["p"] == p, row

        # --- fresh replica on the shrunk mesh (fresh in-memory tiers) ----
        INIT_STATS.reset()
        cache2 = PlanCache()
        store2 = PlanStore(os.path.join(d, "store"))
        step, placed, extras = load_to_mesh(
            mgr, mesh_b, {"params": {"w": NamedSharding(mesh_b, P("x"))}})
        assert step == 5 and extras["mesh"] == {"x": p}
        np.testing.assert_array_equal(np.asarray(placed["params"]["w"]),
                                      np.asarray(params["w"]))
        assert placed["params"]["w"].sharding.mesh.shape["x"] == p // 2
        for req in prewarm.dedupe_requests(list(reqs)):
            row = prewarm.replay_request(replan_mod.reshard_request(req, mesh_b),
                                         store2, cache=cache2,
                                         autotune_iters=2)
            assert "skipped" not in row, row
        s = INIT_STATS.as_dict()
        assert s["autotune_bursts"] == 0, s     # zero measurement bursts
        assert s["table_bakes"] == 0, s         # zero host-side bakes
        assert s["warm_inits"] >= 2 and s["cold_inits"] == 0, s
        assert s["store_hits"] > 0, s

        # --- the resharded exchange is correct on the new geometry -------
        p2 = p // 2
        counts2 = replan_mod.reshard_counts(counts, p2)
        assert counts2.sum() == counts.sum()
        sr2 = max(md.round_up(md.max_total_send(counts2), 8), 8)
        rr2 = max(md.round_up(md.max_total_recv(counts2), 8), 8)
        bufs2 = reference.make_testbufs(counts2, (4,), np.float32, sr2)
        expect2 = reference.alltoallv_global(bufs2, counts2, rr2)
        rc2 = md.recv_counts(counts2)
        plan2 = alltoallv_init(counts2, (4,), jnp.float32, mesh_b, axis="x",
                               variant="fence", cache=cache2, store=store2)
        assert plan2.warm_loaded
        x2 = jax.device_put(jnp.asarray(bufs2.reshape(p2 * sr2, 4)),
                            NamedSharding(mesh_b, P("x")))
        got = np.asarray(plan2.wait(plan2.start(x2))).reshape(p2, rr2, 4)
        _check(got, expect2, rc2, p2)
    print("elastic_resume:", {"from": p, "to": p2, "init": s})


@case
def chaos_recovery():
    """Seeded window/store/stall faults recovered without epoch corruption:
    window-allocation failures retry the build, a poisoned store entry
    degrades to a cold rebuild (store_invalid, never a crash), a flaky
    remote store degrades reads to misses, injected step and device-loss
    faults run the full recovery discipline (device loss rebuilds the
    plan first), every epoch's output is verified against the dense
    oracle, and sustained progress decays the restart budget."""
    import tempfile

    from repro.core import INIT_STATS, PlanCache, WindowCache, alltoallv_init
    from repro.launch.mesh import make_host_mesh
    from repro.planstore import parse_store_url
    from repro.runtime import chaos as chaos_mod
    from repro.runtime import fault as fault_mod

    p = len(jax.devices())
    mesh = make_host_mesh(p)
    counts, bufs, expect, rc, send_rows, recv_rows = _setup_pattern(p, seed=5)
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P("x")))

    with tempfile.TemporaryDirectory() as d:
        store = parse_store_url(f"fsremote://{d}/remote?fail_rate=0.25&seed=11")
        inj = chaos_mod.ChaosInjector(seed=3, window_fail_rate=0.5,
                                      fail_steps=(4,), device_loss_steps=(8,),
                                      stall_steps=(6,), stall_seconds=0.05)
        state: dict = {"rebuilds": 0, "plan_rebuild_hook": 0}

        def rebuild(err=None):
            # Allocation-failure recovery discipline: retry the build (each
            # attempt re-draws from the injector's schedule).  A fresh
            # PlanCache emulates rebuilding device state from scratch; the
            # (flaky, possibly poisoned) store is the only warm tier.
            for _ in range(50):
                try:
                    cache = PlanCache(
                        window_cache=inj.wrap_window_cache(WindowCache()))
                    state["plan"] = alltoallv_init(
                        counts, (4,), jnp.float32, mesh, axis="x",
                        variant="fence", cache=cache, store=store)
                    state["rebuilds"] += 1
                    return
                except chaos_mod.ChaosError:
                    continue
            raise AssertionError("window allocation never succeeded")

        rebuild()
        # Poison every published entry: the next read of it must count as
        # store_invalid and fall back to a cold bake — never crash.
        assert inj.poison_store(store) >= 1

        INIT_STATS.reset()
        done: set = set()

        def run_step(step: int) -> dict:
            inj.step_hook(step)      # stalls at 6; faults at 4 (transient)
            plan = state["plan"]     # and 8 (device-loss class), once each
            got = np.asarray(plan.wait(plan.start(x))).reshape(
                p, recv_rows, 4)
            _check(got, expect, rc, p)      # no epoch corruption, ever
            done.add(step)
            return {}

        def rebuild_plans(err):
            state["plan_rebuild_hook"] += 1
            assert fault_mod.classify_failure(err) == "device_loss"
            rebuild(err)

        def restore() -> int:
            return (max(done) + 1) if done else 0

        policy = fault_mod.RetryPolicy(max_restarts=5, backoff_seconds=0.0,
                                       decay_after=2)
        final = fault_mod.run_with_recovery(
            run_step, restore=restore, start_step=0, n_steps=12,
            policy=policy, rebuild_plans=rebuild_plans)

        assert final == 12 and done == set(range(12))
        # Every injected fault class actually fired (seeded => stable).
        assert inj.injected["step"] == 1, inj.injected
        assert inj.injected["device"] == 1, inj.injected
        assert inj.injected["stall"] >= 1, inj.injected
        assert inj.injected["poison"] >= 1, inj.injected
        assert inj.injected["window"] >= 1, \
            f"window fault never drawn: {inj.injected} (tune seed/rate)"
        # Device loss took the plan-rebuild path, not just restart.
        assert state["plan_rebuild_hook"] == 1
        assert state["rebuilds"] >= 2
        # Poisoned entries degraded to cold rebuilds; the flaky remote's
        # faults degraded to misses (errors counted, nothing raised).
        s = INIT_STATS.as_dict()
        assert s["store_invalid"] + store.errors >= 1, (s, store.stats)
        assert s["cold_inits"] >= 1, s
        # Sustained progress decayed the restart budget (2 failures, but
        # clean stretches forgave them).
        assert policy.restarts <= 1, policy.restarts
        stats = {k: store.stats[k]
                 for k in ("hits", "misses", "invalid", "errors")}
    print("chaos_recovery:", {"injected": inj.injected,
                              "rebuilds": state["rebuilds"],
                              "restarts_left": policy.restarts,
                              "store": stats})


@case
def obs_trace_contract():
    """The repro.obs acceptance contract, end to end on one traced run:
    the exported Chrome trace validates and contains INIT spans (autotune
    bursts, table bakes, store get/put), per-epoch EXECUTE spans, and the
    replan-swap instant; a warm INIT traces with zero bake/burst children;
    the per-rank rings feed PlanSkewMonitor's rank attribution; and a
    break-even residual is computed against the stored Eq.1-3 fit."""
    import tempfile

    from repro.core import EXEC_TELEMETRY, INIT_STATS, PlanCache, alltoallv_init
    from repro.core.autotune import _candidate_spec
    from repro.launch.mesh import make_host_mesh
    from repro.obs import (TRACER, check_breakeven, chrome_trace,
                           render_metrics, validate_trace)
    from repro.planstore import PlanStore
    from repro.runtime import replan as replan_mod
    from repro.runtime.straggler import PlanSkewMonitor

    p = len(jax.devices())
    counts, bufs, expect, rc, send_rows, recv_rows = _setup_pattern(p, seed=33)
    mesh = make_host_mesh(p)
    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       NamedSharding(mesh, P("x")))

    EXEC_TELEMETRY.reset()
    INIT_STATS.reset()
    TRACER.enable()
    try:
        with tempfile.TemporaryDirectory() as d:
            store, cache = PlanStore(d), PlanCache()
            plan = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x",
                                  variant="auto", cache=cache, store=store,
                                  autotune_iters=4)
            digest = plan.signature.digest
            for _ in range(8):
                got = np.asarray(plan.wait(plan.start(x)))
            _check(got.reshape(p, recv_rows, 4), expect, rc, p)

            # Per-rank signal: rank p-1 is the synthetic straggler.  The
            # monitor's attribution must name it from the rank rings.
            for _ in range(8):
                plan.record_epoch_ranks(
                    {r: 0.001 * (3.0 if r == p - 1 else 1.0)
                     for r in range(p)})
            mon = PlanSkewMonitor(plan.epoch_ring, digest=digest)
            worst, ratio = mon.rank_attribution()
            assert worst == p - 1, (worst, ratio)
            assert ratio is not None and ratio > 2.0, ratio
            assert set(plan.rank_summaries()) == set(range(p))

            # Break-even residual against the fit the sweep stored.
            residuals = check_breakeven()
            assert any(r["digest"] == digest for r in residuals), residuals
            r0 = next(r for r in residuals if r["digest"] == digest)
            assert np.isfinite(r0["residual"]) and r0["epochs"] >= 8

            # Operator-forced hot swap to the runner-up -> swap instant.
            times = {v.partition("@")[0]: t
                     for v, t in plan.auto_choice["times"].items()}
            runner = min((v for v in times if v != plan.spec.variant),
                         key=times.get)
            mgr = replan_mod.ReplanManager(plan, mesh, cache, store=store)
            alt = cache.get(_candidate_spec(plan.spec, runner), mesh,
                            store=store)
            assert mgr.force_swap(alt, reason="operator")

            # Warm INIT against the now-populated store: its init span
            # must carry warm=True and contain no bake/burst children —
            # validate_trace enforces exactly that.
            warm = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x",
                                  variant="auto", cache=PlanCache(),
                                  store=PlanStore(d), autotune_iters=4)
            assert warm.warm_loaded

        summary = validate_trace(
            chrome_trace(),
            expect_cats=("init", "init.bake", "init.autotune", "store",
                         "execute", "runtime"))
        assert summary["warm_inits"] >= 1, summary
        assert summary["cold_inits"] >= 1, summary
        by_cat = summary["by_cat"]
        assert by_cat["execute"] >= 8, by_cat        # per-epoch spans
        assert by_cat["runtime"] >= 1, by_cat        # the swap instant

        text = render_metrics()
        assert f'repro_breakeven_residual{{digest="{digest}"}}' in text
        assert "repro_epoch_rank_seconds" in text
        assert 'repro_store_requests_total{result="hit"}' in text
    finally:
        TRACER.disable()
        TRACER.reset()
    print("obs_trace_contract:", summary["by_cat"],
          "residual:", round(r0["residual"], 3),
          "worst_rank:", worst)


@case
def a2a_epoch_scopes():
    """An alltoallv epoch names its stages for the profiler: the standalone
    executable and an embedded epoch compile with ``a2a/pack``,
    ``a2a/exchange`` and ``a2a/unpack`` in their ops' ``op_name``."""
    import re

    from repro.core import PlanCache, alltoallv_init
    from repro.core.plan import A2A_EXCHANGE, A2A_PACK, A2A_UNPACK
    from repro.launch.mesh import make_host_mesh

    p = len(jax.devices())
    counts, bufs, expect, rc, send_rows, recv_rows = _setup_pattern(p, seed=5)
    mesh = make_host_mesh(p)
    plan = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x",
                          variant="fence", cache=PlanCache())
    assert not plan.identity_maps          # irregular: both gathers run

    def scopes(hlo_text):
        names = re.findall(r'op_name="([^"]*)"', hlo_text)
        return {sc for sc in (A2A_PACK, A2A_EXCHANGE, A2A_UNPACK)
                if any(sc + "/" in n for n in names)}

    want = {A2A_PACK, A2A_EXCHANGE, A2A_UNPACK}
    standalone = scopes(plan.compile()._compiled.as_text())
    assert standalone == want, standalone

    spec = plan._x_sharding.spec
    body = shard_map(plan.embed(), mesh=mesh, in_specs=spec, out_specs=spec,
                     check_vma=False)
    x = jax.ShapeDtypeStruct((p * plan.send_rows, 4), jnp.float32,
                             sharding=plan._x_sharding)
    embedded = scopes(jax.jit(body).lower(x).compile().as_text())
    assert embedded == want, embedded

    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       plan._x_sharding)
    got = np.asarray(plan.wait(plan.start(x)))
    _check(got.reshape(p, recv_rows, 4), expect, rc, p)


@case
def a2a_wire_counters():
    """``a2a.epochs`` and ``a2a.wire_bytes`` count each standalone epoch
    (``start`` and ``start_pipelined``) and the bytes its collective puts
    between distinct chips, padding included, as reckoned here from the
    counts: every off-chip fence bucket at the capacity, each lock round at
    its diagonal's tile-rounded maximum, the off-diagonal rows for ragged,
    at the codec's wire width.  An embedded epoch counts nothing."""
    from repro.core import PlanCache, alltoallv_init, metadata as md
    from repro.launch.mesh import make_host_mesh
    from repro.obs.counters import COUNTERS

    p = len(jax.devices())
    counts, bufs, expect, rc, send_rows, recv_rows = _setup_pattern(p, seed=11)
    mesh = make_host_mesh(p)
    row = 4 * 4                                   # (4,) float32
    tile = md.TILE_ROWS
    cap = max(md.round_up(int(counts.max()), tile), tile)
    diag = [int(counts[np.arange(p), (np.arange(p) + r) % p].max())
            for r in range(1, p)]
    ring = [max(md.round_up(m, tile), tile) if m else 0 for m in diag]
    off = int(counts.sum() - np.trace(counts))
    want = {("fence", "identity"): p * (p - 1) * cap * row,
            ("lock", "identity"): p * sum(ring) * row,
            ("ragged", "identity"): off * row,
            ("fence", "bf16"): p * (p - 1) * cap * 4 * 2,
            ("fence", "int8"): p * (p - 1) * cap * (4 * 1 + 4)}
    plans = {}
    for (variant, codec), wire in want.items():
        kw = {"codec": codec, "error_tol": 1.0} if codec != "identity" else {}
        plan = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x",
                              variant=variant, cache=PlanCache(), **kw)
        assert plan.wire_bytes == wire, (variant, codec, plan.wire_bytes, wire)
        plans[variant, codec] = plan
    assert 0 < want["ragged", "identity"] < want["fence", "identity"]

    x = jax.device_put(jnp.asarray(bufs.reshape(p * send_rows, 4)),
                       plans["fence", "identity"]._x_sharding)
    for variant in ("fence", "lock"):
        plan = plans[variant, "identity"]
        before = COUNTERS.snapshot()
        got = np.asarray(plan.wait(plan.start(x)))
        _check(got.reshape(p, recv_rows, 4), expect, rc, p)
        for _ in range(2):
            plan.wait(plan.start_pipelined(x))
        after = COUNTERS.snapshot()
        epochs = after["a2a.epochs"] - before.get("a2a.epochs", 0)
        wire = after["a2a.wire_bytes"] - before.get("a2a.wire_bytes", 0)
        assert (epochs, wire) == (3, 3 * want[variant, "identity"]), \
            (variant, epochs, wire)

    plan = plans["fence", "identity"]
    spec = plan._x_sharding.spec
    body = jax.jit(shard_map(plan.embed(), mesh=mesh, in_specs=spec,
                             out_specs=spec, check_vma=False))
    before = COUNTERS.snapshot()
    got = np.asarray(jax.block_until_ready(body(x)))
    assert COUNTERS.snapshot() == before
    _check(got.reshape(p, recv_rows, 4), expect, rc, p)


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("case")
    ap.add_argument("--devices", type=int, default=8)
    args = ap.parse_args()
    if args.case == "all":
        for name, fn in CASES.items():
            fn()
            print(f"CASE_OK {name}", flush=True)
    else:
        CASES[args.case]()
        print(f"CASE_OK {args.case}", flush=True)


if __name__ == "__main__":
    main()

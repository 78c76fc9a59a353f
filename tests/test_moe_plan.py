"""Single-device halves of the plan-backed MoE dispatch rework: embedded
plan semantics, identity-map detection, chunk-geometry clamping, and EP-axis
derivation from the sharding rules.  Multi-device output identity lives in
test_distributed.py (moe_plan_backed_parity / moe_overlap_invariance /
moe_planstore_warm_start)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from repro.configs.base import MoEConfig
from repro.core import alltoallv_init, metadata as md
from repro.launch.mesh import make_host_mesh, make_mesh
from repro.models import moe as moe_mod
from repro.parallel.sharding import HIER_EP_RULES, axis_rules


def test_identity_maps_detected_for_uniform_pattern():
    """A uniform all-equal tile-aligned counts matrix (the MoE bucket
    layout) has identity pack/unpack maps; a ragged one does not."""
    mesh = make_host_mesh(1)
    plan = alltoallv_init(np.full((1, 1), 8), (4,), jnp.float32, mesh,
                          axis="x")
    assert plan.identity_maps
    ragged = alltoallv_init(np.full((1, 1), 5), (4,), jnp.float32, mesh,
                            axis="x")
    assert not ragged.identity_maps


def test_embed_matches_standalone_start():
    """The embedded epoch body produces the same recv buffer as the
    standalone START path (here on a 1-device mesh; multi-device parity is
    the dist cases' job)."""
    mesh = make_host_mesh(1)
    counts = np.array([[5]])
    plan = alltoallv_init(counts, (4,), jnp.float32, mesh, axis="x")
    x = jnp.arange(plan.global_send_shape[0] * 4, dtype=jnp.float32
                   ).reshape(plan.global_send_shape)
    want = np.asarray(plan.wait(plan.start(x)))
    fn = shard_map(plan.embed(), mesh=mesh, in_specs=P("x"),
                   out_specs=P("x"), check_vma=False)
    got = np.asarray(jax.jit(fn)(x))
    n = int(counts.sum())
    np.testing.assert_array_equal(got[:n], want[:n])
    # embedded path zeroes padding instead of window write-through
    assert not np.abs(got[n:]).any()


def test_embed_rejects_unembeddable_specs():
    mesh = make_host_mesh(1)
    plan = alltoallv_init(np.full((1, 1), 8), (4,), jnp.float32, mesh,
                          axis="x", baked_metadata=False)
    with pytest.raises(ValueError, match="baked_metadata"):
        plan.embed()


def test_overlap_depth_clamps_to_capacity_geometry():
    """Requested depths that do not partition the capacity cleanly clamp to
    the largest feasible divisor; the backing plan (when built) always has
    the chunk geometry."""
    moe = MoEConfig(n_experts=8, top_k=2, d_expert=16, capacity_factor=8.0)
    # mesh=None -> ep=1, table-free, but geometry fields still computed
    p1 = moe_mod.MoEDispatchPlan.build(
        dataclasses.replace(moe, overlap_chunks=4), 128, None)
    assert p1.capacity % p1.overlap_chunks == 0
    assert p1.chunk_capacity * p1.overlap_chunks == p1.capacity
    # a prime-ish capacity: depth 7 request on cap that 7 does not divide
    p2 = moe_mod.MoEDispatchPlan.build(moe, 128, None, overlap_chunks=7)
    assert p2.capacity % p2.overlap_chunks == 0
    assert (p2.e_local * p2.chunk_capacity) % 8 == 0


def test_auto_variant_resolves_when_no_ep_exchange():
    """a2a_variant='auto' with nothing to tune (ep == 1, or a dispatch that
    never runs the a2a) quietly resolves to the dense-uniform default; the
    must-be-plan-backed error is reserved for a real persistent EP exchange
    (covered by dist_cases.moe_planstore_warm_start on 8 devices)."""
    moe = MoEConfig(n_experts=8, top_k=2, d_expert=16, a2a_variant="auto")
    mesh = make_host_mesh(1, axis="model")
    plan = moe_mod.MoEDispatchPlan.build(moe, 64, mesh, plan_backed=False)
    assert plan.variant == "fence" and not plan.plan_backed
    gs = dataclasses.replace(moe, dispatch="gspmd")
    plan = moe_mod.MoEDispatchPlan.build(gs, 64, mesh, d_model=32)
    assert plan.variant == "fence" and not plan.plan_backed


def test_ep_axes_follow_experts_rule():
    """The dispatch plan derives its EP axis (or pair) from the active
    ``experts`` sharding rule — HIER_EP_RULES yields the (pod, model) pair
    without any hier_axes override."""
    mesh = make_mesh((1, 1), ("data", "model"))
    moe = MoEConfig(n_experts=8, top_k=2, d_expert=16)
    # size-1 axes are dropped: no EP
    plan = moe_mod.MoEDispatchPlan.build(moe, 64, mesh)
    assert plan.axis is None and plan.ep_size == 1 and not plan.plan_backed
    with axis_rules(HIER_EP_RULES, mesh):
        # still size-1 -> no EP even under the widened rule
        plan = moe_mod.MoEDispatchPlan.build(moe, 64, mesh)
        assert plan.axis is None and plan.hier_axes is None


def test_plan_backed_counts_are_chunk_geometry():
    """The backing pattern is the uniform chunk-peer-rows matrix, so the
    plan-store signature keys on the pipeline depth."""
    moe = MoEConfig(n_experts=8, top_k=2, d_expert=16, capacity_factor=8.0,
                    dispatch="persistent_a2a")
    mesh = make_host_mesh(1, axis="model")
    # ep == 1 on one device: no backing plan regardless of d_model
    plan = moe_mod.MoEDispatchPlan.build(moe, 64, mesh, d_model=32,
                                         dtype=jnp.float32)
    assert not plan.plan_backed
    # geometry invariants hold anyway
    assert plan.peer_rows == plan.e_local * plan.capacity
    assert plan.chunk_peer_rows * plan.overlap_chunks == plan.peer_rows

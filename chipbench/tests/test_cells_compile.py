"""Compile the exchange cell's epoch (`chipbench/pending/`) for a described
TPU v5e 2x2.

Nothing runs: the plan is built on a mesh of the four described chips with
the cell's counts, row shape and dtype, and its START program is lowered
and compiled by the TPU compiler, which refuses what the CPU accepts.  The
topology is described inside a fixture, so only the worker that runs this
file loads the TPU library.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_exchange_epoch_compiles_for_v5e(topo):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh

    from chipbench import harness, patterns
    from repro.core import alltoallv_init
    from repro.core.plan import PlanCache

    bench = harness.load_json(harness.HERE / "pending" / "a2a.hugetrace.1mib.json", "")
    assert bench["workloads"][0]["chips"] == 4
    config = harness.load_json(harness.ROOT / bench["configs"][0]["file"], "")
    traffic = harness.load_json(harness.HERE / "traffic" / "hugetrace-1mib.json", "")
    p, lanes = config["ranks"], config["row_lanes"]
    dtype = jnp.dtype(config["dtype"])
    counts = patterns.counts(traffic, p, lanes * dtype.itemsize)
    mesh = Mesh(np.array(topo.devices[:p]), ("x",))
    plan = alltoallv_init(counts, (lanes,), dtype, mesh, axis="x",
                          cache=PlanCache(), store=False)
    tables = plan._table_host
    fn = shard_map(plan.shard_fn, mesh=mesh,
                   in_specs=(plan._x_sharding.spec,) * (2 + len(tables)),
                   out_specs=plan._x_sharding.spec, check_vma=False)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=plan._x_sharding)

    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        s(plan.global_send_shape, dtype), s(plan.global_recv_shape, dtype),
        *(s(t.shape, t.dtype) for t in tables)).compile()
    text = compiled.as_text()
    assert "all-to-all" in text
    mem = compiled.memory_analysis()
    # the padded buckets of the hottest pair, P of them per chip, fit easily
    assert mem.argument_size_in_bytes < 1 << 30

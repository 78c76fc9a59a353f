"""Compile the main-path Pallas kernels, and the serving decode step, for a
described TPU v5e (2x2).

Nothing runs here: each test lowers a kernel at real widths and compiles it
with the TPU compiler for a chip that is described, not attached.  That
compiler refuses what interpret mode accepts (a one-row slice of a tiled
ref, VMEM overflow, a collective_id without a barrier semaphore), so these
tests guard the chip path at no chip time.  The decode step's test reads the
compiled module: the KV cache must be written in place.  The topology is
described inside a fixture: only the worker that runs this file loads the
TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import metadata as md
from repro.kernels import ops

# olmoe-1b-7b expert layer: d_model, d_expert, local experts, recv rows.
D, F, E_LOCAL, ROWS = 2048, 1024, 64, 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("kernel", ["pack", "unpack_matmul"])
def test_gather_kernels_compile(topo, kernel):
    one = SingleDeviceSharding(topo.devices[0])

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    x = s((ROWS, D), jnp.bfloat16)
    if kernel == "pack":
        n = 5003                            # not a multiple of the row tile
        txt = _compiled_text(
            lambda x, i, v: ops.pack(x, i, v, interpret=False),
            x, s((n,), jnp.int32), s((n,), jnp.int32))
    else:
        n = 80
        txt = _compiled_text(
            lambda x, i, w, v: ops.fused_unpack_matmul(x, i, w, v,
                                                       interpret=False),
            x, s((E_LOCAL, n), jnp.int32), s((E_LOCAL, D, F), jnp.bfloat16),
            s((E_LOCAL, n), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("kernel,p", [("fence", 4), ("lock", 4),
                                      ("fused", 4), ("fused", 1)])
def test_rma_kernels_compile(topo, kernel, p):
    mesh = Mesh(np.array(topo.devices[:p]), ("x",))
    sh = NamedSharding(mesh, P("x"))
    cap = 13                                # any capacity, not tile-aligned

    if kernel == "fused":
        def body(x, idx, valid):
            return ops.fused_pack_alltoallv(
                x, idx[0], valid[0], p=p, capacity=cap, axis="x",
                mesh_axes=("x",), interpret=False)
        args = (jax.ShapeDtypeStruct((p * 40, D), jnp.bfloat16, sharding=sh),
                jax.ShapeDtypeStruct((p, p * cap), jnp.int32, sharding=sh),
                jax.ShapeDtypeStruct((p, p * cap), jnp.int32, sharding=sh))
    else:
        def body(x):
            return ops.rma_alltoallv(x, variant=kernel, p=p, capacity=cap,
                                     axis="x", mesh_axes=("x",),
                                     interpret=False)
        args = (jax.ShapeDtypeStruct((p * p * cap, D), jnp.bfloat16,
                                     sharding=sh),)
    f = shard_map(body, mesh=mesh, in_specs=(P("x"),) * len(args),
                  out_specs=P("x"), check_vma=False)
    assert "tpu_custom_call" in _compiled_text(f, *args)


def test_hier_leader_exchange_compiles(topo):
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("o", "i"))
    sh = NamedSharding(mesh, P(("o", "i")))
    counts = np.random.default_rng(0).integers(1, 13, (4, 4))
    sched = md.hier_two_stage_schedule(
        counts, 2, 2, md.round_up(md.max_total_recv(counts), 8))

    def body(s1, idx, valid):
        return ops.fused_hier_leader_exchange(
            s1, idx[0], valid[0], schedule=sched, outer_axis="o",
            inner_axis="i", mesh_axes=("o", "i"), interpret=False)

    f = shard_map(body, mesh=mesh, in_specs=(P(("o", "i")),) * 3,
                  out_specs=P(("o", "i")), check_vma=False)
    args = (jax.ShapeDtypeStruct((4 * 2 * sched.s1_cap, D), jnp.bfloat16,
                                 sharding=sh),
            jax.ShapeDtypeStruct((4, sched.total_s2), jnp.int32, sharding=sh),
            jax.ShapeDtypeStruct((4, sched.total_s2), jnp.int32, sharding=sh))
    assert "tpu_custom_call" in _compiled_text(f, *args)


# An HLO instruction with an array result: name, element type, dims, opcode,
# the rest of the line.
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\](?:\{[^}]*\})? "
                    r"([\w-]+)\((.*)$")
_HEADER = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")


def _computations(text: str) -> dict:
    """Optimized HLO text -> {computation name: [instruction matches]}."""
    comps, name = {}, None
    for line in text.splitlines():
        head = _HEADER.match(line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            m = _INSTR.match(line)
            if m:
                comps[name].append((m, line.lstrip().startswith("ROOT ")))
    return comps


@pytest.mark.parametrize("layers", [4, 2])     # lax.scan; unrolled
def test_decode_step_writes_kv_cache_in_place(topo, layers):
    """The compiled decode step aliases every donated cache leaf to its
    output, and no copy, concatenation or other whole-cache write produces
    an array of a whole stacked cache leaf's shape: the only ops that write
    one are dynamic-update-slices of the new rows (fused or not)."""
    from repro.configs import get_reduced
    from repro.configs.base import ShapeConfig
    from repro.launch import steps

    cfg = get_reduced("olmoe-1b-7b", n_layers=layers)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    # a cache large enough to stay in HBM, as a served one does
    bundle = steps.make_decode_bundle(
        cfg, ShapeConfig("decode", "decode", 1024, 8), mesh)
    caches = jax.tree.leaves(bundle.arg_specs[1])
    stack = {",".join(map(str, c.shape)) for c in caches}
    assert len(stack) == 1 and caches[0].shape[0] == layers
    text = bundle.compile().as_text()

    header = text.splitlines()[0]
    aliased = {int(n) for n in re.findall(r"\{[\d,]*\}: \((\d+), \{", header)}
    entry = re.search(r"^ENTRY %(\S+) ", text, re.M).group(1)
    comps = _computations(text)
    fused = {c for comp in comps.values() for m, _ in comp
             for c in re.findall(r"calls=%([\w.-]+)", m.group(5))
             if m.group(4) == "fusion"}
    roots = {name: next(m.group(4) for m, root in comp if root)
             for name, comp in comps.items() if name in fused}
    cache_params, writers = [], []
    for name, comp in comps.items():
        if name in fused:
            continue
        for m, _ in comp:
            if m.group(3) not in stack:
                continue
            op = m.group(4)
            if op == "parameter" and name == entry:
                cache_params.append(int(re.match(r"(\d+)\)", m.group(5))[1]))
            elif op == "fusion":
                root = roots[re.search(r"calls=%([\w.-]+)", m.group(5))[1]]
                if root != "dynamic-update-slice":
                    writers.append(f"{m.group(1)} (fusion rooted in {root})")
            elif op not in ("parameter", "get-tuple-element", "bitcast",
                            "dynamic-update-slice"):
                writers.append(f"{m.group(1)} ({op})")
    assert len(cache_params) == len(caches)
    assert set(cache_params) <= aliased, (cache_params, header)
    assert not writers, writers

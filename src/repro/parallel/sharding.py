"""Logical-axis sharding: MaxText-style rules mapping model dims to mesh axes.

Models are written against *logical* axes ("batch", "heads", "ff", ...);
a ``AxisRules`` table resolves them to physical mesh axes per run profile
(training, decode, long-context SP).  ``cs(x, ...)`` inserts GSPMD sharding
constraints; ``ParamFactory`` records a PartitionSpec alongside every
parameter it creates so the launcher can build in_shardings without a
separate, drift-prone spec tree.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Physical axes of the production mesh (launch/mesh.py):
#   pod   - outer data parallelism across pods
#   data  - data parallelism (or sequence parallelism for long decode)
#   model - tensor / expert parallelism
DEFAULT_RULES: dict[str, tuple[str, ...] | None] = {
    "batch": ("pod", "data"),
    "seq": None,
    # Megatron-style sequence parallelism: the residual stream at block
    # boundaries (the tensors scan-remat must save per layer) shards its
    # sequence dim over the model axis; XLA all-gathers at block entry and
    # reduce-scatters at exit.  Cuts saved-activation memory by the TP width
    # (95-layer deepseek: 102 GB -> 6.4 GB per chip).
    "seq_sp": ("model",),
    "embed": None,
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": None,
    "ff": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "expert_ff": None,
    "d_inner": ("model",),   # mamba / xlstm expanded inner dim
    "state": None,
    "conv": None,
    "frames": None,
    "stack": None,           # scanned-layer leading axis
}

# Long-context decode: batch=1 (replicated), `data` becomes the sequence
# axis (SP) so the KV cache / state shards across it.
LONG_CONTEXT_RULES = dict(DEFAULT_RULES, batch=None, seq=("data",),
                          seq_sp=None)

# Decode: KV caches dominate memory and kv_heads (often 8) cannot split a
# 16-way model axis, so the cache shards over *sequence* on the model axis
# (flash-decoding-style split-KV; GSPMD inserts the softmax reductions).
DECODE_RULES = dict(DEFAULT_RULES, seq=("model",), seq_sp=None)

# Pure data parallelism + FSDP (beyond-paper §Perf profile): no tensor
# parallelism at all — batch shards over every mesh axis and parameters
# FSDP-shard across all of them.  For small-activation models (<= ~3B) the
# per-layer TP activation collectives dwarf the FSDP weight gathers, so this
# profile cuts the collective term by >10x.  Requires global_batch >= chips.
PURE_DP_RULES = dict(
    DEFAULT_RULES,
    batch=("pod", "data", "model"),
    seq_sp=None, heads=None, kv_heads=None, ff=None, vocab=None,
    experts=None, d_inner=None,
)

# Hierarchical expert parallelism: experts widen to the (pod, model) axis
# pair so EP spans pods, and the MoE dispatch plan derives its axis pair
# from this rule (``a2a_variant="fence_hierarchy"`` then routes the
# exchange through the leader-combined schedule: O((EP/g)^2) cross-pod
# messages per layer instead of O(EP^2/g)).  Batch stays on the data axis
# only — the pod axis now carries experts, not data parallelism.
HIER_EP_RULES = dict(DEFAULT_RULES, experts=("pod", "model"),
                     batch=("data",))

# Launch-profile registry (``--rules`` on the launchers).
RULE_PROFILES: dict[str, dict] = {
    "default": DEFAULT_RULES,
    "long_context": LONG_CONTEXT_RULES,
    "decode": DECODE_RULES,
    "pure_dp": PURE_DP_RULES,
    "hier_ep": HIER_EP_RULES,
}


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: dict = dict(DEFAULT_RULES)


_CTX = _Ctx()


@contextlib.contextmanager
def axis_rules(rules: dict, mesh: Optional[Mesh] = None):
    old = (_CTX.rules, _CTX.mesh)
    _CTX.rules = dict(rules)
    if mesh is not None:
        _CTX.mesh = mesh
    try:
        yield
    finally:
        _CTX.rules, _CTX.mesh = old


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    old = _CTX.mesh
    _CTX.mesh = mesh
    try:
        yield
    finally:
        _CTX.mesh = old


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def active_rules() -> dict:
    """The logical-axis rule table currently in effect (a copy)."""
    return dict(_CTX.rules)


def resolve(logical_axes: Sequence[Optional[str]],
            shape: Optional[Sequence[int]] = None) -> P:
    """Logical axis names -> PartitionSpec under the active rules/mesh.

    Shape-aware: when ``shape`` is given, a physical axis is used only if the
    dim size divides evenly (e.g. kv_heads=8 cannot split a 16-way model
    axis -> replicated; a later dim may then claim that axis instead)."""
    mesh = _CTX.mesh
    mesh_axes = set(mesh.axis_names) if mesh is not None else set()
    out = []
    used: set[str] = set()
    for i, ax in enumerate(logical_axes):
        if ax is None:
            out.append(None)
            continue
        phys = _CTX.rules.get(ax)
        if phys is None:
            out.append(None)
            continue
        cand = tuple(p for p in ((phys,) if isinstance(phys, str) else phys)
                     if p in mesh_axes and p not in used
                     and int(mesh.shape[p]) > 1)
        if shape is not None and cand:
            dim = int(shape[i])
            picked = []
            ways = 1
            for p in cand:
                w = int(mesh.shape[p])
                if dim % (ways * w) == 0:
                    picked.append(p)
                    ways *= w
            cand = tuple(picked)
        used.update(cand)
        out.append(cand if len(cand) > 1 else (cand[0] if cand else None))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def batch_ways(n: int, mesh: Optional[Mesh] = None) -> int:
    """Ways a batch dim of size ``n`` actually shards under the ACTIVE
    rules (divisibility-aware).  The single source of truth for MoE
    capacity sizing: both the bundle builders and the plan-less
    ``apply_moe`` fallback divide token counts by this, so a rule profile
    that moves batch off an axis (hier_ep puts experts on pod) or a batch
    dim that cannot split an axis can never desynchronize the two."""
    mesh = mesh if mesh is not None else _CTX.mesh
    if mesh is None:
        return 1
    with use_mesh(mesh):
        spec = resolve(("batch",), (n,))
    axes = spec[0] if len(spec) else None
    ways = 1
    if axes:
        for a in ((axes,) if isinstance(axes, str) else axes):
            ways *= int(mesh.shape[a])
    return ways


def cs(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Sharding constraint on activation ``x`` (no-op without a mesh)."""
    mesh = _CTX.mesh
    if mesh is None or np.prod(mesh.devices.shape) == 1:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, resolve(logical_axes, x.shape)))


def pin_default_layout(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Constrain each shard of ``x`` to the layout its device gives an array
    of that shape by default, which is the layout of a jitted step's
    arguments and results (no-op without a mesh).  Over several devices the
    constraint is applied shard by shard: the partitioner knows no rule for
    it and would gather the whole array first."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    spec = resolve(logical_axes, x.shape)
    device = mesh.devices.flat[0]
    shard = NamedSharding(mesh, spec).shard_shape(x.shape)
    layout = Layout.from_pjrt_layout(
        device.client.get_default_layout(x.dtype, shard, device))
    pin = lambda a: with_layout_constraint(a, layout)  # noqa: E731
    if mesh.devices.size == 1:
        return pin(x)
    return jax.shard_map(pin, mesh=mesh, in_specs=spec, out_specs=spec)(x)


# ---------------------------------------------------------------------------
# Parameter creation with recorded specs
# ---------------------------------------------------------------------------

Initializer = Callable[[jax.Array, tuple[int, ...], jnp.dtype], jax.Array]


def normal_init(stddev: float) -> Initializer:
    def f(key, shape, dtype):
        return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(dtype)
    return f


def zeros_init() -> Initializer:
    return lambda key, shape, dtype: jnp.zeros(shape, dtype)


def ones_init() -> Initializer:
    return lambda key, shape, dtype: jnp.ones(shape, dtype)


class ParamFactory:
    """Builds a params pytree and a parallel logical-spec pytree in lockstep.

    abstract=True skips array creation and records ShapeDtypeStructs instead
    — used by the dry-run to get 67B-parameter shape trees without ever
    allocating (lowering consumes only avals)."""

    def __init__(self, key: Optional[jax.Array], dtype=jnp.float32,
                 abstract: bool = False):
        self._key = key
        self.dtype = dtype
        self.abstract = abstract
        self.params: dict = {}
        self.logical_specs: dict = {}   # same structure, tuples of logical axes

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def param(self, path: str, shape: Sequence[int],
              logical_axes: Sequence[Optional[str]],
              init: Initializer) -> jax.Array:
        """path is '/'-separated, e.g. 'layers/attn/wq'."""
        assert len(shape) == len(logical_axes), (path, shape, logical_axes)
        if self.abstract:
            arr = jax.ShapeDtypeStruct(tuple(shape), self.dtype)
        else:
            arr = init(self._next_key(), tuple(shape), self.dtype)
        node, spec_node = self.params, self.logical_specs
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
            spec_node = spec_node.setdefault(p, {})
        if parts[-1] in node:
            raise ValueError(f"duplicate param {path}")
        node[parts[-1]] = arr
        spec_node[parts[-1]] = tuple(logical_axes)
        return arr

    def scope(self, prefix: str) -> "ScopedFactory":
        return ScopedFactory(self, prefix)


class ScopedFactory:
    def __init__(self, base: ParamFactory, prefix: str):
        self._base = base
        self._prefix = prefix

    @property
    def dtype(self):
        return self._base.dtype

    def param(self, path, shape, logical_axes, init):
        return self._base.param(f"{self._prefix}/{path}", shape, logical_axes, init)

    def scope(self, prefix: str) -> "ScopedFactory":
        return ScopedFactory(self._base, f"{self._prefix}/{prefix}")


def specs_to_shardings(logical_specs, mesh: Mesh, shapes=None):
    """Logical-spec pytree -> NamedSharding pytree (for jit in_shardings).

    Pass the matching shape tree (arrays or ShapeDtypeStructs) to get
    divisibility-aware resolution."""
    is_leaf = lambda x: isinstance(x, tuple)
    if shapes is None:
        return jax.tree.map(lambda axes: NamedSharding(mesh, resolve(axes)),
                            logical_specs, is_leaf=is_leaf)
    return jax.tree.map(
        lambda axes, arr: NamedSharding(mesh, resolve(axes, arr.shape)),
        logical_specs, shapes, is_leaf=is_leaf)


def specs_to_pspecs(logical_specs, shapes=None):
    is_leaf = lambda x: isinstance(x, tuple)
    if shapes is None:
        return jax.tree.map(lambda a: resolve(a), logical_specs, is_leaf=is_leaf)
    return jax.tree.map(lambda a, arr: resolve(a, arr.shape),
                        logical_specs, shapes, is_leaf=is_leaf)

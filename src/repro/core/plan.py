"""AlltoallvPlan — the persistent ``MPIX_Request`` analogue.

``alltoallv_init`` (api.py) builds a plan from a frozen communication
pattern.  INIT performs, once:

  1. the metadata exchange (recv counts, displacements, put displacements),
  2. the capacity schedule (fence bucket size, per-round lock capacities,
     hierarchy factorization) plus the *sparsity analysis*: lock rounds whose
     capacity is 0 are dropped from the epoch, and an all-local pattern lets
     the hierarchical variant skip its outer-stage collective,
  3. host-baked pack/unpack index tables (``metadata.baked_index_tables``):
     every rank's gather maps are materialized as ``[P, P*C]`` /
     ``[P, recv_rows]`` tables, uploaded once *sharded over the
     communication axis* (each device holds only its own row), and handed
     to every START — per-epoch metadata recomputation vanishes (the
     in-graph twins in ``core.variants`` survive only for the
     non-persistent baseline),
  4. window acquisition from the WindowCache (reused while total_recv_bytes
     is unchanged, recreated otherwise — the paper's rule),
  5. AOT lowering + compilation of the START executable with the scalar
     metadata baked in as constants, the index tables as sharded runtime
     parameters, and the window buffer donated.

START then launches the compiled executable (JAX async dispatch returns
immediately — genuine start semantics) and WAIT blocks on the result.
``start_pipelined`` alternates between two window slots so epoch k+1 can be
dispatched while epoch k's output is still being consumed.

Embedded-plan lifecycle
-----------------------

A plan has two consumption forms.  The *standalone* form above owns its own
compiled executable and window.  The *embedded* form (``plan.embed()``)
returns the traced epoch body itself — pack, exchange, unpack driven by the
same INIT-baked metadata — for use INSIDE an enclosing ``shard_map``/``jit``
program (MoE expert dispatch, Ulysses).  The embedding host compiles the
plan's tables into its own executable as constants, so the INIT/EXECUTE
split survives intact: the plan is built once at model INIT (warm-startable
from the plan store), and every jitted train/serve step replays the baked
schedule with zero per-step metadata work.  Uniform all-equal patterns
(the MoE capacity-bucketed layout) are detected at INIT
(``plan.identity_maps``) and skip the pack/unpack gathers entirely.
An embedded plan never touches the window or its standalone executable —
the host program owns buffers and donation — so embedding is free of the
standalone form's device-table upload (which is deferred to the first
``start``/``compile``).
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from ..obs.counters import COUNTERS
from ..obs.spans import TRACER
from ..parallel import wirecodec
from . import metadata as md
from . import patterns as patterns_mod
from . import variants
from ._exec_stats import EXEC_TELEMETRY
from ._init_stats import INIT_STATS
from .window import Window, WindowCache

VARIANTS = ("fence", "lock", "fence_hierarchy", "ragged")

# Named scopes of an epoch's stages: HLO metadata and profiler traces name
# each device op of an alltoallv epoch (standalone or embedded) by them.
A2A_PACK, A2A_EXCHANGE, A2A_UNPACK = "a2a/pack", "a2a/exchange", "a2a/unpack"


def _scoped(name: str, fn: Callable) -> Callable:
    """``fn`` traced under ``jax.named_scope(name)``."""
    def call(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)
    return call


class WarmStartError(Exception):
    """A store artifact does not fit the plan being built (shape or schedule
    geometry mismatch).  ``PlanCache.get`` catches this and falls back to a
    cold INIT — a defective warm artifact must never produce a wrong plan."""


@dataclasses.dataclass(frozen=True, eq=False)  # eq=False: ndarray field
class ExchangeSpec:
    """Frozen description of one exchange pattern (the INIT arguments).

    ``collective`` names the exchange family (``core.patterns``):
    ``"alltoallv"`` (default — the founding collective, byte-identical
    semantics and signatures to the pre-patterns era), ``"allgatherv"``, or
    ``"reduce_scatter"``.  ``send_counts`` is always the *expanded* square
    ``[P, P]`` matrix — the family-specific INIT entry points
    (``allgatherv_init`` / ``reduce_scatter_init``) expand their ``[P]``
    count vectors before building the spec, so every downstream consumer
    (signature digest, displacements, capacity schedule) is shared.
    """

    send_counts: Any                      # [P, P] host array, rows = sender
    feature_shape: tuple[int, ...]        # trailing dims of one row
    dtype: Any
    axis: tuple[str, ...]                 # 1 mesh axis, or (outer, inner)
    variant: str = "fence"
    lock_schedule: str = "ring"           # ring | pairwise
    tile_rows: int = md.TILE_ROWS
    pack_impl: str = "jnp"                # jnp | pallas | fused
    baked_metadata: bool = True           # False: seed-style in-graph maps (A/B)
    codec: str = "identity"               # wire codec (parallel.wirecodec)
    # Per-group leader permutation for fence_hierarchy (leader.py re-bakes);
    # None means identity (round-robin).  Canonicalized so identity specs
    # key exactly as before this dimension existed.
    hier_leader_perm: tuple[tuple[int, ...], ...] | None = None
    collective: str = "alltoallv"         # exchange family (core.patterns)

    def __post_init__(self):
        if self.variant not in VARIANTS:
            raise ValueError(f"variant must be one of {VARIANTS}")
        pattern = patterns_mod.get(self.collective)   # validates the name
        if self.collective != "alltoallv":
            if self.variant not in pattern.supported_variants:
                raise ValueError(
                    f"collective {self.collective!r} supports variants "
                    f"{pattern.supported_variants}, not {self.variant!r}")
            if self.codec != "identity" and not pattern.supports_codec:
                raise ValueError(
                    f"collective {self.collective!r} forbids wire codecs "
                    "(reduced/replicated rows cannot ride an encoded wire)")
            if self.pack_impl != "jnp":
                raise ValueError(
                    f"collective {self.collective!r} uses the jnp "
                    "pack/unpack path (kernel tile shapes are baked for "
                    "the alltoallv bucket layout)")
            if not self.baked_metadata:
                raise ValueError(
                    f"collective {self.collective!r} requires "
                    "baked_metadata=True (no in-graph A/B twin exists)")
            if self.hier_leader_perm is not None:
                raise ValueError(
                    f"collective {self.collective!r} has no leader roles "
                    "(its hierarchy is nested gathers, not a leader "
                    "schedule); hier_leader_perm must be None")
        if self.hier_leader_perm is not None:
            lp = tuple(tuple(int(x) for x in row)
                       for row in self.hier_leader_perm)
            for row in lp:
                if sorted(row) != list(range(len(row))):
                    raise ValueError(
                        f"hier_leader_perm row {row} is not a permutation")
            if md.leader_perm_is_identity(lp):
                lp = None                 # identity keys as the perm-free era
            elif self.variant != "fence_hierarchy":
                raise ValueError("hier_leader_perm only applies to "
                                 "variant='fence_hierarchy'")
            object.__setattr__(self, "hier_leader_perm", lp)
        if self.codec not in wirecodec.CODECS:
            raise ValueError(f"unknown wire codec {self.codec!r}; "
                             f"have {sorted(wirecodec.CODECS)}")
        if self.codec != "identity" and self.variant == "ragged":
            raise ValueError("wire codecs put decoded rows through the "
                             "pack/unpack path; variant='ragged' writes raw "
                             "wire bytes into the window and supports "
                             "codec='identity' only")
        if self.codec != "identity" and not self.baked_metadata:
            raise ValueError("wire codecs require baked_metadata=True (the "
                             "A/B in-graph mode measures the uncoded seed "
                             "path)")
        if self.variant == "fence_hierarchy" and len(self.axis) != 2:
            raise ValueError("fence_hierarchy needs axis=(outer, inner)")
        if self.variant == "ragged" and len(self.axis) != 1:
            raise ValueError("variant ragged takes a single axis")
        if len(self.axis) not in (1, 2):
            # fence/lock accept a 2-axis mesh factorization too (the
            # exchange then runs over the linearized axis pair), so the
            # auto dispatcher can compare flat and hierarchical variants
            # on the same grouped mesh.
            raise ValueError(f"axis must name 1 or 2 mesh axes, got {self.axis}")
        if self.variant == "fence_hierarchy" and not self.baked_metadata:
            raise ValueError("fence_hierarchy is driven by the INIT-baked "
                             "two-stage tables; it requires baked_metadata")
        if self.pack_impl not in ("jnp", "pallas", "fused"):
            raise ValueError(f"unknown pack_impl {self.pack_impl!r}")
        if self.pack_impl == "fused" and self.variant not in (
                "fence", "fence_hierarchy"):
            raise ValueError("pack_impl='fused' fuses the gather into the "
                             "RMA kernel; it requires variant='fence' or "
                             "'fence_hierarchy'")
        if self.pack_impl == "fused" and self.variant == "fence" \
                and len(self.axis) != 1:
            raise ValueError("the fused fence kernel exchanges over a "
                             "single mesh axis")
        if self.pack_impl == "fused" and not self.baked_metadata:
            raise ValueError("pack_impl='fused' needs host-baked index maps")


class ExchangePlan:
    """Persistent request object: metadata + window + compiled executable.

    Collective-agnostic: the spec's ``collective`` resolves to an
    ``ExchangePattern`` (``core.patterns``) that owns the family-specific
    pieces — count-matrix structure, buffer geometry, table baking,
    identity detection, and (for non-alltoallv families) the epoch body.
    Everything else here is shared across families.
    """

    def __init__(self, spec: ExchangeSpec, mesh: jax.sharding.Mesh,
                 window_cache: WindowCache | None = None, warm=None):
        """``warm`` is an optional plan-store artifact (duck-typed: anything
        with ``index_tables`` / ``hier_schedule`` attributes).  When it
        carries the tables this spec needs, the expensive host-side bakes
        are skipped and the artifact's tensors are uploaded instead; a
        geometry mismatch raises WarmStartError (caller falls back cold)."""
        self.spec = spec
        self.mesh = mesh
        self.warm_loaded = False
        self.pattern = patterns_mod.get(spec.collective)
        t0 = time.perf_counter()

        sc = np.asarray(spec.send_counts, dtype=np.int64)
        self.p = sc.shape[0]
        self.pattern.validate_matrix(sc)
        axis_sizes = [mesh.shape[a] for a in spec.axis]
        p_mesh = int(np.prod(axis_sizes))
        if p_mesh != self.p:
            raise ValueError(
                f"counts are {self.p}x{self.p} but axis {spec.axis} has size {p_mesh}")

        # --- metadata exchange (host-side; the INIT-time MPI_Alltoall) ---
        self.send_counts = sc
        self.recv_counts = md.recv_counts(sc)
        self.sdispls = md.displacements(sc)
        self.rdispls = md.displacements(self.recv_counts)
        self.put_displs = md.put_displacements(sc)

        # --- capacity schedule + sparsity analysis ---
        self.capacity = md.global_capacity(sc, spec.tile_rows)
        if spec.variant == "lock":
            # Schedule-aware: ring and XOR rounds gate on different diagonals.
            self.round_capacities = (
                md.xor_round_capacities(sc, spec.tile_rows)
                if spec.lock_schedule == "pairwise"
                else md.ring_round_capacities(sc, spec.tile_rows))
        else:
            self.round_capacities = None
        self.lock_rounds_total = self.p - 1 if spec.variant == "lock" else None
        self.lock_rounds_active = (
            int(md.active_round_schedule(self.round_capacities).size)
            if spec.variant == "lock" else None)
        # --- buffer geometry (SPMD: padded to the max over ranks) ---
        # Pattern-owned: allgatherv sends ONE bucket and receives P;
        # reduce_scatter sends P buckets and receives one reduced bucket.
        self.send_rows = self.pattern.send_rows(sc, spec.tile_rows)
        self.recv_rows = self.pattern.recv_rows(sc, spec.tile_rows)

        # --- leader-combined two-stage schedule (alltoallv hierarchy) ---
        # Other families' fence_hierarchy is nested gathers over the
        # (outer, inner) axes — no leader schedule to bake.
        if spec.variant == "fence_hierarchy" and spec.collective == "alltoallv":
            self.p_outer, self.p_inner = axis_sizes
            want_perm = md.normalize_leader_perm(
                spec.hier_leader_perm, self.p_outer, self.p_inner)
            warm_sched = getattr(warm, "hier_schedule", None)
            if warm_sched is not None:
                if (warm_sched.p_outer != self.p_outer
                        or warm_sched.p_inner != self.p_inner
                        or warm_sched.unpack_src.shape != (self.p, self.recv_rows)):
                    raise WarmStartError(
                        f"hier schedule geometry ({warm_sched.p_outer}x"
                        f"{warm_sched.p_inner}, unpack {warm_sched.unpack_src.shape})"
                        f" does not fit plan ({self.p_outer}x{self.p_inner},"
                        f" recv_rows {self.recv_rows})")
                if warm_sched.leader_perm != want_perm:
                    raise WarmStartError(
                        f"hier schedule leader_perm {warm_sched.leader_perm} "
                        f"does not match requested {want_perm}")
                self.hier_schedule = warm_sched
                self.warm_loaded = True
            else:
                INIT_STATS.bump("table_bakes")
                with TRACER.span("hier_schedule_bake", "init.bake",
                                 p=self.p, variant=spec.variant):
                    self.hier_schedule = md.hier_two_stage_schedule(
                        sc, self.p_outer, self.p_inner, self.recv_rows,
                        spec.tile_rows, leader_perm=want_perm)
            self.hierarchy_remote_needed = self.hier_schedule.remote_needed
            self.cross_group_puts = self.hier_schedule.cross_group_puts
        else:
            if spec.variant == "fence_hierarchy":
                self.p_outer, self.p_inner = axis_sizes
            else:
                self.p_outer = self.p_inner = None
            self.hier_schedule = None
            self.hierarchy_remote_needed = None
            self.cross_group_puts = None

        row_elems = int(np.prod(spec.feature_shape)) if spec.feature_shape else 1
        row_bytes = row_elems * jnp.dtype(spec.dtype).itemsize
        self.wire_bytes = self._wire_bytes(sc, row_elems)
        self.signature = md.PatternSignature.build(
            sc, spec.feature_shape, spec.dtype, spec.variant, spec.axis, row_bytes,
            lock_schedule=spec.lock_schedule, tile_rows=spec.tile_rows,
            pack_impl=spec.pack_impl, baked_metadata=spec.baked_metadata,
            axis_sizes=axis_sizes, codec=spec.codec,
            hier_leader_perm=spec.hier_leader_perm or (),
            collective=spec.collective)

        # --- window (paper: reuse while total_recv_bytes unchanged) ---
        self._window_cache = window_cache if window_cache is not None else WindowCache()
        self.window: Window = self._window_cache.get(
            self.recv_rows, spec.feature_shape, spec.dtype)

        # --- constant metadata tables (baked into the executable) ---
        self._sc_tbl = jnp.asarray(sc, jnp.int32)
        self._sd_tbl = jnp.asarray(self.sdispls, jnp.int32)
        self._rc_tbl = jnp.asarray(self.recv_counts, jnp.int32)
        self._rd_tbl = jnp.asarray(self.rdispls, jnp.int32)
        self._put_tbl = jnp.asarray(self.put_displs, jnp.int32)

        self._x_sharding = NamedSharding(self.mesh, P(spec.axis if len(spec.axis) > 1
                                                      else spec.axis[0]))

        # --- host-baked pack/unpack index maps ---------------------------
        # Computed once on host, uploaded once as device tables *sharded over
        # the communication axis*: each shard holds exactly its own row
        # (O(P*C) per device, not the O(P^2*C) a replicated constant would
        # cost at production rank counts), and no per-call index-map
        # arithmetic remains in the compiled START program.
        # (baked_metadata=False keeps the seed's in-graph recomputation for
        # honest A/B benchmarking.)
        if spec.variant == "fence_hierarchy" and spec.collective == "alltoallv":
            # The two-stage schedule carries its own gather/unpack tables
            # (s1 pack -> s2 slab build -> s3 scatter -> final unpack).
            self.index_tables = None
            self._table_host = self.hier_schedule.tables
        elif spec.baked_metadata and spec.variant != "ragged":
            want_pack, want_unpack = self.pattern.table_shapes(
                self.p, self.capacity, self.recv_rows)
            warm_tables = getattr(warm, "index_tables", None)
            if warm_tables is not None:
                if (warm_tables.pack_src.shape != want_pack
                        or warm_tables.unpack_src.shape != want_unpack):
                    raise WarmStartError(
                        f"baked tables {warm_tables.pack_src.shape}/"
                        f"{warm_tables.unpack_src.shape} do not fit "
                        f"{spec.collective} plan (want {want_pack}/"
                        f"{want_unpack})")
                tables = warm_tables
                self.warm_loaded = True
            else:
                INIT_STATS.bump("table_bakes")
                with TRACER.span("index_table_bake", "init.bake",
                                 p=self.p, variant=spec.variant,
                                 collective=spec.collective):
                    tables = self.pattern.bake_tables(sc, self.capacity,
                                                      self.recv_rows)
            self.index_tables = tables
            self._table_host = (tables.pack_src, tables.pack_valid,
                                tables.unpack_src, tables.unpack_valid)
        else:
            self.index_tables = None
            self._table_host = ()

        # Uniform all-equal patterns (every pair exchanges exactly the
        # bucket capacity, tile-aligned) have identity pack/unpack maps:
        # the ragged layout IS the bucketed layout.  The embedded form
        # elides both gathers for them (MoE dispatch hits this path).
        # Derived from the O(P^2) counts alone — uniform counts equal to
        # the capacity imply identity by construction of
        # ``baked_index_tables`` — NOT by scanning the tables themselves:
        # on a warm start those are read-only memmaps whose bytes a
        # one-header-read load must never page in.
        self.identity_maps = bool(
            self.index_tables is not None
            and self.pattern.identity_maps(sc, self.capacity,
                                           self.send_rows, self.recv_rows))

        self.shard_fn = self._build_shard_fn()
        self._embedded = None
        self._table_args_cached: tuple | None = None
        self._compiled = None
        self.init_host_seconds = time.perf_counter() - t0
        self.init_compile_seconds = 0.0
        self.starts = 0
        # EXECUTE telemetry: start()/start_pipelined() record their epoch
        # dispatch wall time into this plan's ring (keyed by signature
        # digest) unless disabled — drivers that time whole epochs
        # themselves flip record_starts off and call record_epoch instead.
        self.record_starts = True
        if self.warm_loaded:
            INIT_STATS.bump("warm_inits")
        else:
            INIT_STATS.bump("cold_inits")
        # Ring args of this plan's ``plan.start`` and recorded ``epoch``
        # spans, built once.
        self._digest = self.signature.digest
        self._epoch_span_args = {"digest": self._digest,
                                 "variant": spec.variant,
                                 "collective": spec.collective}
        if TRACER.enabled:
            TRACER.emit_span("plan_init", "init", t0, time.perf_counter(),
                             {"digest": self._digest,
                              "variant": spec.variant,
                              "collective": spec.collective,
                              "warm": self.warm_loaded,
                              "p": self.p,
                              "codec": spec.codec})

    def _wire_bytes(self, sc: np.ndarray, row_elems: int) -> int | None:
        """Bytes one standalone epoch's collective carries between distinct
        chips, padding included, summed over the mesh: every off-chip
        bucket at the fence capacity, each lock round at its own capacity,
        the exact off-diagonal rows for ragged.  Rows travel at the wire
        codec's width, plus a 4-byte fp32 scale per row for scaled codecs.
        None where the plan keeps no closed form (hierarchy schedules,
        other collective families)."""
        spec = self.spec
        if spec.collective != "alltoallv":
            return None
        if spec.variant == "fence":
            rows = self.p * (self.p - 1) * self.capacity
        elif spec.variant == "lock":
            rows = self.p * sum(min(int(c), self.capacity)
                                for c in self.round_capacities[1:])
        elif spec.variant == "ragged":
            rows = int(sc.sum() - np.trace(sc))
        else:
            return None
        if spec.codec == "identity":
            return rows * row_elems * jnp.dtype(spec.dtype).itemsize
        codec = wirecodec.get(spec.codec)
        scale = 4 if codec.has_scales else 0
        return rows * (row_elems * jnp.dtype(codec.wire_dtype).itemsize + scale)

    # -- geometry ------------------------------------------------------------
    @property
    def _table_args(self) -> tuple:
        """Axis-sharded device copies of the baked tables, uploaded lazily on
        the first standalone ``compile``/``start``.  device_put straight from
        numpy is a sharded host-to-device upload, so no device ever holds
        more than its own O(P*C) row (a jnp.asarray first would commit the
        whole O(P^2*C) table to device 0 before resharding).  Embedded-only
        plans never trigger the upload — their tables enter the host
        program as compile-time constants instead."""
        if self._table_args_cached is None:
            self._table_args_cached = tuple(
                jax.device_put(t, self._x_sharding) for t in self._table_host)
        return self._table_args_cached

    @property
    def global_send_shape(self) -> tuple[int, ...]:
        return (self.p * self.send_rows,) + self.spec.feature_shape

    @property
    def global_recv_shape(self) -> tuple[int, ...]:
        return (self.p * self.recv_rows,) + self.spec.feature_shape

    def _axis_index(self) -> jax.Array:
        ax = self.spec.axis
        if len(ax) == 1:
            return jax.lax.axis_index(ax[0])
        return jax.lax.axis_index(ax[0]) * self.mesh.shape[ax[1]] + jax.lax.axis_index(ax[1])

    # -- per-shard START body --------------------------------------------------
    def _build_shard_fn(self) -> Callable:
        spec = self.spec
        if spec.collective != "alltoallv":
            # Pattern-owned epoch body (pack -> exchange[+reduce] -> unpack);
            # this wrapper adds only the window write-through.
            epoch = self.pattern.build_epoch(self)

            def pattern_shard_fn(x: jax.Array, window: jax.Array,
                                 *tables) -> jax.Array:
                rows = tuple(t[0] for t in tables)
                out = epoch(x, *rows)
                rvalid = rows[3]
                mask = rvalid.reshape(rvalid.shape + (1,) * (out.ndim - 1))
                return jnp.where(mask, out, window)

            return pattern_shard_fn
        p, cap = self.p, self.capacity
        # fence/lock over a 2-axis mesh exchange over the linearized pair.
        a2a_axis = spec.axis[0] if len(spec.axis) == 1 else tuple(spec.axis)

        if spec.pack_impl in ("pallas", "fused"):
            from repro.kernels import ops as kops
            pack, unpack = kops.pack, kops.unpack
        else:
            kops = None
            pack, unpack = variants.pack_rows, variants.unpack_rows
        pack, unpack = _scoped(A2A_PACK, pack), _scoped(A2A_UNPACK, unpack)
        pack_rows = _scoped(A2A_PACK, variants.pack_rows)
        unpack_rows = _scoped(A2A_UNPACK, variants.unpack_rows)
        hier_exchange = _scoped(A2A_EXCHANGE,
                                variants.hierarchy_exchange_combined)
        # Non-identity codec: the heavy gather/exchange below runs at wire
        # width (encode fused into the pack path); per-row fp32 scales ride
        # the same variant exchange as a tiny [rows, 1] side channel (every
        # exchange body is a row-preserving permutation, so the scale of
        # row r travels with row r by construction).
        codec = wirecodec.get(spec.codec) if spec.codec != "identity" else None
        out_dtype = jnp.dtype(spec.dtype)

        def shard_fn(x: jax.Array, window: jax.Array, *tables) -> jax.Array:
            """Epoch body.  ``tables`` (baked mode) are this shard's rows of
            the INIT-baked index maps — the axis sharding already selected
            rank i's row, so the hot path starts at the gather itself.  In
            A/B mode (baked_metadata=False) it is empty and the seed's
            in-graph recomputation below runs every epoch instead."""
            i = self._axis_index()
            if spec.variant == "ragged":
                with jax.named_scope(A2A_EXCHANGE):
                    return variants.ragged_exchange(
                        x, window,
                        self._sd_tbl[i], self._sc_tbl[i],
                        self._put_tbl[i], self._rc_tbl[i], a2a_axis)

            scales = None
            if codec is not None:
                x, scales = codec.encode(x)
            # Scale inlining (see wirecodec): reference-gather paths fold
            # the [rows, 1] scale channel into extra wire lanes so the
            # exchange stays a single collective; kernel pack paths and the
            # hierarchy schedule keep the side channel.
            k = (wirecodec.inline_lanes(x, scales)
                 if spec.variant != "fence_hierarchy"
                 and spec.pack_impl not in ("pallas", "fused") else 0)
            if k:
                x, scales = wirecodec.inline_rows(x, scales, k), None

            if spec.variant == "fence_hierarchy":
                # Leader-combined three-hop epoch on the two-stage tables.
                rows = tuple(t[0] for t in tables)
                if spec.pack_impl == "fused":
                    stage2 = partial(
                        kops.fused_hier_leader_exchange,
                        schedule=self.hier_schedule,
                        outer_axis=spec.axis[0], inner_axis=spec.axis[1],
                        mesh_axes=tuple(self.mesh.axis_names))
                else:
                    stage2 = None
                buckets = hier_exchange(
                    x, rows[:6], self.hier_schedule,
                    spec.axis[0], spec.axis[1], stage2_impl=stage2)
                rsrc, rvalid = rows[6], rows[7]
                if scales is not None:
                    sc_buckets = hier_exchange(
                        scales, rows[:6], self.hier_schedule,
                        spec.axis[0], spec.axis[1], stage2_impl=None)
            else:
                if spec.baked_metadata:
                    src, valid, rsrc, rvalid = (t[0] for t in tables)
                else:
                    src, valid = variants.pack_index_map_in_graph(
                        self._sc_tbl[i], self._sd_tbl[i], p, cap)
                    rsrc, rvalid = variants.unpack_index_map_in_graph(
                        self._rc_tbl[i], self._rd_tbl[i], p, cap, self.recv_rows)

                def exchange(packed):
                    with jax.named_scope(A2A_EXCHANGE):
                        if spec.variant == "fence":
                            return variants.fence_exchange(packed, a2a_axis)
                        return variants.lock_exchange(
                            packed, a2a_axis, p, cap,
                            self.round_capacities, spec.lock_schedule)

                if spec.pack_impl == "fused":
                    # Pack fused into the remote-DMA kernel: rows are gathered
                    # straight into the put source tile, never materializing the
                    # padded [P*C, F] intermediate in HBM.
                    with jax.named_scope(A2A_EXCHANGE):
                        buckets = kops.fused_pack_alltoallv(
                            x, src, valid, p=p, capacity=cap, axis=a2a_axis,
                            mesh_axes=tuple(self.mesh.axis_names))
                else:
                    buckets = exchange(pack(x, src, valid))
                if scales is not None:
                    sc_buckets = exchange(pack_rows(scales, src, valid))

            out = unpack(buckets, rsrc, rvalid)
            if codec is not None:
                if k:
                    out, sc_out = wirecodec.split_rows(out, k)
                else:
                    sc_out = (unpack_rows(sc_buckets, rsrc, rvalid)
                              if scales is not None else None)
                out = codec.decode(out, sc_out, out_dtype)
            # Write-through into the window: padding keeps stale window bytes
            # (real RMA semantics) and lets XLA alias the donated buffer.
            mask = rvalid.reshape(rvalid.shape + (1,) * (out.ndim - 1))
            return jnp.where(mask, out, window)

        return shard_fn

    # -- embedded form --------------------------------------------------------
    def embed(self) -> Callable:
        """Traced epoch body for use INSIDE an enclosing shard_map program.

        Returns ``fn(x) -> recv``: ``x`` is this shard's ragged send buffer
        ``[send_rows, F...]`` and the result is the ragged recv buffer
        ``[recv_rows, F...]`` (invalid padding rows zeroed — an embedded
        plan has no window to write through).  The INIT-baked index tables
        enter the host program as replicated constants, row-selected by
        ``axis_index`` — they are compiled into the *host's* executable
        once, which is the embedded rendition of the INIT/EXECUTE split.
        Uniform identity patterns (``self.identity_maps``) skip the
        pack/unpack gathers entirely, so the epoch is the bare exchange.

        The enclosing shard_map must span (at least) ``spec.axis``; the
        caller owns jit/compile/donation.  ``variant="ragged"`` cannot be
        embedded (it puts into the plan-owned window) and A/B in-graph mode
        has nothing baked to embed; both raise.
        """
        if self._embedded is not None:
            return self._embedded
        spec = self.spec
        if spec.variant == "ragged":
            raise ValueError("variant='ragged' puts into the plan-owned "
                             "window and cannot be embedded")
        if not spec.baked_metadata:
            raise ValueError("embed() requires baked_metadata=True (the "
                             "A/B in-graph mode has no tables to embed)")
        if spec.collective != "alltoallv":
            if self.identity_maps:
                # Uniform tile-aligned pattern: the epoch is the bare
                # pattern exchange — no tables ever materialize on device
                # (the Ulysses positions gather hits this path).
                embedded = self.pattern.build_exchange(self)
            else:
                epoch = self.pattern.build_epoch(self)
                tbls = tuple(jnp.asarray(t) for t in self._table_host)

                def embedded(x: jax.Array) -> jax.Array:
                    i = self._axis_index()
                    return epoch(x, tbls[0][i], tbls[1][i],
                                 tbls[2][i], tbls[3][i])

            self._embedded = embedded
            return embedded
        p, cap = self.p, self.capacity
        a2a_axis = spec.axis[0] if len(spec.axis) == 1 else tuple(spec.axis)
        codec = wirecodec.get(spec.codec) if spec.codec != "identity" else None
        out_dtype = jnp.dtype(spec.dtype)
        pack_rows = _scoped(A2A_PACK, variants.pack_rows)
        unpack_rows = _scoped(A2A_UNPACK, variants.unpack_rows)
        hier_exchange = _scoped(A2A_EXCHANGE,
                                variants.hierarchy_exchange_combined)

        if spec.variant == "fence_hierarchy":
            tbls = tuple(jnp.asarray(t) for t in self._table_host)
            sched = self.hier_schedule
            if spec.pack_impl == "fused":
                from repro.kernels import ops as kops
                stage2 = partial(
                    kops.fused_hier_leader_exchange, schedule=sched,
                    outer_axis=spec.axis[0], inner_axis=spec.axis[1],
                    mesh_axes=tuple(self.mesh.axis_names))
            else:
                stage2 = None

            def embedded(x: jax.Array) -> jax.Array:
                i = self._axis_index()
                rows = tuple(t[i] for t in tbls)
                scales = None
                if codec is not None:
                    x_wire, scales = codec.encode(x)
                else:
                    x_wire = x
                buckets = hier_exchange(
                    x_wire, rows[:6], sched, spec.axis[0], spec.axis[1],
                    stage2_impl=stage2)
                out = unpack_rows(buckets, rows[6], rows[7])
                if codec is not None:
                    sc_out = None
                    if scales is not None:
                        sc_buckets = hier_exchange(
                            scales, rows[:6], sched, spec.axis[0],
                            spec.axis[1], stage2_impl=None)
                        sc_out = unpack_rows(sc_buckets, rows[6], rows[7])
                    out = codec.decode(out, sc_out, out_dtype)
                return out
        elif self.identity_maps:
            # Uniform identity pattern (the MoE bucket layout): both gathers
            # vanish, no tables are ever materialized on device, and
            # pack_impl is moot — the epoch IS the bare exchange (plus the
            # wire encode/decode and its scale side channel under a codec).
            def bare_exchange(payload):
                with jax.named_scope(A2A_EXCHANGE):
                    if spec.variant == "fence":
                        return variants.fence_exchange(payload, a2a_axis)
                    return variants.lock_exchange(
                        payload, a2a_axis, p, cap,
                        self.round_capacities, spec.lock_schedule)

            def embedded(x: jax.Array) -> jax.Array:
                if codec is None:
                    return bare_exchange(x)
                wire, scales = codec.encode(x)
                k = wirecodec.inline_lanes(wire, scales)
                if k:
                    # Scales ride inline as extra wire lanes: one collective
                    # instead of payload + side channel (see wirecodec).
                    out, sc_out = wirecodec.split_rows(
                        bare_exchange(wirecodec.inline_rows(wire, scales, k)),
                        k)
                else:
                    out = bare_exchange(wire)
                    sc_out = (bare_exchange(scales)
                              if scales is not None else None)
                return codec.decode(out, sc_out, out_dtype)
        else:
            # Honor spec.pack_impl so the embedded epoch runs the same
            # pack/unpack implementation the autotuner measured through the
            # standalone shard_fn (fused = gather fused into the fence RMA
            # kernel; pallas = kernel gathers; jnp = reference gathers).
            tbls = tuple(jnp.asarray(t) for t in self._table_host)
            if spec.pack_impl in ("pallas", "fused"):
                from repro.kernels import ops as kops
                pack_fn, unpack_fn = kops.pack, kops.unpack
            else:
                kops = None
                pack_fn, unpack_fn = variants.pack_rows, variants.unpack_rows
            pack_fn = _scoped(A2A_PACK, pack_fn)
            unpack_fn = _scoped(A2A_UNPACK, unpack_fn)

            def embedded(x: jax.Array) -> jax.Array:
                i = self._axis_index()
                scales = None
                if codec is not None:
                    x, scales = codec.encode(x)
                # Inline the scale channel into the payload rows when the
                # reference gathers run (kernel pack paths keep the side
                # channel — their tile shapes are baked for the bare wire).
                k = (wirecodec.inline_lanes(x, scales)
                     if spec.pack_impl not in ("pallas", "fused") else 0)
                if k:
                    x, scales = wirecodec.inline_rows(x, scales, k), None

                def exchange(packed):
                    with jax.named_scope(A2A_EXCHANGE):
                        if spec.variant == "fence":
                            return variants.fence_exchange(packed, a2a_axis)
                        return variants.lock_exchange(
                            packed, a2a_axis, p, cap,
                            self.round_capacities, spec.lock_schedule)

                if spec.pack_impl == "fused" and spec.variant == "fence":
                    with jax.named_scope(A2A_EXCHANGE):
                        buckets = kops.fused_pack_alltoallv(
                            x, tbls[0][i], tbls[1][i], p=p, capacity=cap,
                            axis=a2a_axis,
                            mesh_axes=tuple(self.mesh.axis_names))
                else:
                    buckets = exchange(pack_fn(x, tbls[0][i], tbls[1][i]))
                out = unpack_fn(buckets, tbls[2][i], tbls[3][i])
                if codec is not None:
                    sc_out = None
                    if k:
                        out, sc_out = wirecodec.split_rows(out, k)
                    elif scales is not None:
                        sc_buckets = exchange(pack_rows(
                            scales, tbls[0][i], tbls[1][i]))
                        sc_out = unpack_rows(
                            sc_buckets, tbls[2][i], tbls[3][i])
                    out = codec.decode(out, sc_out, out_dtype)
                return out

        self._embedded = embedded
        return embedded

    # -- AOT compile ----------------------------------------------------------
    def compile(self) -> "ExchangePlan":
        if self._compiled is not None:
            return self
        t0 = time.perf_counter()
        with TRACER.span("plan_compile", "init",
                         digest=self.signature.digest,
                         variant=self.spec.variant):
            self._compile_impl()
        self.init_compile_seconds = time.perf_counter() - t0
        return self

    def _compile_impl(self) -> None:
        n_tbl = len(self._table_args)
        fn = shard_map(
            self.shard_fn, mesh=self.mesh,
            in_specs=(self._x_sharding.spec,) * (2 + n_tbl),
            out_specs=self._x_sharding.spec, check_vma=False)
        jitted = jax.jit(fn, donate_argnums=(1,))
        x_s = jax.ShapeDtypeStruct(self.global_send_shape, self.spec.dtype,
                                   sharding=self._x_sharding)
        w_s = jax.ShapeDtypeStruct(self.global_recv_shape, self.spec.dtype,
                                   sharding=self._x_sharding)
        t_s = tuple(jax.ShapeDtypeStruct(t.shape, t.dtype,
                                         sharding=self._x_sharding)
                    for t in self._table_args)
        self._compiled = jitted.lower(x_s, w_s, *t_s).compile()

    # -- START / WAIT / FREE ----------------------------------------------------
    def start(self, sendbuf: jax.Array) -> jax.Array:
        """Launch one epoch. Returns the (async) recv buffer.

        Traced as ``plan.start``: the dispatch, which on an accelerator
        returns before the epoch ends (``plan.wait`` is the block)."""
        self.compile()
        win = self.window.materialize(self.global_recv_shape, self._x_sharding)
        with TRACER.span("plan.start", "execute", **self._epoch_span_args):
            t0 = time.perf_counter()
            out = self._compiled(sendbuf, win, *self._table_args)
            t1 = time.perf_counter()
        if self.record_starts:
            EXEC_TELEMETRY.record(self._digest, t1 - t0)
        self.window.adopt(out)   # donated-in, aliased-out: window reuse
        self.starts += 1
        self._count_epoch()
        return out

    def start_pipelined(self, sendbuf: jax.Array, depth: int = 2) -> jax.Array:
        """Launch one epoch against the multi-slot window.

        Epochs rotate through ``depth`` window slots, so epoch k+1's donated
        buffer is never epoch k's output: dispatch of k+1 does not wait for
        k's consumers, letting back-to-back epochs overlap.  Callers must not
        read an epoch's output after ``depth`` further ``start_pipelined``
        calls (its slot has been recycled — the RMA exposure-epoch rule).
        ``depth=2`` is classic double buffering; deeper pipelines trade
        window memory for more epochs in flight (useful when a consumer
        drains several epochs at once, e.g. the hierarchy benchmark's
        batched drains).
        """
        self.compile()
        slot = self.starts % depth
        win = self.window.materialize(
            self.global_recv_shape, self._x_sharding, slot=slot)
        with TRACER.span("plan.start", "execute", **self._epoch_span_args):
            t0 = time.perf_counter()
            out = self._compiled(sendbuf, win, *self._table_args)
            t1 = time.perf_counter()
        if self.record_starts:
            EXEC_TELEMETRY.record(self._digest, t1 - t0)
        self.window.adopt(out, slot=slot)
        self.starts += 1
        self._count_epoch()
        return out

    def _count_epoch(self) -> None:
        COUNTERS.add("a2a.epochs")
        if self.wire_bytes is not None:
            COUNTERS.add("a2a.wire_bytes", self.wire_bytes)

    @staticmethod
    def wait(recvbuf: jax.Array) -> jax.Array:
        with TRACER.span("plan.wait", "execute"):
            return jax.block_until_ready(recvbuf)

    def record_epoch(self, seconds: float, t_end: "float | None" = None) -> None:
        """Record one externally timed epoch into this plan's telemetry
        ring.  The path for consumers whose epochs run inside a larger
        jitted program (``embed()`` bodies cannot self-time) or who want
        end-to-end start+wait wall time instead of dispatch time.

        ``t_end`` anchors the emitted trace span's end (perf_counter
        seconds).  Callers that also emit their own enclosing span (the
        trainer's ``train_step``) must pass the timestamp their window
        measurement straddles, so the backdated epoch span nests cleanly
        instead of spilling past the caller's span by the time it took to
        reach this call."""
        EXEC_TELEMETRY.record(self._digest, float(seconds))
        if TRACER.enabled:
            t1 = time.perf_counter() if t_end is None else float(t_end)
            TRACER.emit_span("epoch", "execute", t1 - float(seconds), t1,
                             self._epoch_span_args)

    def record_epoch_ranks(self, seconds_by_rank) -> None:
        """Per-rank epoch times into the ``(digest, rank)`` rank rings —
        the per-rank signal skew attribution (and the hierarchy leader
        re-assignment roadmap item) consumes.  Accepts a mapping
        ``{rank: seconds}`` or a dense sequence indexed by rank."""
        items = (seconds_by_rank.items()
                 if hasattr(seconds_by_rank, "items")
                 else enumerate(seconds_by_rank))
        for rank, s in items:
            EXEC_TELEMETRY.record_rank(self._digest, int(rank), float(s))

    def rank_summaries(self) -> dict[int, dict]:
        """Per-rank ring summaries for this plan, keyed by rank."""
        return EXEC_TELEMETRY.rank_summary(self._digest)

    @property
    def epoch_ring(self):
        """This plan's EXECUTE telemetry ring (``core._exec_stats``)."""
        return EXEC_TELEMETRY.ring(self.signature.digest)

    def free(self) -> None:
        self._compiled = None
        self.window.release()

    # -- reporting ----------------------------------------------------------
    def metadata_summary(self) -> dict:
        row_bytes = (int(np.prod(self.spec.feature_shape)) if self.spec.feature_shape
                     else 1) * jnp.dtype(self.spec.dtype).itemsize
        return {
            "collective": self.spec.collective,
            "variant": self.spec.variant,
            "p": self.p,
            "capacity_rows": self.capacity,
            "send_rows": self.send_rows,
            "recv_rows": self.recv_rows,
            "payload_bytes_per_rank": int(self.send_counts.sum(axis=1).max()) * row_bytes,
            "padded_bytes_per_rank": self.p * self.capacity * row_bytes,
            "total_recv_bytes": self.signature.total_recv_bytes,
            "init_host_seconds": self.init_host_seconds,
            "init_compile_seconds": self.init_compile_seconds,
            "window_generation": self.window.generation,
            "baked_metadata": self.spec.baked_metadata,
            "pack_impl": self.spec.pack_impl,
            "codec": self.spec.codec,
            "warm_loaded": self.warm_loaded,
            "identity_maps": self.identity_maps,
            "lock_rounds_active": self.lock_rounds_active,
            "lock_rounds_total": self.lock_rounds_total,
            "hierarchy_remote_needed": self.hierarchy_remote_needed,
            # Inter-group messages per epoch (leader-combined hierarchy):
            # O((P/g)^2); the flat fence epoch posts P*(P-1).
            "cross_group_puts": self.cross_group_puts,
        }


class PlanCache:
    """Signature-keyed cache of plans (persistent requests) with statistics."""

    def __init__(self, window_cache: WindowCache | None = None):
        self._plans: dict[md.PatternSignature, ExchangePlan] = {}
        # variant="auto" decisions, keyed by the pattern's auto-signature:
        # {"variant": str, "times": {candidate: seconds}}.  Cached so a
        # recurring pattern pays the measurement sweep once per process
        # (the same amortization rule as the plans themselves).
        self.auto_choices: dict[md.PatternSignature, dict] = {}
        self.window_cache = window_cache if window_cache is not None else WindowCache()
        self.hits = 0
        self.misses = 0

    def get(self, spec: ExchangeSpec, mesh: jax.sharding.Mesh,
            store=None) -> ExchangePlan:
        """Fetch-or-build.  ``store`` (a ``repro.planstore.PlanStore``, duck-
        typed) is the disk tier behind this in-memory one: a miss here
        consults it for a warm artifact before baking, and a cold build
        publishes its artifacts back for the next process."""
        row_elems = int(np.prod(spec.feature_shape)) if spec.feature_shape else 1
        row_bytes = row_elems * jnp.dtype(spec.dtype).itemsize
        sig = md.PatternSignature.build(
            np.asarray(spec.send_counts), spec.feature_shape, spec.dtype,
            spec.variant, spec.axis, row_bytes,
            lock_schedule=spec.lock_schedule, tile_rows=spec.tile_rows,
            pack_impl=spec.pack_impl, baked_metadata=spec.baked_metadata,
            axis_sizes=tuple(mesh.shape[a] for a in spec.axis),
            codec=spec.codec,
            hier_leader_perm=spec.hier_leader_perm or (),
            collective=spec.collective)
        plan = self._plans.get(sig)
        if plan is not None:
            self.hits += 1
            return plan
        self.misses += 1
        warm = store.get(sig) if store is not None else None
        try:
            plan = ExchangePlan(spec, mesh, window_cache=self.window_cache,
                                warm=warm)
        except WarmStartError:
            # Stale-but-colliding artifact: cold INIT, never wrong tables.
            INIT_STATS.bump("store_invalid")
            plan = ExchangePlan(spec, mesh, window_cache=self.window_cache)
        if store is not None and not plan.warm_loaded:
            try:
                store.put_plan(sig, plan)
            except OSError:
                pass                      # full/read-only disk: store stays best-effort
        self._plans[sig] = plan
        return plan

    @property
    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses, "live": len(self._plans),
                "auto_choices": len(self.auto_choices),
                "window": self.window_cache.stats}


# Deprecated shims: the founding collective's names.  Every existing caller
# (and isinstance check) keeps working — an ExchangeSpec defaults to
# collective="alltoallv", so AlltoallvSpec(...) means exactly what it always
# did and its signatures/artifacts are byte-identical to the pre-patterns
# era.
AlltoallvSpec = ExchangeSpec
AlltoallvPlan = ExchangePlan

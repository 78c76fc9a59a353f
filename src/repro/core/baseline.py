"""Non-persistent alltoallv baseline (the ``MPI_Alltoallv`` stand-in).

A non-persistent collective takes counts/displacements as *runtime arguments*
and must therefore redo, on every invocation, all the work a persistent plan
performs once at INIT:

  * the count matrix exchange (one extra latency-bound int32 all_to_all),
  * displacement computation and pack/unpack index-map construction in-graph,
  * conservative capacity: the executable is generic over patterns, so every
    bucket is padded to the declared worst case (a persistent lock plan, by
    contrast, shrinks every round to its measured diagonal),
  * a fresh output buffer each call (no window reuse / donation).

One compiled executable serves *all* patterns of a given geometry — that is
the point: generic-and-slow vs specialized-and-fast, the trade the paper's
break-even model prices.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from jax import shard_map
from . import variants


def nonpersistent_shard_fn(
    x: jax.Array,
    send_counts_row: jax.Array,
    *,
    axis: str,
    p: int,
    capacity: int,
    recv_rows: int,
    variant: str = "fence",
    lock_schedule: str = "ring",
) -> jax.Array:
    """Per-shard non-persistent alltoallv; counts are traced runtime values."""
    # -- per-call metadata processing (what persistence eliminates) --
    rc_row = variants.exchange_counts_in_graph(send_counts_row, axis)
    sd_row = variants.displacements_in_graph(send_counts_row)
    rd_row = variants.displacements_in_graph(rc_row)
    src, valid = variants.pack_index_map_in_graph(send_counts_row, sd_row, p, capacity)
    packed = variants.pack_rows(x, src, valid)

    # -- data movement --
    if variant == "fence":
        buckets = variants.fence_exchange(packed, axis)
    elif variant == "lock":
        # No pattern knowledge -> every round padded to the global capacity.
        buckets = variants.lock_exchange(
            packed, axis, p, capacity, None, lock_schedule)
    else:
        raise ValueError(f"non-persistent baseline supports fence|lock, got {variant}")

    rsrc, rvalid = variants.unpack_index_map_in_graph(rc_row, rd_row, p, capacity, recv_rows)
    return variants.unpack_rows(buckets, rsrc, rvalid)


def make_nonpersistent(mesh, *, axis: str, p: int, capacity: int, send_rows: int,
                       recv_rows: int, feature_shape, dtype,
                       variant: str = "fence", lock_schedule: str = "ring"):
    """Build + AOT-compile the generic executable (counts as runtime args)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    fn = partial(nonpersistent_shard_fn, axis=axis, p=p, capacity=capacity,
                 recv_rows=recv_rows, variant=variant, lock_schedule=lock_schedule)
    x_spec = P(axis)
    mapped = shard_map(
        fn, mesh=mesh, in_specs=(x_spec, x_spec), out_specs=x_spec, check_vma=False)
    jitted = jax.jit(mapped)
    xs = jax.ShapeDtypeStruct((p * send_rows,) + tuple(feature_shape), dtype,
                              sharding=NamedSharding(mesh, x_spec))
    cs = jax.ShapeDtypeStruct((p * p,), jnp.int32,
                              sharding=NamedSharding(mesh, x_spec))
    return jitted.lower(xs, cs).compile()

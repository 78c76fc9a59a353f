"""Pallas TPU kernel: lock-synchronized (passive-target) one-sided alltoallv.

The TPU rendition of Algorithm 3.  Passive-target RMA has no collective
fence; instead each origin acquires per-target access and its puts complete
target-by-target.  On TPU that maps to *serialized pairwise epochs*: round r
puts my bucket to rank (me+r) mod P and blocks until that pairwise transfer
fully completes (send drained + the matching incoming block arrived) before
the next round — the lock/unlock pair around each target's epoch.

This is deliberately the structurally weaker schedule: only one put is in
flight per rank at a time, so a single hot pair gates the whole epoch.  The
paper measures exactly this (lock persistent trails fence at every scale and
degrades most under skewed patterns); on TPU the same serialization shows up
as (P-1) dependent DMA chains instead of the fence kernel's one bulk epoch.

Window exposure: an MPI lock epoch targets a window that already exists on
every rank.  On TPU the window is the kernel's output buffer, which exists
only once its owner has entered the kernel — before that, XLA may still use
those bytes.  So the kernel opens with one handshake on the collective
barrier semaphore (every rank signals every peer it will put to), the TPU
form of the collectively created window.  After it, synchronization is
entirely via the per-transfer DMA semaphores, the passive-target property.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .a2a_fence import barrier_all, collective_params, device_id
from .gather_rows import from_words, to_words


def _lock_kernel(x_ref, out_ref, local_sem, send_sem, recv_sem,
                 *, p, capacity, axis, mesh_axes):
    me = jax.lax.axis_index(axis)

    # Window exposure: every peer's output buffer is live before any put.
    if p > 1:
        barrier_all(p, me, mesh_axes, axis)

    # Local bucket (self "lock" is free).
    local = pltpu.make_async_copy(
        x_ref.at[pl.ds(me * capacity, capacity)],
        out_ref.at[pl.ds(me * capacity, capacity)],
        local_sem)
    local.start()

    # Serialized per-target epochs: lock -> put -> unlock, one peer at a time.
    def round_(r, _):
        tgt = jax.lax.rem(me + r, p)
        rdma = pltpu.make_async_remote_copy(
            src_ref=x_ref.at[pl.ds(tgt * capacity, capacity)],
            dst_ref=out_ref.at[pl.ds(me * capacity, capacity)],
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=device_id(mesh_axes, axis, tgt),
            device_id_type=pltpu.DeviceIdType.MESH)
        rdma.start()
        rdma.wait()   # pairwise completion before the next target (the lock)
        return _

    if p > 1:
        jax.lax.fori_loop(1, p, round_, 0)
    local.wait()


def rma_alltoallv_lock(
    packed: jax.Array,      # per-shard [P*C, F] bucketed send buffer
    *,
    p: int,
    capacity: int,
    axis: str,
    mesh_axes: tuple[str, ...],
    interpret: bool | object = False,
) -> jax.Array:
    """Call inside shard_map over ``mesh_axes``; exchanges over ``axis``."""
    words = to_words(packed)
    out = pl.pallas_call(
        functools.partial(_lock_kernel, p=p, capacity=capacity, axis=axis,
                          mesh_axes=mesh_axes),
        out_shape=jax.ShapeDtypeStruct(words.shape, jnp.uint32),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.DMA],
        compiler_params=collective_params(p, 8),
        interpret=interpret,
    )(words)
    return from_words(out.reshape(words.shape[0], -1), packed.dtype)

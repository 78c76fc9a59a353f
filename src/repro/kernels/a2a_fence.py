"""Pallas TPU kernel: fence-synchronized one-sided alltoallv.

This is the mechanism-level reproduction of Algorithm 1 on TPU hardware:
``MPI_Put`` becomes an inter-chip remote DMA (``pltpu.make_async_remote_copy``)
and the ``MPI_Win_fence`` pair becomes

  * epoch OPEN — a barrier with every peer on the collective barrier
    semaphore (each rank signals all others and waits for P-1 signals).
    This is what guarantees the exposed window (the output buffer, reused
    across epochs by the persistent plan) is no longer being read by its
    owner before new puts land — exactly the hazard ``MPI_Win_fence``
    exists to order.  The barrier semaphore (``pltpu.get_barrier_semaphore``,
    keyed by the kernel's ``collective_id``) is the one semaphore a peer may
    signal before this rank has entered the kernel; scratch semaphores only
    exist while their owner runs it.
  * bulk puts — all P-1 remote DMAs are posted back-to-back and proceed
    concurrently over the ICI links (this is the fence variant's advantage:
    one epoch, maximal overlap).
  * epoch CLOSE — wait until my sends drained and my P-1 expected blocks
    arrived (send/recv DMA semaphores), the ``NOPUT | NOSUCCEED`` closing
    fence.

Layout: the capacity-bucketed send buffer ``x[P*C, F]`` (bucket j = my data
for rank j); output ``out[P*C, F]`` (bucket j = rank j's data for me). Remote
bucket addressing is the put-displacement rule: my block lands at offset
``me * C`` inside every target's window.  Both travel as ``[rows, 1, W]``
32-bit words (``gather_rows.to_words``), so every bucket and row slice is on
the untiled leading dim and any capacity is aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gather_rows import from_words, gather_into, masked_index, to_words


def device_id(mesh_axes, axis, target):
    return tuple(target if a == axis else jax.lax.axis_index(a)
                 for a in mesh_axes)


def barrier_all(p, me, mesh_axes, axis):
    """Signal every peer on the barrier semaphore, then wait for all P-1."""
    barrier = pltpu.get_barrier_semaphore()

    def signal(r, _):
        pltpu.semaphore_signal(
            barrier, 1, device_id=device_id(mesh_axes, axis,
                                            jax.lax.rem(me + r, p)),
            device_id_type=pltpu.DeviceIdType.MESH)
        return _

    jax.lax.fori_loop(1, p, signal, 0)
    pltpu.semaphore_wait(barrier, p - 1)


def collective_params(p: int, collective_id: int, **kwargs):
    """A barrier semaphore exists only for a kernel that talks to peers."""
    return pltpu.CompilerParams(
        collective_id=collective_id if p > 1 else None, **kwargs)


def _fence_kernel(x_ref, out_ref, local_sem, send_sem, recv_sem,
                  *, p, capacity, axis, mesh_axes):
    me = jax.lax.axis_index(axis)

    # ---- epoch OPEN: fence barrier with all peers ----
    if p > 1:
        barrier_all(p, me, mesh_axes, axis)

    # ---- local bucket: never leaves the chip ----
    local = pltpu.make_async_copy(
        x_ref.at[pl.ds(me * capacity, capacity)],
        out_ref.at[pl.ds(me * capacity, capacity)],
        local_sem)
    local.start()

    def put(r):
        tgt = jax.lax.rem(me + r, p)
        return pltpu.make_async_remote_copy(
            src_ref=x_ref.at[pl.ds(tgt * capacity, capacity)],
            dst_ref=out_ref.at[pl.ds(me * capacity, capacity)],
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=device_id(mesh_axes, axis, tgt),
            device_id_type=pltpu.DeviceIdType.MESH)

    # ---- bulk puts: post everything, let the links overlap ----
    if p > 1:
        jax.lax.fori_loop(1, p, lambda r, c: (put(r).start(), c)[1], 0)

    # ---- epoch CLOSE: all sends drained, all expected blocks arrived ----
    local.wait()
    if p > 1:
        jax.lax.fori_loop(1, p, lambda r, c: (put(r).wait(), c)[1], 0)


def rma_alltoallv_fence(
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
        functools.partial(_fence_kernel, p=p, capacity=capacity, axis=axis,
                          mesh_axes=mesh_axes),
        out_shape=jax.ShapeDtypeStruct(words.shape, jnp.uint32),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.DMA],
        compiler_params=collective_params(p, 7),
        interpret=interpret,
    )(words)
    return from_words(out.reshape(words.shape[0], -1), packed.dtype)


# ---------------------------------------------------------------------------
# Fused pack-put: gather rows straight into the remote-DMA source tile
# ---------------------------------------------------------------------------


def _fused_fence_kernel(idx_ref, x_ref, out_ref, scratch, row_sems,
                        local_sem, send_sem, recv_sem,
                        *, p, capacity, axis, mesh_axes):
    """Fence epoch with the pack gather fused into the put pipeline.

    The unfused path writes the full padded ``[P*C, F]`` bucketed buffer to
    HBM (pack) and then reads it back for the puts — one full round trip of
    padded traffic per epoch.  Here each target's ``capacity`` rows are
    gathered from the *ragged* send buffer directly into a VMEM staging tile
    (addresses from the host-baked index map, scalar-prefetched; padding
    rows are zeroed in VMEM) and put remotely from VMEM.  Two
    staging tiles alternate so the gather for target r+1 overlaps the put
    for target r.

    ``send_sem`` is per-slot: all puts move equal byte counts, so a shared
    send semaphore could be satisfied by the *other* slot's put completing
    and let a staging tile be overwritten while its own put still reads it.
    """
    me = jax.lax.axis_index(axis)

    # ---- epoch OPEN: fence barrier with all peers ----
    if p > 1:
        barrier_all(p, me, mesh_axes, axis)

    def remote_put(r):
        """Descriptor for round r's put (also recreated for the waits)."""
        slot = r % 2
        tgt = jax.lax.rem(me + r, p)
        return pltpu.make_async_remote_copy(
            src_ref=scratch.at[slot],
            dst_ref=out_ref.at[pl.ds(me * capacity, capacity)],
            send_sem=send_sem.at[slot], recv_sem=recv_sem,
            device_id=device_id(mesh_axes, axis, tgt),
            device_id_type=pltpu.DeviceIdType.MESH)

    # ---- local bucket: gather into slot 0, copy down without leaving chip --
    gather_into(idx_ref, me * capacity, capacity, x_ref, scratch.at[0],
                row_sems)
    local = pltpu.make_async_copy(
        scratch.at[0], out_ref.at[pl.ds(me * capacity, capacity)], local_sem)
    local.start()

    # ---- pipelined gather+put rounds (slots alternate 1, 0, 1, ...) ----
    for r in range(1, p):
        slot = r % 2
        if r == 2:
            local.wait()               # slot 0 about to be reused
        if r >= 3:
            remote_put(r - 2).wait_send()   # same slot: drain before reuse
        gather_into(idx_ref, jax.lax.rem(me + r, p) * capacity, capacity,
                    x_ref, scratch.at[slot], row_sems)
        remote_put(r).start()

    # ---- epoch CLOSE: sends drained, P-1 expected blocks arrived ----
    if p <= 2:
        local.wait()
    for r in range(max(1, p - 2), p):
        remote_put(r).wait_send()
    for r in range(1, p):
        remote_put(r).wait_recv()


def rma_alltoallv_fence_fused(
    x: jax.Array,           # per-shard [S, F] *ragged* send buffer
    src_idx: jax.Array,     # [P*C] host-baked pack gather map
    valid: jax.Array,       # [P*C] pack padding mask
    *,
    p: int,
    capacity: int,
    axis: str,
    mesh_axes: tuple[str, ...],
    interpret: bool | object = False,
) -> jax.Array:
    """Fused pack + fence-epoch puts; returns the bucketed recv layout."""
    n = p * capacity
    words = to_words(x)
    w = words.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],     # x stays in HBM
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, capacity, 1, w), jnp.uint32),  # staging tiles
            pltpu.SemaphoreType.DMA((capacity,)),         # per-row gathers
            pltpu.SemaphoreType.DMA,                      # local bucket
            pltpu.SemaphoreType.DMA((2,)),                # send, per slot
            pltpu.SemaphoreType.DMA,                      # recv
        ],
    )
    out = pl.pallas_call(
        functools.partial(_fused_fence_kernel, p=p, capacity=capacity,
                          axis=axis, mesh_axes=mesh_axes),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, w), jnp.uint32),
        compiler_params=collective_params(p, 9),
        interpret=interpret,
    )(masked_index(src_idx, valid), words)
    return from_words(out.reshape(n, w), x.dtype)

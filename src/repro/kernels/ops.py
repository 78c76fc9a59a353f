"""Jitted public wrappers around the Pallas kernels.

Handles TPU tiling constraints (lane padding of the 32-bit word view,
8-row-multiple row counts), feature-shape flattening, and backend selection:
on a TPU the kernels always compile natively.  On CPU — the unit-test path
only — the local gather kernels run under the HLO interpreter, the remote-DMA
kernels under the TPU-semantics interpreter (``pltpu.InterpretParams``, which
executes inter-chip DMAs and semaphores across shard_map's host devices), and
``fused_unpack_matmul`` takes its jnp form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

import numpy as np

from . import gather_rows as _gather
from . import gather_matmul as _gmm
from . import a2a_fence as _fence
from . import a2a_hier as _hier
from . import a2a_lock as _lock

LANE = 128
ROW_TILE = 8          # Mosaic's row tile for 32-bit words


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _interpret_rma():
    """Remote DMAs/semaphores need the TPU interpreter, not the HLO one."""
    return pltpu.InterpretParams() if _on_cpu() else False


def _row_tile(n: int) -> tuple[int, int]:
    """``(tile_rows, n_pad)``: ``n`` padded to the 8-row tile, and the
    largest tile of at most 64 rows that divides it."""
    n_pad = -(-n // ROW_TILE) * ROW_TILE
    return next(t for t in (64, 32, 16, 8) if n_pad % t == 0), n_pad


def _pad_rows(a: jax.Array, n_pad: int) -> jax.Array:
    """Pad the last axis of an index/mask array with zeros to ``n_pad``."""
    extra = n_pad - a.shape[-1]
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, extra)]) if extra else a


def _flatten_features(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    feat = x.shape[1:]
    return x.reshape(x.shape[0], -1) if len(feat) != 1 else x, feat


def _pad_lanes(x2d: jax.Array) -> tuple[jax.Array, int]:
    """Pad features so the kernels' 32-bit word view fills whole lanes."""
    f = x2d.shape[1]
    pad = (-f) % (LANE * _gather.words_per_lane(x2d.dtype))
    if pad:
        x2d = jnp.pad(x2d, ((0, 0), (0, pad)))
    return x2d, f


def _masked_gather(x: jax.Array, idx: jax.Array, valid: jax.Array,
                   interpret=None) -> jax.Array:
    interpret = _on_cpu() if interpret is None else interpret
    n = idx.shape[0]
    tile, n_pad = _row_tile(n)
    x2d, feat = _flatten_features(x)
    x2d, f0 = _pad_lanes(x2d)
    out = _gather.gather_rows(
        x2d, _pad_rows(idx.astype(jnp.int32), n_pad), _pad_rows(valid, n_pad),
        tile_rows=tile, interpret=interpret)
    out = out[:n, :f0]
    return out.reshape((n,) + feat)


def pack(x: jax.Array, src_idx: jax.Array, valid: jax.Array,
         interpret=None) -> jax.Array:
    """Ragged send buffer -> capacity-bucketed layout (Pallas gather)."""
    return _masked_gather(x, src_idx, valid, interpret)


def unpack(buckets: jax.Array, src_idx: jax.Array, valid: jax.Array,
           interpret=None) -> jax.Array:
    """Bucketed recv layout -> contiguous ragged recv buffer (Pallas gather)."""
    return _masked_gather(buckets, src_idx, valid, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kernel_unpack_matmul(interp_key, x2d, idx, valid, w):
    return _gmm.gather_matmul(
        x2d, idx, valid, w, tile_rows=_row_tile(idx.shape[1])[0],
        interpret=(interp_key == "interpret"))


def _kernel_unpack_matmul_fwd(interp_key, x2d, idx, valid, w):
    return (_kernel_unpack_matmul(interp_key, x2d, idx, valid, w),
            (x2d, idx, valid, w))


def _kernel_unpack_matmul_bwd(interp_key, res, g):
    # jnp transpose of the fused forward: the backward pass is training-only
    # and off the serve hot path, so it takes the reference scatter-add form.
    x2d, idx, valid, w = res
    e, n = idx.shape
    vm = valid.reshape(e, n, 1).astype(x2d.dtype)
    gc = g.astype(x2d.dtype)
    h = jnp.take(x2d, idx.reshape(-1), axis=0).reshape(e, n, -1) * vm
    dw = jnp.einsum("end,enf->edf", h, gc).astype(w.dtype)
    dh = jnp.einsum("enf,edf->end", gc, w.astype(x2d.dtype)) * vm
    dx = jnp.zeros_like(x2d).at[idx.reshape(-1)].add(dh.reshape(e * n, -1))
    f0 = np.zeros((), jax.dtypes.float0)
    return (dx, np.broadcast_to(f0, idx.shape),
            np.broadcast_to(f0, valid.shape), dw)


_kernel_unpack_matmul.defvjp(_kernel_unpack_matmul_fwd,
                             _kernel_unpack_matmul_bwd)


def unpack_matmul_ref(x2d: jax.Array, idx: jax.Array, w: jax.Array,
                      valid: jax.Array, scales: jax.Array | None = None
                      ) -> jax.Array:
    """jnp form of :func:`fused_unpack_matmul` (its CPU path): gather,
    optional per-row dequant, mask, then one einsum per expert."""
    e, n = idx.shape
    h = jnp.take(x2d, idx.reshape(-1), axis=0).reshape(e, n, -1)
    h = h.astype(w.dtype)
    if scales is not None:
        h = h * jnp.take(scales, idx.reshape(-1), axis=0
                         ).reshape(e, n, 1).astype(w.dtype)
    h = h * valid.reshape(e, n, 1).astype(h.dtype)
    return jnp.einsum("end,edf->enf", h, w)


def fused_unpack_matmul(x: jax.Array, idx: jax.Array, w: jax.Array,
                        valid: jax.Array | None = None,
                        scales: jax.Array | None = None,
                        interpret=None) -> jax.Array:
    """Fused unpack-gather-matmul: ``out[e] = (x[idx[e]] * valid[e]) @ w[e]``.

    Receive-side mirror of the fused pack-put: the expert FFN's first
    matmul reads rows straight out of the receive buffer via the INIT-baked
    unpack table.  On TPU the Pallas kernel (``kernels/gather_matmul.py``)
    DMAs each row tile into VMEM and feeds the MXU — the regrouped
    ``[recv_rows, D]`` intermediate never lands in HBM.  On CPU (the test
    path) the semantically identical jnp gather + einsum runs instead (the per-row
    interpreted DMAs would be orders slower than the reference einsum, and
    the jnp form is natively differentiable); the kernel path carries a
    custom VJP whose backward is the jnp scatter-add transpose.

    ``scales`` ([rows, 1], a wire codec's per-row dequant factors) folds
    the decode into the gather: ``x`` may be narrow wire rows (int8/fp8)
    and each gathered row is scaled as it is read — the decoded
    ``[recv_rows, D]`` fp32 buffer never materializes on the CPU path.  The kernel path pre-scales ``x`` instead (in-kernel dequant is
    future work), which still skips one full-buffer round trip vs
    decode-then-gather.
    """
    idx = jnp.asarray(idx, jnp.int32)
    e, n = idx.shape
    if valid is None:
        valid = jnp.ones((e, n), jnp.int32)
    x2d, _ = _flatten_features(x)
    if interpret is None:
        if _on_cpu():
            return unpack_matmul_ref(x2d, idx, w, valid, scales)
        interpret = False
    if scales is not None or x2d.dtype != w.dtype:
        x2d = x2d.astype(w.dtype)
        if scales is not None:
            x2d = x2d * scales.astype(w.dtype)
    x2d, d0 = _pad_lanes(x2d)
    f0 = w.shape[2]
    wp = jnp.pad(w.astype(x2d.dtype),
                 ((0, 0), (0, x2d.shape[1] - d0), (0, (-f0) % LANE)))
    n_pad = _row_tile(n)[1]
    out = _kernel_unpack_matmul(
        "interpret" if interpret else "compile", x2d, _pad_rows(idx, n_pad),
        _pad_rows(valid.astype(jnp.int32), n_pad), wp)
    return out[:, :n, :f0]


def fused_pack_alltoallv(x: jax.Array, src_idx: jax.Array, valid: jax.Array,
                         *, p: int, capacity: int, axis: str,
                         mesh_axes: tuple[str, ...],
                         interpret=None) -> jax.Array:
    """Fused pack-put fence epoch (call inside shard_map).

    Gathers send rows straight into the remote-DMA source tile using the
    host-baked index map — the padded ``[P*C, F]`` bucketed intermediate is
    never written to HBM, removing one full buffer write+read of padded
    traffic per epoch versus ``pack`` followed by ``rma_alltoallv``.

    """
    interpret = _interpret_rma() if interpret is None else interpret
    x2d, feat = _flatten_features(x)
    x2d, f0 = _pad_lanes(x2d)
    out = _fence.rma_alltoallv_fence_fused(
        x2d, src_idx, valid, p=p, capacity=capacity, axis=axis,
        mesh_axes=mesh_axes, interpret=interpret)
    out = out[:, :f0]
    return out.reshape((p * capacity,) + feat)


def fused_hier_leader_exchange(s1_recv: jax.Array, s2_src: jax.Array,
                               s2_valid: jax.Array, *, schedule,
                               outer_axis: str, inner_axis: str,
                               mesh_axes: tuple[str, ...],
                               interpret=None) -> jax.Array:
    """Fused stage-2 leader epoch of the combined hierarchy (in shard_map).

    Gathers each inter-group slab's rows from the stage-1 recv buffer
    straight into the remote-DMA staging tile (host-baked index map,
    scalar-prefetched) and puts it to the partner leader — the packed slab
    buffer never lands in HBM, and the gather of macro-round m overlaps the
    put of round m-1.
    """
    interpret = _interpret_rma() if interpret is None else interpret
    x2d, feat = _flatten_features(s1_recv)
    x2d, f0 = _pad_lanes(x2d)
    out = _hier.rma_hier_leader_exchange(
        x2d, s2_src, s2_valid,
        p_outer=schedule.p_outer, p_inner=schedule.p_inner,
        round_caps=schedule.s2_caps, round_offs=schedule.s2_offs,
        total_s2=schedule.total_s2,
        outer_axis=outer_axis, inner_axis=inner_axis,
        mesh_axes=mesh_axes, interpret=interpret)
    out = out[:, :f0]
    return out.reshape((schedule.total_s2,) + feat)


def rma_alltoallv(packed: jax.Array, *, variant: str, p: int, capacity: int,
                  axis: str, mesh_axes: tuple[str, ...],
                  interpret=None) -> jax.Array:
    """One-sided bucketed alltoallv (call inside shard_map).

    variant="fence": barrier-bracketed epoch, all puts overlapped.
    variant="lock":  passive-target, serialized pairwise epochs.
    """
    interpret = _interpret_rma() if interpret is None else interpret
    x2d, feat = _flatten_features(packed)
    x2d, f0 = _pad_lanes(x2d)
    kern = {"fence": _fence.rma_alltoallv_fence,
            "lock": _lock.rma_alltoallv_lock}[variant]
    out = kern(x2d, p=p, capacity=capacity, axis=axis, mesh_axes=mesh_axes,
               interpret=interpret)
    out = out[:, :f0]
    return out.reshape((packed.shape[0],) + feat)

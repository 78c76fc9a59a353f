"""Pallas TPU kernel: masked row gather (the pack/unpack hot spot).

Once metadata is amortized by persistence, per-epoch runtime is dominated by
data movement (paper §5).  On TPU the local half of that movement is the
ragged→bucketed pack and bucketed→ragged unpack: a gather of rows from HBM by
a per-row index map.  This kernel streams the gather through VMEM:

  grid step g handles TILE_R output rows; for each row it posts an async
  HBM→VMEM copy of source row ``idx[g*TILE_R + r]`` into a VMEM scratch
  tile, overlapping the TILE_R row DMAs, then masks padding rows and writes
  the tile out.

Row addressing: Mosaic tiles the last two dims of a memref, so a one-row
slice of a 2-D ``[S, F]`` ref is refused (it is not aligned to the 8-row
tile).  The source is therefore viewed as ``[S, 1, W]`` 32-bit words and
each DMA indexes the untiled leading dim.  Every dtype travels as its raw
bytes, so the gather is byte-exact and the 16-bit sublane packing never
meets a one-row slice.

Word format (this module owns it; every kernel in the package uses it): a
row of ``F`` lanes with ``k`` lanes per word is cut into ``k`` contiguous
slabs of ``W = F / k`` lanes, and word ``w`` holds lane ``w + j*W`` in its
byte slot ``j`` (``to_words`` / ``from_words``).  Slabs rather than adjacent
lanes so that ``gather_matmul`` can unpack a bf16 tile with one shift and
one mask per half and multiply each half against a contiguous half of the
expert's weight rows; adjacent-lane words would need a lane interleave, or
a stride-2 split of the weights, to reach the MXU.  Converting costs one
elementwise pass over the source per call, which is also what relayouting
a tiled ``[S, F]`` array into the ``[S, 1, W]`` DMA view would cost.

BlockSpec geometry: the word width is padded to the 128-lane quantum by
``ops.py``; tiles are (TILE_R, W) so the VMEM working set is the scratch
tile plus the double-buffered output block.

The index map arrives via scalar prefetch (SMEM) so the DMA addresses are
known ahead of the tile's execution.  Padding rows carry the index -1
(``masked_index``): the kernel posts no DMA for them and zeroes their
scratch row instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TILE_ROWS = 64


def words_per_lane(dtype) -> int:
    """Lanes of ``dtype`` packed into one 32-bit word."""
    size = jnp.dtype(dtype).itemsize
    if size not in (1, 2, 4):
        raise ValueError(f"no 32-bit word view for {jnp.dtype(dtype)}")
    return 4 // size


def _uint(bits: int):
    return {8: jnp.uint8, 16: jnp.uint16, 32: jnp.uint32}[bits]


def to_words(x: jax.Array) -> jax.Array:
    """``[S, F]`` of any 1/2/4-byte dtype -> ``[S, 1, F // k]`` uint32, the
    DMA view: lane ``w + j*W`` in byte slot ``j`` of word ``w``."""
    k = words_per_lane(x.dtype)
    s, f = x.shape
    if f % k:
        raise ValueError(f"F={f} is not a multiple of {k} lanes per word")
    bits = 32 // k
    raw = jax.lax.bitcast_convert_type(x, _uint(bits))
    if k == 1:
        return raw.reshape(s, 1, f)
    w = f // k
    words = raw[:, :w].astype(jnp.uint32)
    for j in range(1, k):
        words = words | (raw[:, j * w:(j + 1) * w].astype(jnp.uint32)
                         << (j * bits))
    return words.reshape(s, 1, w)


def from_words(words: jax.Array, dtype) -> jax.Array:
    """``[S, W]`` uint32 words -> ``[S, W * k]`` of ``dtype`` (inverse of
    :func:`to_words` after dropping the unit dim)."""
    k = words_per_lane(dtype)
    if k == 1:
        return jax.lax.bitcast_convert_type(words, jnp.dtype(dtype))
    bits = 32 // k
    mask = jnp.uint32((1 << bits) - 1)
    slabs = [((words >> (j * bits)) & mask).astype(_uint(bits))
             for j in range(k)]
    return jax.lax.bitcast_convert_type(jnp.concatenate(slabs, axis=1),
                                        jnp.dtype(dtype))


def masked_index(idx: jax.Array, valid: jax.Array) -> jax.Array:
    """The gather map with padding rows set to -1 (no DMA, zero row)."""
    return jnp.where(valid.astype(bool), idx.astype(jnp.int32), -1)


def gather_into(idx_ref, base, n, src_ref, dst_ref, sems):
    """Rows ``idx[base : base + n]`` of the ``[S, 1, W]`` ref ``src_ref`` ->
    ``dst_ref[0:n]``, all n one-row DMAs in flight together; a row whose
    index is negative is zeroed instead."""
    def row(k, s):
        return pltpu.make_async_copy(src_ref.at[s], dst_ref.at[k], sems.at[k])

    def start_row(k, carry):
        s = idx_ref[base + k]

        @pl.when(s >= 0)
        def _():
            row(k, s).start()

        @pl.when(s < 0)
        def _():
            dst_ref[k] = jnp.zeros(dst_ref.shape[1:], dst_ref.dtype)
        return carry

    def wait_row(k, carry):
        s = idx_ref[base + k]

        @pl.when(s >= 0)
        def _():
            row(k, s).wait()
        return carry

    jax.lax.fori_loop(0, n, start_row, 0)
    jax.lax.fori_loop(0, n, wait_row, 0)


def _gather_kernel(idx_ref, x_ref, out_ref, scratch, sems, *, tile_rows):
    gather_into(idx_ref, pl.program_id(0) * tile_rows, tile_rows, x_ref,
                scratch, sems)
    out_ref[...] = scratch[...].reshape(out_ref.shape)


def gather_rows(
    x: jax.Array,          # [S, F_pad] source rows (HBM-resident)
    idx: jax.Array,        # [N] int32 source row per output row
    valid: jax.Array,      # [N] int32/bool padding mask
    *,
    tile_rows: int = DEFAULT_TILE_ROWS,
    interpret: bool | object = False,
) -> jax.Array:
    n = idx.shape[0]
    if n % tile_rows:
        raise ValueError(f"N={n} must be a multiple of tile_rows={tile_rows}")
    words = to_words(x)
    w = words.shape[2]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // tile_rows,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],            # x stays in HBM
        out_specs=pl.BlockSpec((tile_rows, w), lambda g, idx: (g, 0)),
        scratch_shapes=[
            pltpu.VMEM((tile_rows, 1, w), jnp.uint32),
            pltpu.SemaphoreType.DMA((tile_rows,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_gather_kernel, tile_rows=tile_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, w), jnp.uint32),
        interpret=interpret,
    )(masked_index(idx, valid), words)
    return from_words(out, x.dtype)

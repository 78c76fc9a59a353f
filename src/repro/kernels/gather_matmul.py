"""Pallas TPU kernel: fused unpack-gather-matmul (receive-side mirror of the
fused pack-put).

After a persistent exchange the received rows sit in the window's bucketed
layout; the MoE expert FFN's first matmul wants them regrouped per local
expert.  The reference path materializes that regroup as a full
``[recv_rows, D]`` intermediate in HBM and only then multiplies.  This
kernel deletes the intermediate: grid step (e, g) DMAs the TILE_R source
rows expert ``e`` needs — addressed by the INIT-baked unpack table, scalar-
prefetched so the DMA addresses precede the tile — straight into a VMEM
scratch tile (padding rows are zeroed in VMEM, no DMA), and feeds the tile
to the MXU against expert ``e``'s weight block.  The gathered activations
never round-trip through HBM; per grid step the working set is one
(TILE_R, D) scratch tile, one (D, TF) weight block, and one (TILE_R, TF)
output block.

Row addressing follows ``gather_rows``, whose word format this kernel reads:
the source is viewed as ``[R, 1, W]`` 32-bit words so each row DMA indexes
the untiled leading dim.  A bf16 row's word k holds lanes k and k + D/2, so
the kernel unpacks the two halves with a shift and a mask and multiplies
each against the matching half of the expert's weight rows; f32 rows are
their own words.

BlockSpec geometry: D and F are padded to the 128-lane quantum (D to 256
for bf16, so each half is lane-aligned) by ``ops.py``; x stays in HBM
(``pl.ANY``) and is row-addressed by the prefetched index map.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gather_rows import gather_into, masked_index, to_words

DEFAULT_TILE_ROWS = 64
# Largest weight block held in VMEM (it is double-buffered): wider expert
# weights are walked in column blocks, reusing the gathered row tile.
MAX_W_BLOCK_BYTES = 4 << 20


def _col_block(d: int, f: int, itemsize: int) -> int:
    tf = f
    while d * tf * itemsize > MAX_W_BLOCK_BYTES and tf % 256 == 0:
        tf //= 2
    return tf


def _gather_matmul_kernel(idx_ref, x_ref, w_ref, out_ref, scratch, sems, *,
                          tile_rows, n_per_e):
    base = pl.program_id(0) * n_per_e + pl.program_id(1) * tile_rows

    @pl.when(pl.program_id(2) == 0)
    def _():
        gather_into(idx_ref, base, tile_rows, x_ref, scratch, sems)

    words = scratch[...].reshape(tile_rows, scratch.shape[2])
    wdt = w_ref.dtype

    def rows(bits):
        return jax.lax.bitcast_convert_type(bits, jnp.float32).astype(wdt)

    if wdt == jnp.float32:
        acc = jnp.dot(rows(words), w_ref[0],
                      preferred_element_type=jnp.float32)
    else:
        # bf16 is the high half of an f32: shift each lane half into place.
        h = words.shape[1]
        acc = (jnp.dot(rows(words << 16), w_ref[0, :h, :],
                       preferred_element_type=jnp.float32)
               + jnp.dot(rows(words & jnp.uint32(0xFFFF0000)),
                         w_ref[0, h:, :], preferred_element_type=jnp.float32))
    out_ref[0] = acc.astype(out_ref.dtype)


def gather_matmul(
    x: jax.Array,          # [R, D_pad] source rows (HBM-resident), w.dtype
    idx: jax.Array,        # [E, N] int32 source row per (expert, output row)
    valid: jax.Array,      # [E, N] int32/bool padding mask
    w: jax.Array,          # [E, D_pad, F_pad] per-expert weight blocks
    *,
    tile_rows: int = DEFAULT_TILE_ROWS,
    interpret: bool | object = False,
) -> jax.Array:
    e, n = idx.shape
    if n % tile_rows:
        raise ValueError(f"N={n} must be a multiple of tile_rows={tile_rows}")
    d = x.shape[1]
    f = w.shape[2]
    if w.shape[:2] != (e, d):
        raise ValueError(f"w {w.shape} does not match idx E={e}, x D={d}")
    if x.dtype != w.dtype:
        raise ValueError(f"x {x.dtype} and w {w.dtype} must match")
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        raise ValueError(
            f"gather_matmul takes f32 or bf16 rows, not {x.dtype}")
    words = to_words(x)
    wd = words.shape[2]
    blocks_per_e = n // tile_rows
    tf = _col_block(d, f, w.dtype.itemsize)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(e, blocks_per_e, f // tf),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),                 # x stays in HBM
            pl.BlockSpec((1, d, tf), lambda ei, g, j, idx: (ei, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, tile_rows, tf),
                               lambda ei, g, j, idx: (ei, g, j)),
        scratch_shapes=[
            pltpu.VMEM((tile_rows, 1, wd), jnp.uint32),
            pltpu.SemaphoreType.DMA((tile_rows,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_gather_matmul_kernel, tile_rows=tile_rows,
                          n_per_e=n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, n, f), x.dtype),
        # The column walk reuses the row tile gathered at j == 0.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(masked_index(idx.reshape(e * n), valid.reshape(e * n)), words, w)

"""Pallas TPU kernel: fused partial-block RMQ scans (query phase, level 1).

The RT-core analogue: ``tile`` queries per grid step ("a warp of rays"), with
each query's two candidate blocks DMA'd HBM->VMEM by hand. Scalar prefetch
(SMEM) carries per-query block ids so the kernel can gather *data-dependent*
blocks — the TPU-idiomatic replacement for the BVH descent picking which leaf
a ray visits: instead of a pointer walk, the DMA engine is programmed with
the block id.

Both partial scans (left tail, right head) are fused into one kernel, and the
grid is tiled ``(B // tile,)``: each step gathers its ``tile`` left rows and
``tile`` right rows into ``(tile, bs)`` VMEM scratch so the VPU does two
masked mins for the whole tile instead of per query, amortizing DMA issue and
grid overhead.

For the fully fused path (interior sparse-table candidate + final merge in
the same dispatch) see ``fused_query.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.block_rmq import maxval

from .tiling import (
    block_rows,
    pad_to_tiles,
    partial_candidates,
    resolve_interpret,
    run_copies,
    scalar_col,
    tile_out_specs,
)
from .tuning import DEFAULT_TILE

__all__ = ["rmq_partials", "DEFAULT_TILE"]


def _kernel(tile, bl_ref, br_ref, ls_ref, le_ref, re_ref, x_hbm, val_ref, idx_ref,
            xl_buf, xr_buf, sems):
    q0 = pl.program_id(0) * tile
    run_copies(block_rows(x_hbm, bl_ref, br_ref, q0, tile, xl_buf, xr_buf, sems))

    def col(ref):
        return scalar_col(ref, q0, tile)

    pv, pi = partial_candidates(
        xl_buf[...], xr_buf[...], col(bl_ref), col(br_ref), col(ls_ref),
        col(le_ref), col(re_ref), maxval(xl_buf.dtype),
    )
    val_ref[...] = pv
    idx_ref[...] = pi


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def rmq_partials(
    x_blocks: jax.Array,
    bl: jax.Array,
    br: jax.Array,
    lstart: jax.Array,
    lend: jax.Array,
    rend: jax.Array,
    *,
    tile: int = DEFAULT_TILE,
    interpret: bool | None = None,
):
    """Fused partial-block candidates. Returns (value (B,), global idx (B,))."""
    _, bs = x_blocks.shape
    interpret = resolve_interpret(interpret, tile, bs)
    b = bl.shape[0]
    args = [a.astype(jnp.int32) for a in (bl, br, lstart, lend, rend)]

    # Pad the batch to a whole number of tiles with trivial block-0 queries.
    args, bp = pad_to_tiles(args, b, tile)

    row = pltpu.VMEM((tile, bs), x_blocks.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(bp // tile,),
        # data-dependent rows x_blocks[bl[q]] and x_blocks[br[q]], DMA'd by hand
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tile_out_specs(tile),
        scratch_shapes=[row, row, pltpu.SemaphoreType.DMA((2,))],
    )
    val, idx = pl.pallas_call(
        functools.partial(_kernel, tile),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bp, 1), x_blocks.dtype),
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(*args, x_blocks)
    return val[:b, 0], idx[:b, 0]

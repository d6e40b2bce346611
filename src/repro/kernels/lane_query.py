"""Pallas TPU kernel: fused lane-RMQ query (beyond-paper O(1) engine).

Fuses the per-query work of ``repro.core.lane_rmq.query`` minus the O(1)
sparse-table interior (which stays in XLA). The grid is tiled
``(B // tile,)``: each step answers ``tile`` queries, gathering per query
three 128-lane rows by DMA — the suffix-min row of l's lane-block, the
prefix-min row of r's lane-block, and the raw row for the same-block case.
The same-block masked min and the straddle candidates' one-hot lane picks
run vectorized on the ``(tile, LANE)`` stacks (one VPU op per tile rather
than per query). Scalar prefetch drives the data-dependent row selection
(same pattern as rmq_query.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.block_rmq import maxval
from repro.core.lane_rmq import LANE

from .tiling import (
    lane_pick,
    pad_to_tiles,
    resolve_interpret,
    row_copies,
    run_copies,
    scalar_col,
    tile_out_specs,
)
from .tuning import DEFAULT_TILE

__all__ = ["lane_partials", "DEFAULT_TILE"]


def _kernel(tile, sl_ref, sr_ref, llo_ref, rlo_ref,
            sv_hbm, si_hbm, pv_hbm, pi_hbm, xs_hbm, val_ref, idx_ref,
            sv_buf, si_buf, pv_buf, pi_buf, xs_buf, sems):
    q0 = pl.program_id(0) * tile

    def rows(src, sel_ref, buf, j):
        return row_copies(
            [src.at[pl.ds(sel_ref[q0 + t], 1)] for t in range(tile)], buf, sems.at[j]
        )

    run_copies(
        rows(sv_hbm, sl_ref, sv_buf, 0)  # suffix minima of l's lane-block
        + rows(si_hbm, sl_ref, si_buf, 1)
        + rows(pv_hbm, sr_ref, pv_buf, 2)  # prefix minima of r's lane-block
        + rows(pi_hbm, sr_ref, pi_buf, 3)
        + rows(xs_hbm, sl_ref, xs_buf, 4)  # raw row for the same-block case
    )

    def col(ref):
        return scalar_col(ref, q0, tile)

    big = maxval(xs_buf.dtype)
    big_i = jnp.iinfo(jnp.int32).max
    lanes = jax.lax.broadcasted_iota(jnp.int32, (tile, LANE), 1)
    sl, sr, llo, rlo = col(sl_ref), col(sr_ref), col(llo_ref), col(rlo_ref)
    same = sl == sr

    # Straddling candidates: one lane pick from each min row.
    lv = lane_pick(sv_buf[...], llo, big)
    li = lane_pick(si_buf[...], llo, big_i)
    rv = lane_pick(pv_buf[...], rlo, big)
    ri = lane_pick(pi_buf[...], rlo, big_i)
    take_l = lv <= rv  # suffix candidate has smaller indices on ties
    str_v = jnp.where(take_l, lv, rv)
    str_i = jnp.where(take_l, li, ri)

    # Same-block: masked vector min over the (tile, LANE) stack of raw rows.
    masked = jnp.where((lanes >= llo) & (lanes <= rlo), xs_buf[...], big)
    mv = jnp.min(masked, axis=1, keepdims=True)
    mi = jnp.min(jnp.where(masked == mv, lanes, jnp.int32(LANE)), axis=1, keepdims=True)
    mi = sl * LANE + mi

    val_ref[...] = jnp.where(same, mv, str_v)
    idx_ref[...] = jnp.where(same, mi, str_i)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def lane_partials(
    xs: jax.Array,  # (nsub, LANE)
    suff_val: jax.Array, suff_idx: jax.Array,  # (nsub, LANE)
    pref_val: jax.Array, pref_idx: jax.Array,
    sl: jax.Array, sr: jax.Array, llo: jax.Array, rlo: jax.Array,  # (B,)
    *,
    tile: int = DEFAULT_TILE,
    interpret: bool | None = None,
):
    """Fused non-interior candidates. Returns (value (B,), global idx (B,))."""
    interpret = resolve_interpret(interpret, tile, LANE)
    b = sl.shape[0]
    args = [a.astype(jnp.int32) for a in (sl, sr, llo, rlo)]

    args, bp = pad_to_tiles(args, b, tile)

    vrow = pltpu.VMEM((tile, LANE), xs.dtype)
    irow = pltpu.VMEM((tile, LANE), jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(bp // tile,),
        # all five tables stay in HBM; rows picked by sl / sr are DMA'd by hand
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 5,
        out_specs=tile_out_specs(tile),
        scratch_shapes=[vrow, irow, vrow, irow, vrow, pltpu.SemaphoreType.DMA((5,))],
    )
    val, idx = pl.pallas_call(
        functools.partial(_kernel, tile),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bp, 1), xs.dtype),
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(*args, suff_val, suff_idx, pref_val, pref_idx, xs)
    return val[:b, 0], idx[:b, 0]

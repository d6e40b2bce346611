"""Shared scaffolding for the Pallas kernels.

The query kernels run a 1-D grid ``(B // tile,)``: each step answers
``tile`` queries. The data-dependent rows a query needs (a block of ``x``,
or a 128-lane window of a doubling-table row) stay in HBM
(``memory_space=pl.ANY``) and are gathered by hand — one async DMA per
query into row ``t`` of a ``(tile, w)`` VMEM scratch, all started before any
is waited on. A BlockSpec cannot express that fetch on a TPU: a ``(1, w)``
block breaks the (8, 128) tiling rule. Per-query scalars and single-cell
picks are then vectorised over the tile as ``(tile, 1)`` columns, because
Mosaic cannot read one lane at a dynamic offset.

The batch padding, SMEM scalar columns, lane picks, block-row DMAs and
partial-block scans are shared by every query kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "LANES",
    "SUBLANES",
    "block_rows",
    "lane_pick",
    "pad_to_tiles",
    "partial_candidates",
    "resolve_interpret",
    "row_copies",
    "run_copies",
    "scalar_col",
    "tile_out_specs",
]

# A (tile, 1) output block meets the TPU's (8, 128) tiling rule only when
# tile is a multiple of the 8 sublanes of a vreg.
SUBLANES = 8
LANES = 128


def resolve_interpret(
    interpret: bool | None, tile: int | None = None, row: int | None = None
) -> bool:
    """Whether a Pallas kernel runs in interpret mode.

    ``None`` means: compiled on a TPU, interpreted on the CPU backend (the
    test and development path). Any other backend raises rather than
    quietly interpreting on a device. A compiled launch also needs ``tile``
    (when given) to be a whole number of sublanes, and each DMA'd ``row``
    (when given) to be exactly one 128-lane tile: Mosaic refuses a one-row
    slice of a wider array.
    """
    if interpret is None:
        backend = jax.default_backend()
        if backend == "tpu":
            interpret = False
        elif backend == "cpu":
            interpret = True
        else:
            raise RuntimeError(
                f"the Pallas kernels compile for a TPU or interpret on the CPU; "
                f"backend {backend!r} is neither"
            )
    if not interpret and tile is not None and tile % SUBLANES:
        raise ValueError(f"a compiled launch needs tile % {SUBLANES} == 0, got tile={tile}")
    if not interpret and row is not None and row != LANES:
        raise ValueError(f"a compiled launch DMAs {LANES}-lane rows, got rows of {row}")
    return bool(interpret)


def pad_to_tiles(args, b: int, tile: int):
    """Zero-pad each (B,) int array to a whole number of tiles.

    The pad queries resolve to block/row 0 with trivial bounds — valid by
    construction; callers slice outputs back to ``b``. Returns (args, bp).
    """
    bp = -(-b // tile) * tile
    if bp != b:
        args = [jnp.pad(a, (0, bp - b)) for a in args]
    return args, bp


def scalar_col(ref, q0, tile: int):
    """A tile's per-query scalars from an SMEM ref as a ``(tile, 1)`` column."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    out = jnp.zeros((tile, 1), ref.dtype)
    for t in range(tile):
        out = jnp.where(rows == t, ref[q0 + t], out)
    return out


def lane_pick(rows, pos, fill):
    """``rows[t, pos[t]]`` per tile row, as a ``(tile, 1)`` column.

    A one-hot masked min over the lanes: exactly one lane matches, so the
    min is that cell's value, whatever ``fill`` is. ``rows`` is ``(tile, w)``
    or a ``(1, w)`` plane shared by every row; ``pos`` is ``(tile, 1)``.
    """
    shape = (pos.shape[0], rows.shape[1])
    lanes = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    picked = jnp.where(lanes == pos, jnp.broadcast_to(rows, shape), fill)
    return jnp.min(picked, axis=1, keepdims=True)


def row_copies(srcs, dst, sem):
    """One DMA per tile slot: HBM view ``srcs[t]`` (shape ``(1, w)``) into
    row ``t`` of the ``(tile, w)`` VMEM scratch ``dst``."""
    return [
        pltpu.make_async_copy(src, dst.at[pl.ds(t, 1)], sem)
        for t, src in enumerate(srcs)
    ]


def run_copies(copies) -> None:
    """Start every copy, then wait for all: the row fetches overlap."""
    for c in copies:
        c.start()
    for c in copies:
        c.wait()


def block_rows(x_hbm, bl_ref, br_ref, q0, tile, xl_buf, xr_buf, sems):
    """The DMAs staging each query's left and right partial-block rows."""
    return row_copies(
        [x_hbm.at[pl.ds(bl_ref[q0 + t], 1)] for t in range(tile)], xl_buf, sems.at[0]
    ) + row_copies(
        [x_hbm.at[pl.ds(br_ref[q0 + t], 1)] for t in range(tile)], xr_buf, sems.at[1]
    )


def partial_candidates(xl, xr, bl, br, ls, le, re, big):
    """Left/right partial-block candidates of a tile, leftmost ties.

    ``xl``/``xr`` are the ``(tile, bs)`` staged rows; the scalars are
    ``(tile, 1)`` columns. Returns ``(pv, pi)`` columns.
    """
    bs = xl.shape[1]
    big_i = jnp.int32(bs)
    lanes = jax.lax.broadcasted_iota(jnp.int32, xl.shape, 1)

    ml = jnp.where((lanes >= ls) & (lanes <= le), xl, big)
    lv = jnp.min(ml, axis=1, keepdims=True)
    li = jnp.min(jnp.where(ml == lv, lanes, big_i), axis=1, keepdims=True)
    lg = bl * bs + li

    # Right partials (masked off for single-block queries).
    mr = jnp.where(lanes <= re, xr, big)
    rv = jnp.min(mr, axis=1, keepdims=True)
    rv = jnp.where(br > bl, rv, big)
    ri = jnp.min(jnp.where(mr == rv, lanes, big_i), axis=1, keepdims=True)
    rg = br * bs + ri

    take_l = lv <= rv  # left candidate has smaller indices: leftmost ties
    return jnp.where(take_l, lv, rv), jnp.where(take_l, lg, rg)


def tile_out_specs(tile: int, n: int = 2):
    """The ``(tile, 1)`` output blocks (value, index, ...) of a query kernel."""
    return [pl.BlockSpec((tile, 1), lambda i, *s: (i, 0)) for _ in range(n)]

"""Pallas TPU kernel: per-block min + leftmost argmin (build phase, level 1).

This is the preprocessing analogue of RTXRMQ's geometry build: one VMEM tile
of blocks per grid step, a vector min along lanes, and a min-over-iota trick
for the *leftmost* argmin using only min-reductions (MXU/VPU friendly — no
data-dependent control flow, matching TPU's systolic/vector execution model).

Tiling: the (tile_rows, block_size) input block lives in VMEM; block_size is
a multiple of 128 (lane width) by construction (enforced in core.block_rmq),
and tile_rows trades VMEM footprint vs. grid overhead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.block_rmq import maxval

from .tiling import resolve_interpret

__all__ = ["block_min"]


def _kernel(x_ref, val_ref, idx_ref):
    x = x_ref[...]  # (tile_rows, bs) in VMEM
    bs = x.shape[1]
    vmin = jnp.min(x, axis=1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    cand = jnp.where(x == vmin[:, None], lanes, jnp.int32(bs))
    lidx = jnp.min(cand, axis=1)  # leftmost argmin via min-reduce
    val_ref[...] = vmin[:, None]
    idx_ref[...] = lidx[:, None]


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def block_min(x_blocks: jax.Array, *, tile_rows: int = 8, interpret: bool | None = None):
    """Per-block (min value, leftmost local argmin). x_blocks: (nb, bs)."""
    interpret = resolve_interpret(interpret)
    nb, bs = x_blocks.shape
    pad = (-nb) % tile_rows
    if pad:
        x_blocks = jnp.pad(x_blocks, ((0, pad), (0, 0)), constant_values=maxval(x_blocks.dtype))
    nbp = nb + pad
    val, idx = pl.pallas_call(
        _kernel,
        grid=(nbp // tile_rows,),
        in_specs=[pl.BlockSpec((tile_rows, bs), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((tile_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nbp, 1), x_blocks.dtype),
            jax.ShapeDtypeStruct((nbp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(x_blocks)
    return val[:nb, 0], idx[:nb, 0]

"""Pallas TPU megakernel v2: tiled, fully-fused blocked-RMQ query.

One ``pallas_call`` answers a query batch end-to-end — left partial, right
partial, *and* the O(1) sparse-table interior candidate — emitting the final
``(idx, val)``. This collapses the previous three dispatches (partials
kernel, XLA sparse-table gathers, XLA merge) into a single kernel launch.

Grid: ``(B // tile,)``. Each step gathers the rows its ``tile`` queries need
from HBM with one async DMA per query and row (``tiling.row_copies``) into
``(tile, w)`` VMEM scratch, then answers the whole tile vectorized — one VPU
masked min per partial side, one-hot lane picks for the interior cells —
and writes its ``(tile, 1)`` output block. Operand count is constant in
``tile``: one operand per logical input, the scalar-prefetch index vectors
naming each query's rows.

Two fetch strategies share the kernel body (``fetch=``):

  * ``"resident"`` — the per-block min arrays (``bmin_val``/``bmin_gidx``)
    ride along as constant whole-array VMEM residents. Each query DMAs the
    two ``(1, 128)`` windows of the level-k doubling-table row
    ``st.idx[k[q], :]`` that hold its cells, then hops through the resident
    planes with a one-hot pick over all nb lanes. VMEM and that pick grow
    with nb, which caps this path at nb ~ 2^13 blocks.
  * ``"dma"`` — nothing nb-sized touches VMEM. The doubling table is
    *value-augmented* at build time (``st_val[k, p] = bmin_val[st.idx[k, p]]``
    and ``st_gidx`` likewise, see :func:`interior_tables`), so the interior
    candidate needs only the two table cells at ``(k, ilo)`` and
    ``(k, bpos)``. Each query DMAs four ``(1, 128)`` lane-aligned windows
    (value + gidx at each of the two positions) — bounded VMEM for
    arbitrarily large nb.

A single-row DMA compiles only when the row is one 128-lane tile, so every
table the kernel windows is viewed as ``(K, nbp // 128, 128)``
(:func:`as_windows`), and the compiled kernels need ``block_size == 128``.

``fetch="auto"`` picks per the nb ceiling (``tuning.RESIDENT_NB_CEILING``).
Both strategies are bit-identical to the oracle: the lo window starts at or
before the hi window (``ilo <= bpos``), so preferring lo on value ties is
exactly the leftmost rule ``sparse_table._pick_left`` applies to the
resident tables.

Correctness: the merge keeps the exact leftmost-tie rule of
``kernels/ops.py`` — partial candidates are merged left-over-right
(``lv <= rv``), then preferred over the interior only when strictly smaller
or when the partial index lies left of the interior's block range
(``pi < (bl + 1) * bs``). See DESIGN.md §4 and §12.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing
from repro.core.block_rmq import maxval
from repro.core.sparse_table import exact_log2

from .tiling import (
    LANES,
    block_rows,
    lane_pick,
    pad_to_tiles,
    partial_candidates,
    resolve_interpret,
    row_copies,
    run_copies,
    scalar_col,
    tile_out_specs,
)
from .tuning import DEFAULT_TILE, resolve_fetch

__all__ = ["as_windows", "fused_query", "fused_query_packed", "interior_tables", "DEFAULT_TILE"]

_logger = logging.getLogger(__name__)

# One warning per process for the derive-on-the-fly DMA path (below); a
# per-call warning would flood serving logs, and a per-jit-cache warning
# would be silent exactly when the recompute recurs (same shapes re-trace).
_warned_materialize = False

# DMA window width: one lane-aligned vreg row per fetched table cell.
_W = LANES

# Scalar-prefetch operand order (SMEM: DMA addresses and per-query columns).
_N_PREFETCH = 11  # bl, br, ls, le, re, k, ilo, bpos, hasint, wlo, whi

_ANY = pl.BlockSpec(memory_space=pl.ANY)  # operand left in HBM, DMA'd by hand


def _decompose(l, r, bs: int, nb: int):
    """Host-side (XLA) per-query scalars, in ``_N_PREFETCH`` order."""
    l = l.astype(jnp.int32)
    r = r.astype(jnp.int32)
    bl = l // bs
    br = r // bs
    ls = l - bl * bs
    re = r - br * bs
    le = jnp.where(bl == br, re, bs - 1)
    hasint = ((br - bl) >= 2).astype(jnp.int32)
    ilo = jnp.clip(bl + 1, 0, nb - 1)
    ihi = jnp.maximum(jnp.clip(br - 1, 0, nb - 1), ilo)
    k = exact_log2(ihi - ilo + 1)
    bpos = ihi - jnp.left_shift(jnp.int32(1), k) + 1
    wlo = ilo // _W  # lane-aligned window ids for the dma fetch strategy
    whi = bpos // _W
    return [bl, br, ls, le, re, k, ilo, bpos, hasint, wlo, whi]


def _windows(t_hbm, k_ref, w_ref, q0, tile, buf, sem):
    """The DMAs of each query's window ``t_hbm[k[q], w[q]]`` of a
    ``(K, nbp // 128, 128)`` windowed table (:func:`as_windows`)."""
    return row_copies(
        [t_hbm.at[k_ref[q0 + t], pl.ds(w_ref[q0 + t], 1)] for t in range(tile)], buf, sem
    )


def as_windows(table: jax.Array, fill) -> jax.Array:
    """A ``(K, nb)`` table padded with ``fill`` to ``nbp`` (a multiple of 128)
    columns and viewed as ``(K, nbp // 128, 128)`` lane-aligned windows."""
    k, nb = table.shape
    nbp = -(-nb // _W) * _W
    return jnp.pad(table, ((0, 0), (0, nbp - nb)), constant_values=fill).reshape(
        k, nbp // _W, _W
    )


def _merge(pv, pi, iv, ii, bl, bs, val_ref, idx_ref):
    """Final merge, exact leftmost: prefer the partial only when strictly
    smaller, or tied with an index left of the interior block range."""
    int_start = (bl + 1) * bs
    prefer_partial = (pv < iv) | ((pv == iv) & (pi < int_start))
    val_ref[...] = jnp.where(prefer_partial, pv, iv)
    idx_ref[...] = jnp.where(prefer_partial, pi, ii)


def _kernel(tile, fetch, *refs):
    (bl_ref, br_ref, ls_ref, le_ref, re_ref,
     k_ref, ilo_ref, bpos_ref, hasint_ref, wlo_ref, whi_ref) = refs[:_N_PREFETCH]
    body = refs[_N_PREFETCH:]
    q0 = pl.program_id(0) * tile
    if fetch == "resident":
        (x_hbm, st_hbm, bv_ref, bg_ref, val_ref, idx_ref,
         xl_buf, xr_buf, lo_buf, hi_buf, sems) = body
        copies = _windows(st_hbm, k_ref, wlo_ref, q0, tile, lo_buf, sems.at[2]) + _windows(
            st_hbm, k_ref, whi_ref, q0, tile, hi_buf, sems.at[3]
        )
    else:
        (x_hbm, sv_hbm, sg_hbm, val_ref, idx_ref,
         xl_buf, xr_buf, lov, hiv, log, hig, sems) = body
        copies = (
            _windows(sv_hbm, k_ref, wlo_ref, q0, tile, lov, sems.at[2])
            + _windows(sv_hbm, k_ref, whi_ref, q0, tile, hiv, sems.at[3])
            + _windows(sg_hbm, k_ref, wlo_ref, q0, tile, log, sems.at[4])
            + _windows(sg_hbm, k_ref, whi_ref, q0, tile, hig, sems.at[5])
        )
    run_copies(block_rows(x_hbm, bl_ref, br_ref, q0, tile, xl_buf, xr_buf, sems) + copies)

    def col(ref):  # (tile, 1) column of per-query scalars from SMEM
        return scalar_col(ref, q0, tile)

    bs = xl_buf.shape[1]
    big = maxval(xl_buf.dtype)
    big_i = jnp.iinfo(jnp.int32).max
    off_lo = col(ilo_ref) - col(wlo_ref) * _W  # cell offsets inside the windows
    off_hi = col(bpos_ref) - col(whi_ref) * _W

    # This tile's interior candidates: the two doubling-table cells per query.
    if fetch == "resident":
        a = lane_pick(lo_buf[...], off_lo, big_i)  # block ids, then the hop
        b = lane_pick(hi_buf[...], off_hi, big_i)
        bv, bg = bv_ref[...], bg_ref[...]
        av, bv_ = lane_pick(bv, a, big), lane_pick(bv, b, big)
        ai, bi = lane_pick(bg, a, big_i), lane_pick(bg, b, big_i)
    else:
        av = lane_pick(lov[...], off_lo, big)
        bv_ = lane_pick(hiv[...], off_hi, big)
        ai = lane_pick(log[...], off_lo, big_i)
        bi = lane_pick(hig[...], off_hi, big_i)
    # Leftmost tie: the lo cell covers [ilo, ilo+2^k) which starts at or
    # before the hi cell's [bpos, ihi], so prefer lo on equal values.
    iv = jnp.where(col(hasint_ref) == 1, jnp.minimum(av, bv_), big)
    ii = jnp.where(av <= bv_, ai, bi)

    bl, br = col(bl_ref), col(br_ref)
    pv, pi = partial_candidates(
        xl_buf[...], xr_buf[...], bl, br, col(ls_ref), col(le_ref), col(re_ref), big
    )
    _merge(pv, pi, iv, ii, bl, bs, val_ref, idx_ref)


def interior_tables(bmin_val: jax.Array, bmin_gidx: jax.Array, st_idx: jax.Array):
    """Value-augmented doubling tables for the DMA fetch strategy.

    ``st_val[k, p] = bmin_val[st_idx[k, p]]`` and ``st_gidx`` likewise, so
    the in-kernel interior lookup is two direct cell reads instead of an
    index hop through the resident block-min arrays. Computed once at build
    (XLA gathers are fine here — this is O(K * nb) build work, keeping the
    per-query jaxpr gather-free) and returned in the kernel's windowed
    ``(K, nbp // 128, 128)`` layout, so no query relays them out.
    """
    return (
        as_windows(bmin_val[st_idx], maxval(bmin_val.dtype)),
        as_windows(bmin_gidx[st_idx], 0),
    )


@functools.partial(
    jax.jit, static_argnames=("tile", "fetch", "interpret", "materialize_interior")
)
def fused_query(
    x_blocks: jax.Array,  # (nb, bs)
    bmin_val: jax.Array,  # (nb,)
    bmin_gidx: jax.Array,  # (nb,) int32
    st_idx: jax.Array,  # (K, nb) int32 doubling table over bmin_val
    l: jax.Array,  # (B,)
    r: jax.Array,  # (B,)
    *,
    st_val: jax.Array | None = None,  # bmin_val[st_idx], windowed (K, nbp // 128, 128)
    st_gidx: jax.Array | None = None,  # bmin_gidx[st_idx] int32, windowed likewise
    tile: int = DEFAULT_TILE,
    fetch: str = "auto",
    interpret: bool | None = None,
    materialize_interior: bool | None = None,
):
    """End-to-end fused blocked RMQ. Returns (idx (B,) int32, value (B,)).

    Single kernel dispatch per batch; ``tile`` queries per grid step.
    ``fetch`` selects the table strategy ("resident" | "dma" | "auto", see
    module docstring). A DMA-strategy call that does not pass the augmented
    tables has them derived on the fly — O(K * nb) gathers *per jit trace*
    that build-time callers precompute exactly once via
    :func:`interior_tables`. ``materialize_interior`` makes that choice
    explicit: ``True`` opts into the on-the-fly derivation silently,
    ``False`` forbids it (raises instead of recomputing — for callers whose
    build stage owns the augmented tables and must notice losing them), and
    the default ``None`` derives but warns once per process.
    """
    nb, bs = x_blocks.shape
    interpret = resolve_interpret(interpret, tile, bs)
    b = l.shape[0]
    big = maxval(x_blocks.dtype)
    fetch = resolve_fetch(fetch, nb)

    # Pad the batch to a whole number of tiles with trivial (0, 0) queries.
    scalars, bp = pad_to_tiles(_decompose(l, r, bs, nb), b, tile)

    row = pltpu.VMEM((tile, bs), x_blocks.dtype)  # staged partial-block rows
    iwin = pltpu.VMEM((tile, _W), jnp.int32)  # one fetched table window per query
    if fetch == "resident":
        # Lane-align the resident planes (last dim multiple of 128 for VMEM).
        nbp = -(-nb // _W) * _W
        bv2 = jnp.pad(bmin_val, (0, nbp - nb), constant_values=big)[None, :]
        bg2 = jnp.pad(bmin_gidx, (0, nbp - nb))[None, :]
        in_specs = [
            _ANY,  # x_blocks: rows x_blocks[bl[q]], x_blocks[br[q]]
            _ANY,  # st.idx windows
            pl.BlockSpec((1, nbp), lambda i, *s: (0, 0)),  # bmin_val (resident)
            pl.BlockSpec((1, nbp), lambda i, *s: (0, 0)),  # bmin_gidx (resident)
        ]
        # At nb <= the resident ceiling this per-call relayout is < 1 MiB.
        operands = (x_blocks, as_windows(st_idx, 0), bv2, bg2)
        scratch = [row, row, iwin, iwin, pltpu.SemaphoreType.DMA((4,))]
    else:
        if st_val is None or st_gidx is None:
            if materialize_interior is False:
                raise ValueError(
                    "fetch='dma' without st_val/st_gidx while "
                    "materialize_interior=False: the caller expected "
                    "precomputed augmented tables (interior_tables) but "
                    "the structure does not carry them"
                )
            if materialize_interior is None:
                global _warned_materialize
                if not _warned_materialize:
                    _warned_materialize = True
                    _logger.warning(
                        "fused_query fetch='dma' is deriving its augmented "
                        "interior tables on the fly (O(K*nb) gathers per jit "
                        "trace). Precompute them at build time "
                        "(kernels.ops.build / interior_tables), or pass "
                        "materialize_interior=True to opt in silently."
                    )
            st_val, st_gidx = interior_tables(bmin_val, bmin_gidx, st_idx)
        in_specs = [_ANY, _ANY, _ANY]  # x_blocks rows; st_val, st_gidx windows
        operands = (x_blocks, st_val, st_gidx)
        vwin = pltpu.VMEM((tile, _W), x_blocks.dtype)
        scratch = [row, row, vwin, vwin, iwin, iwin, pltpu.SemaphoreType.DMA((6,))]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=_N_PREFETCH,
        grid=(bp // tile,),
        in_specs=in_specs,
        out_specs=tile_out_specs(tile),
        scratch_shapes=scratch,
    )
    val, idx = pl.pallas_call(
        functools.partial(_kernel, tile, fetch),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bp, 1), x_blocks.dtype),
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(*scalars, *operands)
    return idx[:b, 0], val[:b, 0]


# --- packed megakernel ------------------------------------------------------
#
# The bandwidth-optimal variant (DESIGN.md §13). For the exact packed32
# layout every table the kernel touches is ONE plane of order-isomorphic
# int32 words, so:
#
#   * the partial-block scan is a plain masked word min — no equality
#     rescan to recover the lane, the word IS (value, global index);
#   * the interior candidate is two cells of the packed doubling table
#     ``stw`` — TWO (1, 128) windows per query where the unpacked dma
#     kernel fetches FOUR (value + gidx at each position), and NO resident
#     planes at all, so packed32 has one kernel and no ``fetch`` knob;
#   * the final merge is ``min`` of three words — the leftmost-tie
#     select chain is subsumed by word order, and the kernel emits one
#     packed word per query that the host unpacks.
#
# The quantized layout keeps raw value blocks (partials need exact values)
# and fetches interior candidates from the int32 ``stw`` of
# (bucket, exact-argmin) words; bucket ties fall back to exact values via
# the resident ``bmin_val`` plane — the argmin of an interior window is the
# minimum of its own (fully covered) block, so ``bmin_val[idx // bs]`` IS
# its exact value. That fallback hop is why quantized has no dma strategy.
#
# packed64 words are int64 — outside the TPU kernel vocabulary — so that
# layout serves through the XLA packed engines, never this kernel.


def _kernel_packed(tile, idx_bits, pad, *refs):
    """Exact-layout (packed32) kernel body: everything is int32 words."""
    (bl_ref, br_ref, ls_ref, le_ref, re_ref,
     k_ref, ilo_ref, bpos_ref, hasint_ref, wlo_ref, whi_ref) = refs[:_N_PREFETCH]
    x_hbm, stw_hbm, word_ref, xl_buf, xr_buf, lo_buf, hi_buf, sems = refs[_N_PREFETCH:]
    q0 = pl.program_id(0) * tile
    run_copies(
        block_rows(x_hbm, bl_ref, br_ref, q0, tile, xl_buf, xr_buf, sems)
        + _windows(stw_hbm, k_ref, wlo_ref, q0, tile, lo_buf, sems.at[2])
        + _windows(stw_hbm, k_ref, whi_ref, q0, tile, hi_buf, sems.at[3])
    )

    def col(ref):
        return scalar_col(ref, q0, tile)

    wa = lane_pick(lo_buf[...], col(ilo_ref) - col(wlo_ref) * _W, pad)
    wb = lane_pick(hi_buf[...], col(bpos_ref) - col(whi_ref) * _W, pad)
    iw = jnp.where(col(hasint_ref) == 1, jnp.minimum(wa, wb), pad)

    bl, br = col(bl_ref), col(br_ref)
    ls, le, re = col(ls_ref), col(le_ref), col(re_ref)
    lanes = jax.lax.broadcasted_iota(jnp.int32, xl_buf.shape, 1)
    # Partials: one masked word min per side; the min word IS the
    # leftmost argmin (pad words strictly dominate real ones).
    lw = jnp.min(
        jnp.where((lanes >= ls) & (lanes <= le), xl_buf[...], pad), axis=1, keepdims=True
    )
    rw = jnp.min(jnp.where(lanes <= re, xr_buf[...], pad), axis=1, keepdims=True)
    rw = jnp.where(br > bl, rw, pad)
    word_ref[...] = jnp.minimum(jnp.minimum(lw, rw), iw)


def _kernel_quantized(tile, idx_bits, *refs):
    """Quantized kernel body: raw-value partials + bucket-word interior with
    the exact fallback hop through the resident ``bmin_val`` plane."""
    (bl_ref, br_ref, ls_ref, le_ref, re_ref,
     k_ref, ilo_ref, bpos_ref, hasint_ref, wlo_ref, whi_ref) = refs[:_N_PREFETCH]
    (x_hbm, stw_hbm, bv_ref, val_ref, idx_ref,
     xl_buf, xr_buf, lo_buf, hi_buf, sems) = refs[_N_PREFETCH:]
    q0 = pl.program_id(0) * tile
    run_copies(
        block_rows(x_hbm, bl_ref, br_ref, q0, tile, xl_buf, xr_buf, sems)
        + _windows(stw_hbm, k_ref, wlo_ref, q0, tile, lo_buf, sems.at[2])
        + _windows(stw_hbm, k_ref, whi_ref, q0, tile, hi_buf, sems.at[3])
    )

    def col(ref):
        return scalar_col(ref, q0, tile)

    bs = xl_buf.shape[1]
    big = maxval(xl_buf.dtype)
    big_i = jnp.iinfo(jnp.int32).max
    mask = (1 << idx_bits) - 1

    wa = lane_pick(lo_buf[...], col(ilo_ref) - col(wlo_ref) * _W, big_i)
    wb = lane_pick(hi_buf[...], col(bpos_ref) - col(whi_ref) * _W, big_i)
    ai = wa & mask
    bi = wb & mask
    # Exact values via the block hop: an interior cell's argmin is the min
    # of its own fully-covered block, so its exact value is that block's.
    bv = bv_ref[...]
    ava = lane_pick(bv, ai // bs, big)
    avb = lane_pick(bv, bi // bs, big)
    collide = (wa >> idx_bits) == (wb >> idx_bits)
    # (A select between two boolean vectors does not lower on the TPU.)
    take_a = (collide & (ava <= avb)) | (~collide & (wa <= wb))
    iv = jnp.where(col(hasint_ref) == 1, jnp.where(take_a, ava, avb), big)
    ii = jnp.where(take_a, ai, bi)

    bl, br = col(bl_ref), col(br_ref)
    pv, pi = partial_candidates(
        xl_buf[...], xr_buf[...], bl, br, col(ls_ref), col(le_ref), col(re_ref), big
    )
    _merge(pv, pi, iv, ii, bl, bs, val_ref, idx_ref)


@functools.partial(jax.jit, static_argnames=("spec", "tile", "interpret"))
def fused_query_packed(
    blocks: jax.Array,  # (nb, bs): packed words (packed32) | raw values (quantized)
    stw: jax.Array,  # (K, nb) int32 packed doubling table over block minima
    l: jax.Array,  # (B,)
    r: jax.Array,  # (B,)
    *,
    spec,  # packing.PackSpec (static: hashable NamedTuple of primitives)
    bmin_val: jax.Array | None = None,  # (nb,) exact minima (quantized only)
    tile: int = DEFAULT_TILE,
    interpret: bool | None = None,
):
    """Packed fused blocked RMQ. Returns (idx (B,) int32, value (B,)).

    One kernel dispatch per batch over single-plane packed structures (see
    the section comment above for the per-layout fetch volumes). Each layout
    has one kernel, so there is no ``fetch`` knob: packed32 DMAs ``stw``
    windows only, quantized adds its resident ``bmin_val`` plane. packed64
    raises — int64 words have no TPU kernel path.
    """
    nb, bs = blocks.shape
    interpret = resolve_interpret(interpret, tile, bs)
    if spec.layout == "packed64":
        raise ValueError(
            "packed64 words are int64 and have no TPU kernel path; "
            "serve packed64 through the XLA packed engines"
        )
    if spec.layout not in ("packed32", "quantized"):
        raise ValueError(f"fused_query_packed wants packed32|quantized, got {spec.layout!r}")
    b = l.shape[0]
    pad = packing.pad_word(spec)

    scalars, bp = pad_to_tiles(_decompose(l, r, bs, nb), b, tile)

    row = pltpu.VMEM((tile, bs), blocks.dtype)
    win = pltpu.VMEM((tile, _W), jnp.int32)
    scratch = [row, row, win, win, pltpu.SemaphoreType.DMA((4,))]
    # The packed doubling table is built (K, nb); this per-call relayout to
    # windows costs one pass over it.
    stw3 = as_windows(stw, pad)

    if spec.layout == "quantized":
        if bmin_val is None:
            raise ValueError("quantized fused_query_packed needs the bmin_val plane")
        # The exact-fallback hop lives in this resident plane: no dma strategy.
        nbp = -(-nb // _W) * _W
        bv2 = jnp.pad(bmin_val, (0, nbp - nb), constant_values=maxval(blocks.dtype))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=_N_PREFETCH,
            grid=(bp // tile,),
            in_specs=[
                _ANY,  # raw blocks: rows blocks[bl[q]], blocks[br[q]]
                _ANY,  # stw windows
                pl.BlockSpec((1, nbp), lambda i, *s: (0, 0)),  # bmin_val
            ],
            out_specs=tile_out_specs(tile),
            scratch_shapes=scratch,
        )
        val, idx = pl.pallas_call(
            functools.partial(_kernel_quantized, tile, spec.idx_bits),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((bp, 1), blocks.dtype),
                jax.ShapeDtypeStruct((bp, 1), jnp.int32),
            ],
            interpret=interpret,
        )(*scalars, blocks, stw3, bv2[None, :])
        return idx[:b, 0], val[:b, 0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=_N_PREFETCH,
        grid=(bp // tile,),
        in_specs=[_ANY, _ANY],  # word block rows; stw windows
        out_specs=tile_out_specs(tile, 1),
        scratch_shapes=scratch,
    )
    (word,) = pl.pallas_call(
        functools.partial(_kernel_packed, tile, spec.idx_bits, pad),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bp, 1), jnp.int32)],
        interpret=interpret,
    )(*scalars, blocks, stw3)
    w = word[:b, 0]
    return packing.unpack_idx(spec, w), packing.unpack_val(spec, w)

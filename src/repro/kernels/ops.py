"""Jit'd public wrappers: the kernelized RTXRMQ-TPU engine.

``build`` / ``query`` mirror ``repro.core.block_rmq`` but route the hot path
through the Pallas kernels (validated in interpret mode on CPU, compiled for
TPU on real hardware). ``query`` dispatches the *fused tiled megakernel*
(``fused_query.py``): one kernel launch answers the whole batch end-to-end —
partials, sparse-table interior, and final merge — ``tile`` queries per grid
step, with the launch geometry (tile, table fetch strategy) taken from a
``tuning.KernelConfig``. ``build`` returns a ``FusedRMQ``: the shared
``BlockRMQ`` fields plus the value-augmented doubling tables the DMA fetch
strategy reads, precomputed once so the per-query jaxpr stays gather-free.
The legacy two-pass path (partials kernel + XLA interior/merge) remains
available via ``query(..., fused=False)`` for A/B benchmarking.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import block_rmq, packing, sparse_table
from repro.core.block_rmq import BlockRMQ, maxval, _pick

from .block_min import block_min
from .fused_query import DEFAULT_TILE, fused_query, fused_query_packed, interior_tables
from .lane_query import lane_partials
from .rmq_query import rmq_partials
from .tuning import KernelConfig

__all__ = [
    "FusedRMQ",
    "PackedFusedRMQ",
    "build",
    "build_packed",
    "query",
    "query_packed",
    "block_min",
    "fused_query",
    "fused_query_packed",
    "rmq_partials",
    "lane_query",
    "lane_partials",
]


class FusedRMQ(NamedTuple):
    """Megakernel state: ``BlockRMQ``'s fields + the DMA-strategy tables.

    A separate type (rather than widening ``BlockRMQ``) because
    ``distributed.py``'s PartitionSpecs mirror ``BlockRMQ``'s field layout;
    the augmented tables are single-host kernel state only.
    """

    x_blocks: jax.Array  # (nb, bs)
    bmin_val: jax.Array  # (nb,)
    bmin_gidx: jax.Array  # (nb,) int32
    st: sparse_table.SparseTable  # doubling table over bmin_val
    st_val: jax.Array  # bmin_val[st.idx], windowed (K, nbp // 128, 128) (dma)
    st_gidx: jax.Array  # bmin_gidx[st.idx] int32, windowed likewise


def build(x: jax.Array, block_size: int, *, interpret: bool | None = None) -> FusedRMQ:
    """Kernelized build: Pallas per-block minima + doubling tables."""
    if block_size % 128 != 0:
        raise ValueError(f"block_size must be a multiple of 128, got {block_size}")
    n = x.shape[0]
    nb = -(-n // block_size)
    big = maxval(x.dtype)
    xp = jnp.pad(x, (0, nb * block_size - n), constant_values=big)
    xb = xp.reshape(nb, block_size)
    bmin_val, lidx = block_min(xb, interpret=interpret)
    bmin_gidx = jnp.arange(nb, dtype=jnp.int32) * block_size + lidx
    st = sparse_table.build(bmin_val)
    st_val, st_gidx = interior_tables(bmin_val, bmin_gidx, st.idx)
    return FusedRMQ(
        x_blocks=xb,
        bmin_val=bmin_val,
        bmin_gidx=bmin_gidx,
        st=st,
        st_val=st_val,
        st_gidx=st_gidx,
    )


class PackedFusedRMQ(NamedTuple):
    """Packed megakernel state (DESIGN.md §13): single-plane tables.

    ``blocks`` holds packed words for exact layouts (the kernel's partial
    scan is a word min) or raw values for the quantized layout (partials
    need exact values); ``stw`` is the packed doubling table over block
    minima — the only table the kernel fetches. ``bmin_val`` is the
    quantized layout's exact-fallback resident plane (None otherwise).
    The shared ``PackSpec`` rides beside the state, not in it, so this
    pytree stays all-array (checkpoint leaves, device_put, shard specs).
    """

    blocks: jax.Array  # (nb, bs) packed words | raw values (quantized)
    stw: jax.Array  # (K, nb) packed doubling table
    bmin_val: jax.Array | None = None  # (nb,) exact minima, quantized only


def build_packed(
    x: jax.Array,
    block_size: int,
    *,
    spec=None,
    layout: str = "auto",
    interpret: bool | None = None,
):
    """Packed kernel build. Returns ``(PackedFusedRMQ, spec)``.

    Structure math is shared with ``core.block_rmq.build_packed`` (the
    kernel consumes the same word planes the XLA engines do); the quantized
    layout additionally keeps its exact per-block minima for the in-kernel
    fallback hop. ``interpret`` is accepted for signature parity with
    ``build`` — the packed build is pure XLA.
    """
    del interpret  # no Pallas stage in the packed build
    if block_size % 128 != 0:
        raise ValueError(f"block_size must be a multiple of 128, got {block_size}")
    s, spec = block_rmq.build_packed(x, block_size, spec=spec, layout=layout)
    bmin_val = None
    if spec.layout == "quantized":
        bmin_val = jnp.min(s.blocks, axis=1)  # blocks are raw (maxval-padded)
    return PackedFusedRMQ(blocks=s.blocks, stw=s.stw, bmin_val=bmin_val), spec


def query_packed(
    s: PackedFusedRMQ,
    spec,
    l: jax.Array,
    r: jax.Array,
    *,
    config: KernelConfig | None = None,
    tile: int | None = None,
    interpret: bool | None = None,
):
    """Packed megakernel batched query -> (leftmost argmin idx int32, value).

    Mirrors :func:`query` over ``PackedFusedRMQ`` state; the launch
    geometry comes from ``config`` (its ``layout`` and ``fetch`` fields are
    the tuner's bookkeeping — the structure's ``spec`` is authoritative
    here, and each packed layout has one kernel).
    """
    if config is None:
        config = KernelConfig()
    if tile is None:
        tile = config.tile
    return fused_query_packed(
        s.blocks,
        s.stw,
        l,
        r,
        spec=spec,
        bmin_val=s.bmin_val,
        tile=tile,
        interpret=interpret,
    )


def query(
    s,
    l: jax.Array,
    r: jax.Array,
    *,
    config: KernelConfig | None = None,
    tile: int | None = None,
    fetch: str | None = None,
    fused: bool = True,
    interpret: bool | None = None,
):
    """Kernelized batched query. Returns (leftmost argmin idx int32, value).

    ``s`` is a ``FusedRMQ`` (or a bare ``BlockRMQ``, in which case the DMA
    strategy derives its augmented tables on the fly). ``config`` carries the
    tuned launch geometry (its build-time ``block_size`` knob is ignored here
    — the structure is already committed to one); ``tile``/``fetch`` override
    the individual knobs for direct A/B calls.

    ``fused=True`` (default): single megakernel dispatch (fused_query.py).
    ``fused=False``: legacy two-pass path — tiled partials kernel, then the
    XLA sparse-table interior + merge (kept for A/B benchmarking).
    """
    if config is None:
        config = KernelConfig()
    if tile is None:
        tile = config.tile
    if fetch is None:
        fetch = config.fetch
    if fused:
        return fused_query(
            s.x_blocks, s.bmin_val, s.bmin_gidx, s.st.idx, l, r,
            st_val=getattr(s, "st_val", None),
            st_gidx=getattr(s, "st_gidx", None),
            tile=tile, fetch=fetch, interpret=interpret,
        )
    bs = s.x_blocks.shape[1]
    nb = s.x_blocks.shape[0]
    big = maxval(s.x_blocks.dtype)
    l = l.astype(jnp.int32)
    r = r.astype(jnp.int32)

    bl = l // bs
    br = r // bs
    ll = l - bl * bs
    rl = r - br * bs
    lend = jnp.where(bl == br, rl, bs - 1)

    pv, pi = rmq_partials(s.x_blocks, bl, br, ll, lend, rl, tile=tile, interpret=interpret)

    has_interior = (br - bl) >= 2
    ilo = jnp.clip(bl + 1, 0, nb - 1)
    ihi = jnp.maximum(jnp.clip(br - 1, 0, nb - 1), ilo)
    bi = sparse_table.query(s.st, ilo, ihi)
    iv = jnp.where(has_interior, s.bmin_val[bi], big)
    ii = s.bmin_gidx[bi]

    # Partial candidates straddle the interior in index order; exactness of
    # the leftmost tie still holds: if the interior ties with the left
    # partial, the left partial's indices are smaller; if it ties with the
    # right partial, the interior's indices are smaller — and the fused
    # kernel already resolved left-vs-right. Prefer (left|right) only when
    # strictly smaller OR when it is the left partial (pi < interior block
    # range start).
    int_start = (bl + 1) * bs
    prefer_partial = (pv < iv) | ((pv == iv) & (pi < int_start))
    v = jnp.where(prefer_partial, pv, iv)
    i = jnp.where(prefer_partial, pi, ii)
    return i, v


def lane_query(
    s,
    l: jax.Array,
    r: jax.Array,
    *,
    tile: int = DEFAULT_TILE,
    interpret: bool | None = None,
):
    """Kernelized beyond-paper lane-RMQ query (mirrors core.lane_rmq.query).

    The fused tiled Pallas kernel answers the same-block case and the
    straddle prefix/suffix candidates (``tile`` queries per grid step); the
    O(1) sparse-table interior stays in XLA.
    """
    from repro.core import lane_rmq, sparse_table
    from repro.core.block_rmq import _pick

    nsub = s.xs.shape[0]
    big = maxval(s.xs.dtype)
    l = l.astype(jnp.int32)
    r = r.astype(jnp.int32)
    sl = l // lane_rmq.LANE
    sr = r // lane_rmq.LANE
    llo = l - sl * lane_rmq.LANE
    rlo = r - sr * lane_rmq.LANE

    pv, pi = lane_partials(
        s.xs, s.suff_val, s.suff_idx, s.pref_val, s.pref_idx,
        sl, sr, llo, rlo, tile=tile, interpret=interpret,
    )

    has_interior = (sr - sl) >= 2
    ilo = jnp.clip(sl + 1, 0, nsub - 1)
    ihi = jnp.maximum(jnp.clip(sr - 1, 0, nsub - 1), ilo)
    bi = sparse_table.query(s.st, ilo, ihi)
    iv = jnp.where(has_interior, s.st.x[bi], big)
    ii = s.sub_gidx[bi]
    # same tie logic as kernels.ops.query: the interior's indices sit between
    # the suffix and prefix candidates, so prefer the partial only when it is
    # strictly smaller or it comes from the left (suffix) side.
    int_start = (sl + 1) * lane_rmq.LANE
    prefer_partial = (pv < iv) | ((pv == iv) & (pi < int_start))
    return jnp.where(prefer_partial, pi, ii), jnp.where(prefer_partial, pv, iv)

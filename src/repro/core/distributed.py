"""Level-3 of the hierarchy: RMQ sharded across the device mesh.

The paper leaves multi-BVH distribution as future work (§7.i): "one BVH per
cluster of blocks". On a TPU pod that is exactly block-range ownership per
device: each device holds a contiguous chunk of the array with its own local
blocked structure, answers the query restricted to its chunk, and the shards
merge with two all-reduce-mins over ICI (value min, then leftmost index among
value-matching shards — exact leftmost semantics with only min collectives).

Works on any mesh: the array is sharded over *all* given axes flattened, so
the same code runs a 16x16 pod and a (pod=2, 16, 16) multi-pod mesh.

Three distribution strategies are provided (DESIGN.md §6, §8):

* **Structure-sharded** (``build_sharded`` / ``build_sharded_st`` +
  ``make_query_fn`` / ``make_st_query_fn``): the *array* is sharded, the
  query batch is replicated, and every device answers every query against
  its chunk; shards merge with the two-pmin leftmost trick. Memory scales
  with device count; per-query work is replicated.
* **Batch-sharded** (``build_replicated`` / ``build_replicated_st`` + the
  same query factories with ``batch_sharded=True``): the *query batch* is
  sharded over the flattened mesh axes and each device answers its slice
  locally against a replicated structure. Serving throughput scales with
  device count; each query is answered by exactly one device, so the merge
  degenerates from the two-pmin reduction to a collective-free concatenation
  along the sharded batch dim.
* **2D (structure x batch)** (the same factories with ``batch_axes=...``):
  the structure is sharded over the given ``axis_names`` and the query batch
  over the disjoint ``batch_axes``, so memory AND throughput both scale —
  each batch slice is answered by one structure-shard group, merged with
  pmins over the structure axes only.

The sharded sparse-table path (``ShardedSparseTable``) is the long-range
constituent of ``core.sharded_hybrid``: the doubling table is column-sharded,
each lookup column is owned by exactly one device (per structure-shard
group), and the two window candidates merge with the same pmin trick. Its
build is *distributed* — per-shard doubling with a level-k halo exchange of
boundary columns (``st_local_level0`` + ``st_halo_doubling``, sequenced by
the ``core.build`` BuildPlan pipeline) — so build-time memory is bounded by
the shard, never the full (K, n) table.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from . import block_rmq, packing, sparse_table
from .block_rmq import BlockRMQ, PackedBlockRMQ, maxval
from .sparse_table import PackedSparseTable, SparseTable

__all__ = [
    "ShardedSparseTable",
    "build_replicated",
    "build_replicated_packed",
    "build_replicated_st",
    "build_replicated_st_packed",
    "build_sharded",
    "build_sharded_packed",
    "build_sharded_st",
    "build_sharded_st_packed",
    "make_packed_query_fn",
    "make_packed_st_query_fn",
    "make_query_fn",
    "make_st_query_fn",
    "num_shards",
    "pack_global",
    "pad_to_shards",
    "patch_sharded",
    "patch_sharded_packed",
    "patch_sharded_st",
    "patch_sharded_st_packed",
    "st_halo_doubling",
    "st_halo_doubling_packed",
    "st_levels",
    "st_local_level0",
]

_INT_BIG = jnp.int32(2**31 - 1)


def num_shards(mesh: Mesh, axis_names: Sequence[str]) -> int:
    """Product of the given mesh axes — the flattened shard count."""
    num = 1
    for a in axis_names:
        num *= mesh.shape[a]
    return num


def _flat_axis_index(axis_names: Sequence[str]) -> jax.Array:
    """Flattened linear device index across the given mesh axes."""
    idx = jnp.int32(0)
    for name in axis_names:
        idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
    return idx


def pad_to_shards(x: jax.Array, num_shards: int, block_size: int) -> jax.Array:
    """Pad so every shard owns the same whole number of blocks."""
    chunk = num_shards * block_size
    n_pad = -(-x.shape[0] // chunk) * chunk
    return jnp.pad(x, (0, n_pad - x.shape[0]), constant_values=maxval(x.dtype))


@functools.lru_cache(maxsize=None)
def _sharded_build_fn(mesh: Mesh, axis_names: Tuple[str, ...], block_size: int):
    def local_build(x_local):
        return block_rmq.build(x_local[0], block_size)

    out_specs = BlockRMQ(
        x_blocks=P(axis_names),
        bmin_val=P(axis_names),
        bmin_gidx=P(axis_names),
        st=SparseTable(idx=P(None, axis_names), x=P(axis_names)),
    )
    return jax.jit(
        shard_map(
            local_build,
            mesh=mesh,
            in_specs=P(axis_names),
            out_specs=out_specs,
            check_vma=False,
        )
    )


def build_sharded(x: jax.Array, mesh: Mesh, axis_names: Sequence[str], block_size: int) -> BlockRMQ:
    """Build per-shard blocked structures; leaves are sharded on the block dim.

    The BuildPlan "local build" stage of the mesh engines: no communication,
    one compiled (and cached) per-shard ``block_rmq.build`` over the mesh.
    """
    axis_names = tuple(axis_names)
    num = num_shards(mesh, axis_names)
    x = pad_to_shards(x, num, block_size)
    # shard_map gives each shard x of shape (n/num,); wrap in a leading dim so
    # the local function sees a rank-1 chunk regardless of axis grouping.
    return _sharded_build_fn(mesh, axis_names, block_size)(x.reshape(num, -1))


def _block_rmq_specs(spec_blocks, spec_table):
    """BlockRMQ pytree of PartitionSpecs: block dim `spec_blocks`, tables too."""
    return BlockRMQ(
        x_blocks=spec_blocks,
        bmin_val=spec_blocks,
        bmin_gidx=spec_blocks,
        st=SparseTable(idx=spec_table, x=spec_blocks),
    )


def _pad_batch(l, r, num: int):
    """Pad a query batch with trivial (0, 0) queries to a multiple of `num`."""
    b = l.shape[0]
    bp = -(-b // num) * num
    return jnp.pad(l, (0, bp - b)), jnp.pad(r, (0, bp - b)), b


def _check_batch_axes(axis_names, batch_axes, batch_sharded):
    """Normalize/validate the 2D-mode batch axes (disjoint from structure)."""
    batch_axes = tuple(batch_axes or ())
    if batch_axes and batch_sharded:
        raise ValueError("batch_axes is the 2D mode; batch_sharded shards over "
                         "ALL axes — pass one or the other")
    overlap = set(batch_axes) & set(axis_names)
    if overlap:
        raise ValueError(f"batch_axes {sorted(overlap)} overlap the structure axes")
    return batch_axes


def make_query_fn(
    mesh: Mesh,
    axis_names: Sequence[str],
    *,
    batch_sharded: bool = False,
    batch_axes: Sequence[str] | None = None,
):
    """Jitted batched distributed query: (BlockRMQ, l, r) -> (idx, val).

    ``batch_sharded=False`` (default): the structure is sharded
    (``build_sharded``), queries are replicated, every device answers every
    query against its chunk, and shards merge with two pmin all-reduces.

    ``batch_sharded=True``: the structure is replicated (``build_replicated``),
    the query batch is sharded over the flattened mesh axes, and each device
    answers only its ``B / num_shards`` slice — work scales with device count
    and the outputs concatenate along the sharded batch dim with no
    collective. Batches are padded internally to a shard multiple.

    ``batch_axes=...`` (2D mesh mode): the structure stays sharded over
    ``axis_names`` while the query batch is sharded over the disjoint
    ``batch_axes`` — each batch slice is answered by one structure-shard
    group, so the pmin merge runs over the structure axes only and both
    memory and throughput scale. Empty ``batch_axes`` degrades exactly to
    the default structure-sharded path.
    """
    axis_names = tuple(axis_names)
    batch_axes = _check_batch_axes(axis_names, batch_axes, batch_sharded)

    if batch_sharded:
        num = num_shards(mesh, axis_names)
        inner = shard_map(
            block_rmq.query,
            mesh=mesh,
            in_specs=(_block_rmq_specs(P(), P()), P(axis_names), P(axis_names)),
            out_specs=(P(axis_names), P(axis_names)),
            check_vma=False,
        )

        def fn(s: BlockRMQ, l, r):
            lp, rp, b = _pad_batch(l, r, num)
            idx, val = inner(s, lp, rp)
            return idx[:b], val[:b]

        return jax.jit(fn)

    def local_query(s: BlockRMQ, l, r):
        bs = s.x_blocks.shape[1]
        local_n = s.x_blocks.shape[0] * bs
        big = maxval(s.x_blocks.dtype)
        off = _flat_axis_index(axis_names) * local_n

        has = (r >= off) & (l <= off + local_n - 1)
        ql = jnp.clip(l - off, 0, local_n - 1)
        qr = jnp.clip(r - off, 0, local_n - 1)
        idx, val = block_rmq.query(s, ql, qr)
        val = jnp.where(has, val, big)
        gidx = jnp.where(has, idx + off, _INT_BIG)

        # Exact leftmost merge with two min all-reduces over ICI.
        vmin = jax.lax.pmin(val, axis_names)
        cand = jnp.where(val == vmin, gidx, _INT_BIG)
        imin = jax.lax.pmin(cand, axis_names)
        return imin, vmin

    spec_b = P(batch_axes) if batch_axes else P()
    in_specs = (
        _block_rmq_specs(P(axis_names), P(None, axis_names)),
        spec_b,  # queries replicated (default) or sharded over batch_axes (2D)
        spec_b,
    )
    inner = shard_map(
        local_query,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(spec_b, spec_b),
        check_vma=False,
    )
    if not batch_axes:
        return jax.jit(inner)
    nb = num_shards(mesh, batch_axes)

    def fn(s: BlockRMQ, l, r):
        lp, rp, b = _pad_batch(l, r, nb)
        idx, val = inner(s, lp, rp)
        return idx[:b], val[:b]

    return jax.jit(fn)


def build_replicated(x: jax.Array, mesh: Mesh, block_size: int) -> BlockRMQ:
    """Full blocked structure, replicated on every device (batch-sharded mode).

    The memory/throughput dual of ``build_sharded``: every device holds the
    whole structure so it can answer any query slice locally.
    """
    s = block_rmq.build(x, block_size)
    return jax.device_put(s, jax.sharding.NamedSharding(mesh, P()))


class ShardedSparseTable(NamedTuple):
    """Globally-built doubling table, column-sharded over the mesh.

    Unlike the per-shard tables inside ``build_sharded`` (whose windows never
    cross a chunk boundary), this table is built over the *full* array and
    then sharded by column, so any O(1) window lookup is answered by exactly
    the device owning that column. ``val`` materializes ``x[idx]`` so a
    lookup never needs a cross-shard value gather.
    """

    idx: jax.Array  # (K, n_pad) int32 leftmost argmin per doubling window
    val: jax.Array  # (K, n_pad) the corresponding window-min values


def _flat_shift(x, mesh: Mesh, axis_names: Sequence[str], d: int):
    """Value held by the shard ``d`` places to the right in flattened order.

    The halo-exchange transport: each device receives the array held by the
    device whose flattened index (over ``axis_names``) is its own plus ``d``;
    devices whose source falls off the grid receive zeros (callers mask those
    positions — they correspond to out-of-range global columns). A flat shift
    over a multi-axis product decomposes into a minor-axis rotation plus a
    carry-select between two recursive shifts of the remaining axes, so only
    single-axis ``ppermute`` collectives are ever issued.
    """
    if d == 0:
        return x
    name = axis_names[-1]
    size = mesh.shape[name]
    if len(axis_names) == 1:
        if d >= size:
            return jnp.zeros_like(x)
        return jax.lax.ppermute(x, name, [(i, i - d) for i in range(d, size)])
    d_major, d_minor = divmod(d, size)
    rot = (
        jax.lax.ppermute(x, name, [(i, (i - d_minor) % size) for i in range(size)])
        if d_minor
        else x
    )
    lo = _flat_shift(rot, mesh, axis_names[:-1], d_major)
    if d_minor == 0:
        return lo
    hi = _flat_shift(rot, mesh, axis_names[:-1], d_major + 1)
    carry = jax.lax.axis_index(name) + d_minor >= size
    return jnp.where(carry, hi, lo)


def st_levels(n_pad: int) -> int:
    """Doubling-table depth for a length-``n_pad`` array (matches
    ``sparse_table.build`` exactly — bit-identity depends on it)."""
    return max(1, (n_pad - 1).bit_length() + 1) if n_pad > 1 else 1


@functools.lru_cache(maxsize=None)
def _st_level0_fn(mesh: Mesh, axis_names: Tuple[str, ...], shard_len: int):
    def local(x_local):
        flat = _flat_axis_index(axis_names)
        idx = flat * shard_len + jnp.arange(shard_len, dtype=jnp.int32)
        return idx.astype(jnp.int32), x_local

    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=P(axis_names),
            out_specs=(P(axis_names), P(axis_names)),
            check_vma=False,
        )
    )


def st_local_level0(
    xp: jax.Array, mesh: Mesh, axis_names: Sequence[str]
) -> Tuple[jax.Array, jax.Array]:
    """BuildPlan "local build" stage: per-shard level-0 (idx, val) rows.

    ``xp`` is the shard-divisible padded array; each device computes the
    trivial level-0 row for its own columns (global index + value) with no
    communication. Outputs stay column-sharded over ``axis_names``.
    """
    axis_names = tuple(axis_names)
    num = num_shards(mesh, axis_names)
    return _st_level0_fn(mesh, axis_names, xp.shape[0] // num)(xp)


@functools.lru_cache(maxsize=None)
def _st_halo_fn(mesh: Mesh, axis_names: Tuple[str, ...], n_pad: int, num: int):
    shard_len = n_pad // num
    k_levels = st_levels(n_pad)

    def local(idx, val):
        flat = _flat_axis_index(axis_names)
        cols = jnp.arange(shard_len, dtype=jnp.int32)
        is_last = flat == num - 1
        # Rows are written into the preallocated tables in place, so only
        # the current level stays live beside them (not every level's row).
        idx_tab = jnp.zeros((k_levels, shard_len), idx.dtype).at[0].set(idx)
        val_tab = jnp.zeros((k_levels, shard_len), val.dtype).at[0].set(val)
        for k in range(1, k_levels):
            h = 1 << (k - 1)
            if h >= n_pad:
                # Window spans the whole array: rows repeat from here on
                # (sparse_table.build appends cur unchanged).
                idx_tab = idx_tab.at[k].set(idx)
                val_tab = val_tab.at[k].set(val)
                continue
            d, r = divmod(h, shard_len)
            wi = _flat_shift(idx, mesh, axis_names, d)
            wv = _flat_shift(val, mesh, axis_names, d)
            if r:
                bi = _flat_shift(idx, mesh, axis_names, d + 1)
                bv = _flat_shift(val, mesh, axis_names, d + 1)
                wi = jnp.concatenate([wi[r:], bi[:r]])
                wv = jnp.concatenate([wv[r:], bv[:r]])
            # Tail clamp: global column >= n_pad reads the previous row's
            # last column. Only the last shard holds it; pmax over -1 filler
            # (indices are non-negative) and a one-contributor psum broadcast
            # the (idx, val) pair everywhere.
            g = flat * shard_len + h + cols
            last_i = jax.lax.pmax(jnp.where(is_last, idx[-1], -1), axis_names)
            last_v = jax.lax.psum(
                jnp.where(is_last, val[-1], jnp.zeros_like(val[-1])), axis_names
            )
            wi = jnp.where(g >= n_pad, last_i, wi)
            wv = jnp.where(g >= n_pad, last_v, wv)
            take = val <= wv  # leftmost-tie: prefer the unshifted (left) row
            idx = jnp.where(take, idx, wi)
            val = jnp.where(take, val, wv)
            idx_tab = idx_tab.at[k].set(idx)
            val_tab = val_tab.at[k].set(val)
        return idx_tab, val_tab

    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis_names), P(axis_names)),
            out_specs=(P(None, axis_names), P(None, axis_names)),
            check_vma=False,
        )
    )


def st_halo_doubling(
    idx0: jax.Array, val0: jax.Array, mesh: Mesh, axis_names: Sequence[str]
) -> Tuple[jax.Array, jax.Array]:
    """BuildPlan "halo exchange" stage: the distributed doubling recurrence.

    Level k merges the previous row with itself shifted left by
    ``h = 2^(k-1)``: for a shard owning columns ``[s*C, (s+1)*C)`` the shifted
    operand is the contiguous window ``[s*C + h, s*C + h + C)`` of the
    previous row — exactly one shard-width, owned by shards ``s + h//C`` and
    ``s + h//C + 1``. Two ``_flat_shift`` transports fetch it, global columns
    past ``n_pad`` clamp to the previous row's last column (replicating the
    replicated build's tail rule), and the leftmost-tie pick finishes the
    level. (idx, val) pairs travel together so no level ever gathers from the
    full array: per-device memory is O(K * C), never O(K * n).

    Bit-identical to ``sparse_table.build`` on the same padded array. The
    compiled doubling program is cached per (mesh, axes, geometry) so
    repeated builds trace once.
    """
    axis_names = tuple(axis_names)
    num = num_shards(mesh, axis_names)
    n_pad = idx0.shape[0]
    return _st_halo_fn(mesh, axis_names, n_pad, num)(idx0, val0)


def build_sharded_st(x: jax.Array, mesh: Mesh, axis_names: Sequence[str]) -> ShardedSparseTable:
    """Distributed build of the column-sharded global doubling table.

    Lowers through the staged ``core.build`` pipeline (shard layout ->
    local build -> halo exchange -> finalize): per-shard doubling with a
    level-k halo exchange of the boundary columns, bit-identical to
    ``sparse_table.build`` on the padded array. Build-time memory per device
    is O(K * n / D) — the full (K, n) table is never materialized anywhere.
    """
    from . import build as build_mod  # deferred: build sequences these stages

    return build_mod.build("sharded_st", x, mesh=mesh, axis_names=axis_names)


def build_replicated_st(x: jax.Array, mesh: Mesh) -> SparseTable:
    """Full doubling table replicated on every device (batch-sharded mode)."""
    st = sparse_table.build(x)
    return jax.device_put(st, jax.sharding.NamedSharding(mesh, P()))


# --- incremental patch kernels (the online-update subsystem's SPMD side) ----
#
# ``repro.update`` mutates structures under live traffic. For the sharded
# engines the patch must run where the data lives: each device scatters the
# updates it owns, repairs only its touched blocks, and re-runs the doubling
# recurrence masked to the affected column windows — the same level-k window
# containment argument as the host-side ``repro.update.patch`` kernels, the
# same ``_flat_shift`` halo transport as the distributed build when a window
# straddles shard boundaries. SPMD masking means devices outside a window do
# (discarded) lane work rather than skipping it, but no new collective kinds
# are introduced and per-device memory stays bounded by the shard. Results
# are bit-identical to a from-scratch rebuild of the mutated array.


def _pad_updates(upd_pos, upd_val, val_dtype):
    """Pad (positions, values) to a power of two with ``pos = -1`` sentinels,
    so the compiled patch kernels see a bounded set of shapes."""
    upd_pos = np.asarray(upd_pos, np.int64)
    upd_val = np.asarray(upd_val)
    if upd_pos.size == 0:
        raise ValueError("patch called with no updates")
    p = 1 << (upd_pos.size - 1).bit_length() if upd_pos.size > 1 else 1
    pos = np.full(p, -1, np.int32)
    val = np.zeros(p, np.dtype(val_dtype))
    pos[: upd_pos.size] = upd_pos
    val[: upd_val.size] = upd_val
    return jnp.asarray(pos), jnp.asarray(val)


def _window_hull(upd_pos):
    """(lo, hi) hull of the valid (non-sentinel) update positions."""
    valid = upd_pos >= 0
    lo = jnp.min(jnp.where(valid, upd_pos, _INT_BIG))
    hi = jnp.max(jnp.where(valid, upd_pos, -1))
    return lo, hi


@functools.lru_cache(maxsize=None)
def _st_patch_fn(mesh: Mesh, axis_names: Tuple[str, ...], n_pad: int, num: int, p: int):
    shard_len = n_pad // num
    k_levels = st_levels(n_pad)

    def local(idx, val, upd_pos, upd_val):
        flat = _flat_axis_index(axis_names)
        c0 = flat * shard_len
        cols = jnp.arange(shard_len, dtype=jnp.int32)
        is_last = flat == num - 1
        mn, mx = _window_hull(upd_pos)
        # Scatter the owned updates into the level-0 value row (the level-0
        # index row is the identity and never changes); non-owned updates
        # fall off the end and are dropped.
        lp = upd_pos - c0
        owned = (upd_pos >= 0) & (lp >= 0) & (lp < shard_len)
        cur_v = val[0].at[jnp.where(owned, lp, shard_len)].set(
            upd_val.astype(val.dtype), mode="drop"
        )
        cur_i = idx[0]
        idx_rows, val_rows = [cur_i], [cur_v]
        for k in range(1, k_levels):
            h = 1 << (k - 1)
            if h >= n_pad:  # window spans the whole array: rows repeat
                idx_rows.append(cur_i)
                val_rows.append(cur_v)
                continue
            # Same transport as st_halo_doubling: the shifted operand is one
            # shard-width of the previous (patched) row, fetched from up to
            # two shards to the right, tail-clamped to its last column.
            d, r = divmod(h, shard_len)
            wi = _flat_shift(cur_i, mesh, axis_names, d)
            wv = _flat_shift(cur_v, mesh, axis_names, d)
            if r:
                bi = _flat_shift(cur_i, mesh, axis_names, d + 1)
                bv = _flat_shift(cur_v, mesh, axis_names, d + 1)
                wi = jnp.concatenate([wi[r:], bi[:r]])
                wv = jnp.concatenate([wv[r:], bv[:r]])
            g = c0 + h + cols
            last_i = jax.lax.pmax(jnp.where(is_last, cur_i[-1], -1), axis_names)
            last_v = jax.lax.psum(
                jnp.where(is_last, cur_v[-1], jnp.zeros_like(cur_v[-1])), axis_names
            )
            wi = jnp.where(g >= n_pad, last_i, wi)
            wv = jnp.where(g >= n_pad, last_v, wv)
            take = cur_v <= wv  # leftmost-tie: prefer the unshifted (left) row
            cand_i = jnp.where(take, cur_i, wi)
            cand_v = jnp.where(take, cur_v, wv)
            # Affected-column window at level k: an entry at column c covers
            # [c, c + 2^k), so only c in [mn - 2^k + 1, mx] can change.
            gc = c0 + cols
            in_win = (gc >= mn - ((1 << k) - 1)) & (gc <= mx)
            cur_i = jnp.where(in_win, cand_i, idx[k])
            cur_v = jnp.where(in_win, cand_v, val[k])
            idx_rows.append(cur_i)
            val_rows.append(cur_v)
        return jnp.stack(idx_rows), jnp.stack(val_rows)

    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(None, axis_names), P(None, axis_names), P(), P()),
            out_specs=(P(None, axis_names), P(None, axis_names)),
            check_vma=False,
        )
    )


def patch_sharded_st(
    t: ShardedSparseTable, upd_pos, upd_val, mesh: Mesh, axis_names: Sequence[str]
) -> ShardedSparseTable:
    """Patch the column-sharded doubling table in place of a rebuild.

    ``upd_pos``/``upd_val`` are the coalesced changed positions and values
    (host arrays; appends within the padded capacity are just updates at pad
    columns). Per level the doubling recurrence re-runs masked to the
    affected window, with the ``_flat_shift`` halo transport covering
    windows that straddle shard boundaries — bit-identical to
    ``build_sharded_st`` on the mutated array, with no device ever holding
    the full table.
    """
    axis_names = tuple(axis_names)
    num = num_shards(mesh, axis_names)
    n_pad = t.idx.shape[1]
    pos, val = _pad_updates(upd_pos, upd_val, t.val.dtype)
    idx, vals = _st_patch_fn(mesh, axis_names, n_pad, num, pos.shape[0])(
        t.idx, t.val, pos, val
    )
    return ShardedSparseTable(idx=idx, val=vals)


@functools.lru_cache(maxsize=None)
def _blocked_patch_fn(
    mesh: Mesh, axis_names: Tuple[str, ...], nb_local: int, bs: int, p: int
):
    local_n = nb_local * bs
    k_levels = st_levels(nb_local) if nb_local > 1 else 1

    def local(s: BlockRMQ, upd_pos, upd_val):
        flat = _flat_axis_index(axis_names)
        off = flat * local_n
        lp = upd_pos - off
        owned = (upd_pos >= 0) & (lp >= 0) & (lp < local_n)
        # Scatter owned values into the padded block matrix.
        xf = s.x_blocks.reshape(-1)
        xf = xf.at[jnp.where(owned, lp, local_n)].set(
            upd_val.astype(xf.dtype), mode="drop"
        )
        xb = xf.reshape(nb_local, bs)
        # O(bs) per-update block-min repair (duplicate updates to one block
        # recompute the same answer; drops discard the rest).
        blk = jnp.clip(lp // bs, 0, nb_local - 1)
        rows = jnp.take(xb, blk, axis=0)  # (P, bs)
        lidx = jnp.argmin(rows, axis=1).astype(jnp.int32)
        newmin = jnp.take_along_axis(rows, lidx[:, None], axis=1)[:, 0]
        tgt = jnp.where(owned, blk, nb_local)
        bmin_val = s.bmin_val.at[tgt].set(newmin, mode="drop")
        bmin_gidx = s.bmin_gidx.at[tgt].set(
            (blk * bs).astype(jnp.int32) + lidx, mode="drop"
        )
        # Masked windowed repair of the LOCAL doubling table over block
        # minima (per-shard tables never cross chunk boundaries, so there is
        # no transport here — just the same window containment as the host
        # patch kernels). Shards owning no update have an empty window and
        # keep every row.
        mnb = jnp.min(jnp.where(owned, blk, _INT_BIG))
        mxb = jnp.max(jnp.where(owned, blk, -1))
        cols = jnp.arange(nb_local, dtype=jnp.int32)
        cur = s.st.idx[0]
        rows_out = [cur]
        for k in range(1, k_levels):
            h = 1 << (k - 1)
            if h >= nb_local:
                rows_out.append(cur)
                continue
            shifted = jnp.concatenate([cur[h:], jnp.broadcast_to(cur[-1], (h,))])
            cand = jnp.where(bmin_val[cur] <= bmin_val[shifted], cur, shifted)
            in_win = (cols >= mnb - ((1 << k) - 1)) & (cols <= mxb)
            cur = jnp.where(in_win, cand, s.st.idx[k])
            rows_out.append(cur)
        st = SparseTable(idx=jnp.stack(rows_out), x=bmin_val)
        return BlockRMQ(x_blocks=xb, bmin_val=bmin_val, bmin_gidx=bmin_gidx, st=st)

    specs = _block_rmq_specs(P(axis_names), P(None, axis_names))
    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(specs, P(), P()),
            out_specs=specs,
            check_vma=False,
        )
    )


def patch_sharded(
    s: BlockRMQ, upd_pos, upd_val, mesh: Mesh, axis_names: Sequence[str]
) -> BlockRMQ:
    """Patch the mesh-sharded blocked structure in place of a rebuild.

    Each device scatters the updates it owns into its chunk, re-argmins only
    the touched blocks (O(bs) each), and window-patches its local block-min
    doubling table. Bit-identical to ``build_sharded`` on the mutated array.
    """
    axis_names = tuple(axis_names)
    num = num_shards(mesh, axis_names)
    bs = s.x_blocks.shape[1]
    nb_local = s.x_blocks.shape[0] // num
    pos, val = _pad_updates(upd_pos, upd_val, s.x_blocks.dtype)
    return _blocked_patch_fn(mesh, axis_names, nb_local, bs, pos.shape[0])(s, pos, val)


def make_st_query_fn(
    mesh: Mesh,
    axis_names: Sequence[str],
    *,
    batch_sharded: bool = False,
    batch_axes: Sequence[str] | None = None,
):
    """Jitted distributed sparse-table query -> (idx, val).

    ``batch_sharded=False``: takes a ``ShardedSparseTable`` (column-sharded
    global table), queries replicated. Each query needs two window lookups
    (columns ``l`` and ``r - 2^k + 1``); each column is owned by exactly one
    device, so non-owners contribute +inf/int-max and two pmins recover both
    candidates everywhere, then the standard leftmost-tie pick (prefer the
    left window on value ties) finishes the query.

    ``batch_sharded=True``: takes a replicated ``SparseTable``
    (``build_replicated_st``), the query batch is sharded, and each device
    answers its slice with the plain O(1) lookup plus a local value gather.

    ``batch_axes=...`` (2D mesh mode): the table stays column-sharded over
    ``axis_names``, the query batch is sharded over the disjoint
    ``batch_axes``, and the owner-column pmins run over the structure axes
    only — one structure-shard group answers each batch slice.
    """
    axis_names = tuple(axis_names)
    batch_axes = _check_batch_axes(axis_names, batch_axes, batch_sharded)

    if batch_sharded:
        num = num_shards(mesh, axis_names)

        def local_st(t: SparseTable, l, r):
            idx = sparse_table.query(t, l, r)
            return idx, t.x[idx]

        inner = shard_map(
            local_st,
            mesh=mesh,
            in_specs=(SparseTable(idx=P(), x=P()), P(axis_names), P(axis_names)),
            out_specs=(P(axis_names), P(axis_names)),
            check_vma=False,
        )

        def fn(t: SparseTable, l, r):
            lp, rp, b = _pad_batch(l, r, num)
            idx, val = inner(t, lp, rp)
            return idx[:b], val[:b]

        return jax.jit(fn)

    def local_query(t: ShardedSparseTable, l, r):
        cols = t.idx.shape[1]  # columns owned by this shard
        c0 = _flat_axis_index(axis_names) * cols
        big = maxval(t.val.dtype)
        l = l.astype(jnp.int32)
        r = r.astype(jnp.int32)
        k = sparse_table.exact_log2(r - l + 1)
        # The two covering windows start at columns l and r - 2^k + 1.
        cand = jnp.stack([l, r - jnp.left_shift(jnp.int32(1), k) + 1])  # (2, B)
        owned = (cand >= c0) & (cand < c0 + cols)
        cl = jnp.clip(cand - c0, 0, cols - 1)
        kk = jnp.broadcast_to(k[None, :], cand.shape)
        v = jnp.where(owned, t.val[kk, cl], big)
        i = jnp.where(owned, t.idx[kk, cl], _INT_BIG)
        # One owner per column: the pmins select the owner's candidate.
        v = jax.lax.pmin(v, axis_names)
        i = jax.lax.pmin(i, axis_names)
        take_left = v[0] <= v[1]  # left window on ties -> exact leftmost
        return jnp.where(take_left, i[0], i[1]), jnp.where(take_left, v[0], v[1])

    spec_b = P(batch_axes) if batch_axes else P()
    inner = shard_map(
        local_query,
        mesh=mesh,
        in_specs=(
            ShardedSparseTable(idx=P(None, axis_names), val=P(None, axis_names)),
            spec_b,
            spec_b,
        ),
        out_specs=(spec_b, spec_b),
        check_vma=False,
    )
    if not batch_axes:
        return jax.jit(inner)
    nb = num_shards(mesh, batch_axes)

    def fn(t: ShardedSparseTable, l, r):
        lp, rp, b = _pad_batch(l, r, nb)
        idx, val = inner(t, lp, rp)
        return idx[:b], val[:b]

    return jax.jit(fn)


# --- packed (single-word-plane) distributed tier ----------------------------
#
# Every structure above moves an (idx, val) PAIR through its halos, pmins,
# and patches. The packed tier (DESIGN.md §13) moves ONE plane of
# order-isomorphic words (``core.packing``): the two-pmin leftmost merge
# collapses to a single pmin, the level-k halo exchange ships half the
# bytes (packed32) or half the collectives (packed64), and the patch
# kernels repair one plane. Exact layouts only — the quantized layout's
# bucket-tie fallback needs value gathers that would cross shards, so
# planners reject it for mesh engines.


def pack_global(x: jax.Array, spec, n_pad: int) -> jax.Array:
    """Pack ``x`` with *global* indices and pad to ``n_pad`` with pad words.

    Packing precedes padding so pads are the reserved ``pad_word`` (always
    lose a min) rather than an encodable maxval element — this is also what
    keeps packed32's measured key-range fit independent of padding.
    """
    n = x.shape[0]
    xw = packing.pack(spec, x, jnp.arange(n, dtype=jnp.int32))
    return jnp.pad(xw, (0, n_pad - n), constant_values=packing.pad_word(spec))


def _pad_word_arr(spec):
    return jnp.asarray(packing.pad_word(spec), packing.word_dtype(spec))


@functools.lru_cache(maxsize=None)
def _sharded_build_packed_fn(mesh: Mesh, axis_names: Tuple[str, ...], block_size: int, spec):
    def local_build(w_local):
        wb = w_local[0].reshape(-1, block_size)
        return PackedBlockRMQ(
            blocks=wb, stw=block_rmq._doubling_min(jnp.min(wb, axis=1))
        )

    out_specs = PackedBlockRMQ(blocks=P(axis_names), stw=P(None, axis_names))
    return jax.jit(
        shard_map(
            local_build,
            mesh=mesh,
            in_specs=P(axis_names),
            out_specs=out_specs,
            check_vma=False,
        )
    )


def build_sharded_packed(
    x: jax.Array, mesh: Mesh, axis_names: Sequence[str], block_size: int, spec
) -> PackedBlockRMQ:
    """Per-shard packed blocked structures (one word plane per tier).

    Words carry global indices, so shard merges need no index offsetting —
    the min word across shards is already the global answer.
    """
    axis_names = tuple(axis_names)
    num = num_shards(mesh, axis_names)
    chunk = num * block_size
    n_pad = -(-x.shape[0] // chunk) * chunk
    xw = pack_global(x, spec, n_pad)
    return _sharded_build_packed_fn(mesh, axis_names, block_size, spec)(
        xw.reshape(num, -1)
    )


def build_replicated_packed(
    x: jax.Array, mesh: Mesh, block_size: int, spec
) -> PackedBlockRMQ:
    """Full packed blocked structure replicated on every device."""
    s, _ = block_rmq.build_packed(x, block_size, spec=spec)
    return jax.device_put(s, jax.sharding.NamedSharding(mesh, P()))


def make_packed_query_fn(
    mesh: Mesh,
    axis_names: Sequence[str],
    spec,
    *,
    batch_sharded: bool = False,
    batch_axes: Sequence[str] | None = None,
):
    """Jitted packed distributed query: (PackedBlockRMQ, l, r) -> (idx, val).

    Mirrors ``make_query_fn``'s three modes; the structure-sharded merge is
    ONE pmin over packed words instead of the two-pmin (value, then index)
    reduction — half the collectives, and exact leftmost ties by word order.
    """
    axis_names = tuple(axis_names)
    batch_axes = _check_batch_axes(axis_names, batch_axes, batch_sharded)
    pad = packing.pad_word(spec)

    if batch_sharded:
        num = num_shards(mesh, axis_names)

        def local_bs(s: PackedBlockRMQ, l, r):
            w = block_rmq.query_words(spec, s.blocks, s.stw, l, r)
            return packing.unpack_idx(spec, w), packing.unpack_val(spec, w)

        inner = shard_map(
            local_bs,
            mesh=mesh,
            in_specs=(
                PackedBlockRMQ(blocks=P(), stw=P()),
                P(axis_names),
                P(axis_names),
            ),
            out_specs=(P(axis_names), P(axis_names)),
            check_vma=False,
        )

        def fn(s: PackedBlockRMQ, l, r):
            lp, rp, b = _pad_batch(l, r, num)
            idx, val = inner(s, lp, rp)
            return idx[:b], val[:b]

        return jax.jit(fn)

    def local_query(s: PackedBlockRMQ, l, r):
        bs = s.blocks.shape[1]
        local_n = s.blocks.shape[0] * bs
        off = _flat_axis_index(axis_names) * local_n

        has = (r >= off) & (l <= off + local_n - 1)
        ql = jnp.clip(l - off, 0, local_n - 1)
        qr = jnp.clip(r - off, 0, local_n - 1)
        w = block_rmq.query_words(spec, s.blocks, s.stw, ql, qr)
        w = jnp.where(has, w, pad)
        # Exact leftmost merge with ONE min all-reduce over ICI.
        wmin = jax.lax.pmin(w, axis_names)
        return packing.unpack_idx(spec, wmin), packing.unpack_val(spec, wmin)

    spec_b = P(batch_axes) if batch_axes else P()
    inner = shard_map(
        local_query,
        mesh=mesh,
        in_specs=(
            PackedBlockRMQ(blocks=P(axis_names), stw=P(None, axis_names)),
            spec_b,
            spec_b,
        ),
        out_specs=(spec_b, spec_b),
        check_vma=False,
    )
    if not batch_axes:
        return jax.jit(inner)
    nb = num_shards(mesh, batch_axes)

    def fn(s: PackedBlockRMQ, l, r):
        lp, rp, b = _pad_batch(l, r, nb)
        idx, val = inner(s, lp, rp)
        return idx[:b], val[:b]

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _st_halo_packed_fn(mesh: Mesh, axis_names: Tuple[str, ...], n_pad: int, num: int, spec):
    shard_len = n_pad // num
    k_levels = st_levels(n_pad)
    pad = packing.pad_word(spec)

    def local(w):
        flat = _flat_axis_index(axis_names)
        cols = jnp.arange(shard_len, dtype=jnp.int32)
        is_last = flat == num - 1
        rows = [w]
        for k in range(1, k_levels):
            h = 1 << (k - 1)
            if h >= n_pad:
                rows.append(w)
                continue
            # Same transport as st_halo_doubling, HALF the planes: one
            # word array rides each _flat_shift instead of an (idx, val)
            # pair, and the tail clamp is one pmin broadcast (the last
            # shard's word beats every non-contributor's pad filler).
            d, r = divmod(h, shard_len)
            ww = _flat_shift(w, mesh, axis_names, d)
            if r:
                bw = _flat_shift(w, mesh, axis_names, d + 1)
                ww = jnp.concatenate([ww[r:], bw[:r]])
            g = flat * shard_len + h + cols
            last_w = jax.lax.pmin(
                jnp.where(is_last, w[-1], jnp.asarray(pad, w.dtype)), axis_names
            )
            ww = jnp.where(g >= n_pad, last_w, ww)
            w = jnp.minimum(w, ww)  # leftmost-tie is free: word order
            rows.append(w)
        return jnp.stack(rows)

    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=P(axis_names),
            out_specs=P(None, axis_names),
            check_vma=False,
        )
    )


def st_halo_doubling_packed(
    w0: jax.Array, mesh: Mesh, axis_names: Sequence[str], spec
) -> jax.Array:
    """Packed distributed doubling: the halo recurrence on ONE word plane.

    ``w0`` is the shard-divisible packed level-0 row (``pack_global``).
    Bit-identical (after unpacking) to ``st_halo_doubling`` on the same
    data — the leftmost-tie pick is subsumed by word ``minimum``.
    """
    axis_names = tuple(axis_names)
    num = num_shards(mesh, axis_names)
    return _st_halo_packed_fn(mesh, axis_names, w0.shape[0], num, spec)(w0)


def build_sharded_st_packed(
    x: jax.Array, mesh: Mesh, axis_names: Sequence[str], spec
) -> PackedSparseTable:
    """Distributed build of the column-sharded packed doubling table."""
    axis_names = tuple(axis_names)
    num = num_shards(mesh, axis_names)
    n_pad = -(-max(x.shape[0], 1) // num) * num
    return PackedSparseTable(
        words=st_halo_doubling_packed(pack_global(x, spec, n_pad), mesh, axis_names, spec)
    )


def build_replicated_st_packed(x: jax.Array, mesh: Mesh, spec) -> PackedSparseTable:
    """Full packed doubling table replicated on every device."""
    t, _ = sparse_table.build_packed(x, spec=spec)
    return jax.device_put(t, jax.sharding.NamedSharding(mesh, P()))


def make_packed_st_query_fn(
    mesh: Mesh,
    axis_names: Sequence[str],
    spec,
    *,
    batch_sharded: bool = False,
    batch_axes: Sequence[str] | None = None,
):
    """Jitted packed distributed sparse-table query -> (idx, val).

    The owner-column merge is one pmin over a (2, B) word stack, and the
    left/right window pick is a plain word ``minimum`` — no value/index
    plane pair, no tie select.
    """
    axis_names = tuple(axis_names)
    batch_axes = _check_batch_axes(axis_names, batch_axes, batch_sharded)
    pad = packing.pad_word(spec)

    if batch_sharded:
        num = num_shards(mesh, axis_names)

        def local_st(t: PackedSparseTable, l, r):
            return sparse_table.query_packed(t, spec, l, r)

        inner = shard_map(
            local_st,
            mesh=mesh,
            in_specs=(
                PackedSparseTable(words=P(), x=None),
                P(axis_names),
                P(axis_names),
            ),
            out_specs=(P(axis_names), P(axis_names)),
            check_vma=False,
        )

        def fn(t: PackedSparseTable, l, r):
            lp, rp, b = _pad_batch(l, r, num)
            idx, val = inner(t, lp, rp)
            return idx[:b], val[:b]

        return jax.jit(fn)

    def local_query(t: PackedSparseTable, l, r):
        cols = t.words.shape[1]
        c0 = _flat_axis_index(axis_names) * cols
        l = l.astype(jnp.int32)
        r = r.astype(jnp.int32)
        k = sparse_table.exact_log2(r - l + 1)
        cand = jnp.stack([l, r - jnp.left_shift(jnp.int32(1), k) + 1])  # (2, B)
        owned = (cand >= c0) & (cand < c0 + cols)
        cl = jnp.clip(cand - c0, 0, cols - 1)
        kk = jnp.broadcast_to(k[None, :], cand.shape)
        w = jnp.where(owned, t.words[kk, cl], jnp.asarray(pad, t.words.dtype))
        w = jax.lax.pmin(w, axis_names)  # one collective, was two
        wm = jnp.minimum(w[0], w[1])  # leftmost-tie by word order
        return packing.unpack_idx(spec, wm), packing.unpack_val(spec, wm)

    spec_b = P(batch_axes) if batch_axes else P()
    inner = shard_map(
        local_query,
        mesh=mesh,
        in_specs=(
            PackedSparseTable(words=P(None, axis_names), x=None),
            spec_b,
            spec_b,
        ),
        out_specs=(spec_b, spec_b),
        check_vma=False,
    )
    if not batch_axes:
        return jax.jit(inner)
    nb = num_shards(mesh, batch_axes)

    def fn(t: PackedSparseTable, l, r):
        lp, rp, b = _pad_batch(l, r, nb)
        idx, val = inner(t, lp, rp)
        return idx[:b], val[:b]

    return jax.jit(fn)


def _pad_updates_packed(upd_pos, upd_val, spec):
    """Pad (positions, packed update words) to a power of two.

    Packs host-side — a packed32 spec that cannot encode a new value raises
    ``OverflowError`` here, *before* any device state mutates, so callers
    can fall back to a structural rebuild with a fresh spec.
    """
    upd_pos = np.asarray(upd_pos, np.int64)
    if upd_pos.size == 0:
        raise ValueError("patch called with no updates")
    words = packing.pack_np(spec, upd_val, upd_pos.astype(np.int32))
    p = 1 << (upd_pos.size - 1).bit_length() if upd_pos.size > 1 else 1
    pos = np.full(p, -1, np.int32)
    wrd = np.full(p, packing.pad_word(spec), packing.word_dtype_np(spec))
    pos[: upd_pos.size] = upd_pos
    wrd[: words.size] = words
    return jnp.asarray(pos), jnp.asarray(wrd)


@functools.lru_cache(maxsize=None)
def _st_patch_packed_fn(mesh: Mesh, axis_names: Tuple[str, ...], n_pad: int, num: int, p: int, spec):
    shard_len = n_pad // num
    k_levels = st_levels(n_pad)
    pad = packing.pad_word(spec)

    def local(words, upd_pos, upd_w):
        flat = _flat_axis_index(axis_names)
        c0 = flat * shard_len
        cols = jnp.arange(shard_len, dtype=jnp.int32)
        is_last = flat == num - 1
        mn, mx = _window_hull(upd_pos)
        lp = upd_pos - c0
        owned = (upd_pos >= 0) & (lp >= 0) & (lp < shard_len)
        cur = words[0].at[jnp.where(owned, lp, shard_len)].set(
            upd_w.astype(words.dtype), mode="drop"
        )
        rows = [cur]
        for k in range(1, k_levels):
            h = 1 << (k - 1)
            if h >= n_pad:
                rows.append(cur)
                continue
            d, r = divmod(h, shard_len)
            ww = _flat_shift(cur, mesh, axis_names, d)
            if r:
                bw = _flat_shift(cur, mesh, axis_names, d + 1)
                ww = jnp.concatenate([ww[r:], bw[:r]])
            g = c0 + h + cols
            last_w = jax.lax.pmin(
                jnp.where(is_last, cur[-1], jnp.asarray(pad, cur.dtype)), axis_names
            )
            ww = jnp.where(g >= n_pad, last_w, ww)
            cand = jnp.minimum(cur, ww)
            # Level-k containment: an entry at column c covers [c, c + 2^k).
            gc = c0 + cols
            in_win = (gc >= mn - ((1 << k) - 1)) & (gc <= mx)
            cur = jnp.where(in_win, cand, words[k])
            rows.append(cur)
        return jnp.stack(rows)

    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(None, axis_names), P(), P()),
            out_specs=P(None, axis_names),
            check_vma=False,
        )
    )


def patch_sharded_st_packed(
    t: PackedSparseTable, upd_pos, upd_val, mesh: Mesh, axis_names: Sequence[str], spec
) -> PackedSparseTable:
    """Windowed patch of the column-sharded packed doubling table.

    One plane rides the halo transport (the unpacked patch ships two);
    bit-identical to ``build_sharded_st_packed`` on the mutated array.
    Raises ``OverflowError`` before touching device state when a packed32
    spec cannot encode a new value.
    """
    axis_names = tuple(axis_names)
    num = num_shards(mesh, axis_names)
    n_pad = t.words.shape[1]
    pos, wrd = _pad_updates_packed(upd_pos, upd_val, spec)
    words = _st_patch_packed_fn(mesh, axis_names, n_pad, num, pos.shape[0], spec)(
        t.words, pos, wrd
    )
    return PackedSparseTable(words=words)


@functools.lru_cache(maxsize=None)
def _blocked_patch_packed_fn(
    mesh: Mesh, axis_names: Tuple[str, ...], nb_local: int, bs: int, p: int, spec
):
    local_n = nb_local * bs
    k_levels = st_levels(nb_local) if nb_local > 1 else 1

    def local(s: PackedBlockRMQ, upd_pos, upd_w):
        flat = _flat_axis_index(axis_names)
        off = flat * local_n
        lp = upd_pos - off
        owned = (upd_pos >= 0) & (lp >= 0) & (lp < local_n)
        wf = s.blocks.reshape(-1)
        wf = wf.at[jnp.where(owned, lp, local_n)].set(
            upd_w.astype(wf.dtype), mode="drop"
        )
        wb = wf.reshape(nb_local, bs)
        blk = jnp.clip(lp // bs, 0, nb_local - 1)
        neww = jnp.min(jnp.take(wb, blk, axis=0), axis=1)  # O(bs) block repair
        tgt = jnp.where(owned, blk, nb_local)
        cur = s.stw[0].at[tgt].set(neww, mode="drop")
        mnb = jnp.min(jnp.where(owned, blk, _INT_BIG))
        mxb = jnp.max(jnp.where(owned, blk, -1))
        cols = jnp.arange(nb_local, dtype=jnp.int32)
        rows_out = [cur]
        for k in range(1, k_levels):
            h = 1 << (k - 1)
            if h >= nb_local:
                rows_out.append(cur)
                continue
            shifted = jnp.concatenate([cur[h:], jnp.broadcast_to(cur[-1], (h,))])
            cand = jnp.minimum(cur, shifted)
            in_win = (cols >= mnb - ((1 << k) - 1)) & (cols <= mxb)
            cur = jnp.where(in_win, cand, s.stw[k])
            rows_out.append(cur)
        return PackedBlockRMQ(blocks=wb, stw=jnp.stack(rows_out))

    specs = PackedBlockRMQ(blocks=P(axis_names), stw=P(None, axis_names))
    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(specs, P(), P()),
            out_specs=specs,
            check_vma=False,
        )
    )


def patch_sharded_packed(
    s: PackedBlockRMQ, upd_pos, upd_val, mesh: Mesh, axis_names: Sequence[str], spec
) -> PackedBlockRMQ:
    """Windowed patch of the mesh-sharded packed blocked structure.

    Scatter owned word updates, re-min touched blocks, window-repair the
    per-shard doubling plane — all on single word planes. Bit-identical to
    ``build_sharded_packed`` on the mutated array.
    """
    axis_names = tuple(axis_names)
    num = num_shards(mesh, axis_names)
    bs = s.blocks.shape[1]
    nb_local = s.blocks.shape[0] // num
    pos, wrd = _pad_updates_packed(upd_pos, upd_val, spec)
    return _blocked_patch_packed_fn(mesh, axis_names, nb_local, bs, pos.shape[0], spec)(
        s, pos, wrd
    )

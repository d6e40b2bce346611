"""Sparse-table (doubling) RMQ: O(n log n) build, O(1) batched query.

This is the level-2 structure of the blocked RMQ (DESIGN.md §2, Insight B):
RTXRMQ answers the fully-covered-blocks sub-query with a second RT geometry
over block minima; on TPU the natural O(1) analogue is the classic doubling
table — two gathers and a select per query, fully vectorized over the batch.

The table stores *indices* (int32), so queries answer argmin directly and the
leftmost-tie convention is preserved exactly (see ``_pick_left``).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import packing

__all__ = [
    "PackedSparseTable",
    "SparseTable",
    "build",
    "build_packed",
    "query",
    "query_packed",
]


class SparseTable(NamedTuple):
    """Doubling table over ``x``. ``idx[k, i]`` = leftmost argmin of x[i : i+2^k]."""

    idx: jax.Array  # (K, n) int32
    x: jax.Array  # (n,) values the table indexes into


def _pick_left(x, a, b):
    """Leftmost-tie argmin merge: prefer ``a`` when values tie.

    Correct whenever, on ties, position ``a`` is guaranteed to be <= the
    leftmost min (holds for both the build windows and the query overlap —
    see the window-containment argument in DESIGN.md §2 note 4).
    """
    return jnp.where(x[a] <= x[b], a, b)


@jax.jit
def build(x: jax.Array) -> SparseTable:
    """Build the doubling table: one jitted loop over the K levels.

    Each level carries its window minima beside their indices, so a level is
    a shift and a select — the same ``x[a] <= x[b]`` pick as ``_pick_left``
    without gathering ``x`` — and writes its row into the preallocated table
    in place. Peak memory is the table plus a few length-n rows, which is
    what lets a TPU build the largest table its HBM holds.
    """
    n = x.shape[0]
    k_levels = max(1, (n - 1).bit_length() + 1) if n > 1 else 1
    cur = jnp.arange(n, dtype=jnp.int32)
    table = jnp.zeros((k_levels, n), jnp.int32).at[0].set(cur)

    def shift(a, h):  # a[i + h], clamped to a[-1] past the end
        ext = jnp.concatenate([a, jnp.broadcast_to(a[-1], (n,))])
        return jax.lax.dynamic_slice(ext, (h,), (n,))

    def level(k, carry):
        table, cur, val = carry  # val == x[cur]
        h = jnp.left_shift(jnp.int32(1), k - 1)  # < n for every level built
        sv = shift(val, h)
        take = val <= sv  # leftmost tie: prefer the left window
        cur = jnp.where(take, cur, shift(cur, h))
        val = jnp.where(take, val, sv)
        return table.at[k].set(cur), cur, val

    table, _, _ = jax.lax.fori_loop(1, k_levels, level, (table, cur, x))
    return SparseTable(idx=table, x=x)


def exact_log2(length: jax.Array) -> jax.Array:
    """floor(log2(length)) computed exactly for int32 length >= 1.

    float log2 alone can be off-by-one at powers of two; correct it with
    integer shifts so 2^k <= length < 2^(k+1) always holds.
    """
    k = jnp.floor(jnp.log2(length.astype(jnp.float32))).astype(jnp.int32)
    k = jnp.maximum(k, 0)
    k = jnp.where(jnp.left_shift(jnp.int32(1), k) > length, k - 1, k)
    k = jnp.where(jnp.left_shift(jnp.int32(1), k + 1) <= length, k + 1, k)
    return k


def query(table: SparseTable, l: jax.Array, r: jax.Array) -> jax.Array:
    """Batched O(1) query. Returns leftmost argmin indices (int32)."""
    l = l.astype(jnp.int32)
    r = r.astype(jnp.int32)
    length = r - l + 1
    k = exact_log2(length)
    a = table.idx[k, l]
    b = table.idx[k, r - jnp.left_shift(jnp.int32(1), k) + 1]
    return _pick_left(table.x, a, b)


# --- packed variant ---------------------------------------------------------
#
# One word plane instead of idx + x: a query touches two table cells and is
# done — no value gathers, no select chain (DESIGN.md §13). For the
# quantized layout the word carries (bucket, exact-argmin-index); bucket
# ties fall back to an exact value compare against the retained ``x``.


class PackedSparseTable(NamedTuple):
    """Doubling table of packed words.

    ``words[k, i]`` encodes the leftmost argmin of ``x[i : i+2^k]`` as one
    ``(key << idx_bits) | index`` word (``core.packing``). ``x`` is kept
    only for the quantized layout's exact bucket-tie fallback (None for
    packed64/packed32 — exact decode needs no raw plane).
    """

    words: jax.Array  # (K, n) packed words
    x: Optional[jax.Array] = None  # (n,) raw values, quantized layouts only


def build_packed(x: jax.Array, spec=None, layout: str = "auto"):
    """Build the packed doubling table; returns ``(PackedSparseTable, spec)``.

    Exact layouts fold the doubling merge into ``jnp.minimum`` over words.
    The quantized layout first builds the exact index table (bucket codes
    cannot resolve in-bucket ties during construction) and then encodes
    each cell's exact argmin with its bucket.
    """
    n = x.shape[0]
    if spec is None:
        spec = packing.spec_for(x, n, layout)
    if spec.layout == "quantized":
        t = build(x)
        words = packing.pack(spec, x[t.idx], t.idx)
        return PackedSparseTable(words=words, x=x), spec
    k_levels = max(1, (n - 1).bit_length() + 1) if n > 1 else 1
    cur = packing.pack(spec, x, jnp.arange(n, dtype=jnp.int32))
    rows = [cur]
    for k in range(1, k_levels):
        h = 1 << (k - 1)
        if h >= n:
            rows.append(cur)
            continue
        shifted = jnp.concatenate([cur[h:], jnp.broadcast_to(cur[-1], (h,))])
        cur = jnp.minimum(cur, shifted)
        rows.append(cur)
    return PackedSparseTable(words=jnp.stack(rows)), spec


@partial(jax.jit, static_argnums=0)
def _query_packed_jit(spec, words, x, l, r):
    length = r - l + 1
    k = exact_log2(length)
    wa = words[k, l]
    wb = words[k, r - jnp.left_shift(jnp.int32(1), k) + 1]
    if spec.layout != "quantized":
        w = jnp.minimum(wa, wb)
        return packing.unpack_idx(spec, w), packing.unpack_val(spec, w)
    # Bucket-tie fallback: equal buckets gather both exact values; the
    # leftmost-tie argument of _pick_left carries over (window containment
    # gives ia <= ib on exact value ties).
    ia = packing.unpack_idx(spec, wa)
    ib = packing.unpack_idx(spec, wb)
    va = x[ia]
    vb = x[ib]
    collide = (wa >> spec.idx_bits) == (wb >> spec.idx_bits)
    take_a = jnp.where(collide, va <= vb, wa <= wb)
    return jnp.where(take_a, ia, ib), jnp.where(take_a, va, vb)


def query_packed(table: PackedSparseTable, spec, l: jax.Array, r: jax.Array):
    """Batched O(1) packed query -> ``(idx int32, val)``, exact leftmost ties."""
    return _query_packed_jit(
        spec, table.words, table.x, l.astype(jnp.int32), r.astype(jnp.int32)
    )

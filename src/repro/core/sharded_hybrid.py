"""Sharded range-adaptive hybrid RMQ: the crossover, distributed.

The paper's two deferred directions meet here. §7.i leaves multi-BVH
distribution ("one BVH per cluster of blocks") as future work — that is
``core.distributed``'s mesh-sharded blocked engine. §6 shows the headline
result is regime-dependent — the blocked structure wins at small ranges, the
O(1) table family at large ones — which ``core.hybrid`` exploits on one
host. This engine fuses them: a sharded deployment that still routes every
query to the regime-appropriate structure.

Data flow (DESIGN.md §6):

    host batch (l, r)
      └─ partition by range length vs threshold        (numpy, host-side)
           ├─ short sub-batch -> sharded blocked path  (two-pmin merge)
           └─ long sub-batch  -> sharded sparse-table  (owner-column pmin)
      └─ exact leftmost scatter-back into batch order

Three distribution modes, one per scaling axis (plus the product):

* ``mode="shard_structure"`` (default): the *array* is sharded — per-device
  blocked chunks for the short path, a column-sharded global doubling table
  for the long path. Memory scales with device count; queries are replicated
  and merge via pmin collectives.
* ``mode="shard_batch"``: the *query batch* is sharded — each device holds
  the full (replicated) structures and answers only its slice, so serving
  throughput scales with device count instead of being replicated work.
* ``mode="shard_2d"``: both — the structure is sharded over the FIRST mesh
  axis and the query batch over the remaining axes, so memory scales with
  the structure axis and throughput with the batch axes. Each batch slice
  is answered by one structure-shard group (pmins over the structure axis
  only). On a 1-axis mesh it degrades to ``shard_structure``.

Builds lower through the staged ``core.build`` BuildPlan pipeline
(shard layout -> local build -> halo exchange -> finalize); the long-path
doubling table is built *distributed* (per-shard doubling + level-k halo
exchange), so build-time memory per device is bounded by the shard.

The routing threshold (``build(threshold=...)``): ``None`` is the
deterministic sqrt(n) default, exactly as in ``hybrid.build``; ``"cached"``
consults the persistent calibration cache (``calib_cache``, keyed by
``(n, block_size, backend, n_devices)``) and falls back to sqrt(n) on a
miss without ever measuring; ``"calibrated"`` measures on a miss and
persists the result; an int pins it explicitly. Machine state is opt-in —
default builds (registry, tests, benchmarks) never read the cache.

Results are bit-identical to ``block_rmq.query`` on the same batch — every
constituent is exact-leftmost, and the scatter-back preserves batch order.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

import jax
import numpy as np

from .hybrid import dispatch_by_length

__all__ = ["MODES", "ShardedHybridRMQ", "build", "query"]

MODES = ("shard_structure", "shard_batch", "shard_2d")


class ShardedHybridRMQ(NamedTuple):
    """Both distributed constituents plus routing/launch metadata."""

    blocked: object  # sharded (or replicated) BlockRMQ — short-range path
    st: object  # ShardedSparseTable (or replicated SparseTable) — long path
    n: int  # logical array length (pre-padding)
    threshold: int  # range lengths <= threshold go to the blocked path
    mode: str  # "shard_structure" | "shard_batch" | "shard_2d"
    n_shards: int  # flattened mesh size (batch-pad granularity)
    dtype: object  # value dtype for the host-side scatter-back
    short_fn: object  # jitted (blocked, l, r) -> (idx, val)
    long_fn: object  # jitted (st, l, r) -> (idx, val)


def build(
    x: jax.Array,
    mesh=None,
    axis_names: Sequence[str] | None = None,
    block_size: int = 128,
    *,
    threshold: int | str | None = None,
    mode: str = "shard_structure",
    cache_path=None,
    packed=None,
) -> ShardedHybridRMQ:
    """Build both distributed constituents over ``mesh`` (default: all devices).

    Lowers through the staged ``core.build`` BuildPlan pipeline.

    ``threshold``: int pins the crossover; ``None`` is the deterministic
    sqrt(n) default (no cache, matching ``hybrid.build``); ``"cached"``
    reads the calibration cache with the sqrt(n) fallback, never measuring;
    ``"calibrated"`` measures on a cache miss — timing the *sharded*
    constituents on this very mesh and mode — and persists the result under
    the existing ``(n, bs, backend, ndev)`` key.
    """
    from . import build as build_mod  # deferred: build.py hosts the planner

    return build_mod.build(
        "sharded_hybrid",
        x,
        mesh=mesh,
        axis_names=axis_names,
        block_size=block_size,
        threshold=threshold,
        mode=mode,
        cache_path=cache_path,
        packed=packed,
    )


def query(s: ShardedHybridRMQ, l, r) -> Tuple[jax.Array | np.ndarray, jax.Array | np.ndarray]:
    """Range-adaptive distributed batched RMQ -> (leftmost idx int32, value).

    Host-side partition by range length, per-regime *sharded* launches,
    ordered scatter-back — ``hybrid.dispatch_by_length`` with the sharded
    constituents closed over their states. (The batch-sharded query fns pad
    to a shard multiple internally, so divisibility is not this layer's
    concern.) Bit-identical to ``block_rmq.query``. A uniform batch's
    answers are device arrays, a mixed batch's host ``np.ndarray``s merged
    on the host.
    """
    return dispatch_by_length(
        l,
        r,
        s.threshold,
        lambda lm, rm: s.short_fn(s.blocked, lm, rm),
        lambda lm, rm: s.long_fn(s.st, lm, rm),
        s.dtype,
    )

"""Paper Table 2: memory of each approach's data structure (MB) — plus the
``build_mem`` sweep: *peak per-device build memory* of the doubling-table
family across device counts.

Reproduced claim ordering: geometric/blocked structure uses the most memory
(the paper's BVH is ~9n+ the input; our blocked structure is ~(1+1/BS)n +
tables), LCA/Euler is mid, the O(1)-table structures trade memory for time.

``build_mem`` (``run_build_mem``) compares, per fake-device count:

* ``replicated`` — ``build_replicated_st``: every device holds the full
  (K, n) table (batch-sharded mode's structure);
* ``sharded_steady`` — the column-sharded ``ShardedSparseTable`` steady
  state: (K, n/D) idx+val per device;
* ``distributed_build_peak`` — the max per-device bytes live at ANY stage of
  the staged BuildPlan build (observer over shard layout -> local build ->
  halo exchange), demonstrating the build transient is bounded by the shard
  too — the old single-device materialization would show up here as a full
  (K, n) spike.

Per device count through ``common.per_device_count`` (virtual CPU devices
in a child process, or the chips this process holds).
"""

from __future__ import annotations

from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import block_rmq, lane_rmq, lca, sparse_table

from . import common
from .common import emit

SIZES = [1 << 10, 1 << 15, 1 << 20]


def tree_mb(tree) -> float:
    return sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(tree)) / 2**20


def run():
    rng = np.random.default_rng(2)
    sizes = SIZES[:2] if common.SMOKE else SIZES
    for n in sizes:
        x = rng.random(n, dtype=np.float32)
        xj = jnp.asarray(x)
        input_mb = n * 4 / 2**20
        rows = {
            "RTXRMQ": tree_mb(block_rmq.build(xj, 128)),
            "LANE": tree_mb(lane_rmq.build(xj)),
            "LCA": tree_mb(lca.build(x)),
            "SPARSE_TABLE": tree_mb(sparse_table.build(xj)),
        }
        for name, mb in rows.items():
            emit(f"table2/{name}/n={n}", 0.0, f"{mb:.3f}MB_vs_input_{input_mb:.3f}MB")


def _max_device_bytes(tree) -> int:
    by_dev = defaultdict(int)
    seen = set()  # the finalize stage aliases arrays (state -> result):
    for arr in jax.tree_util.tree_leaves(tree):  # count each buffer once
        if isinstance(arr, jax.Array) and id(arr) not in seen:
            seen.add(id(arr))
            for sh in arr.addressable_shards:
                by_dev[sh.device] += sh.data.nbytes
    return max(by_dev.values()) if by_dev else 0


def measure_build_mem(devices, n: int):
    """Max per-device bytes: [(kind, bytes)] for the doubling-table builds."""
    from repro.core import build as build_mod
    from repro.core import distributed
    from repro.launch.mesh import make_group_mesh

    mesh = make_group_mesh(devices)
    x = jnp.asarray(np.random.default_rng(0).random(n, dtype=np.float32))
    rep = distributed.build_replicated_st(x, mesh)
    jax.block_until_ready(rep)
    rows = [("replicated", _max_device_bytes(rep))]
    del rep

    peak = 0

    def observe(stage, state):
        nonlocal peak
        live = [v for k, v in state.items() if k != "x"]
        jax.block_until_ready(live)
        peak = max(peak, _max_device_bytes(live))

    sharded = build_mod.build(
        "sharded_st", x, mesh=mesh, axis_names=("shard",), observer=observe
    )
    rows.append(("distributed_build_peak", peak))
    rows.append(("sharded_steady", _max_device_bytes(sharded)))
    return rows


def run_build_mem():
    devices = [1, 2] if common.SMOKE else [1, 2, 4, 8]
    n = 1 << 16 if common.SMOKE else 1 << 20
    for n_dev, rows in common.per_device_count(
        "benchmarks.memory_usage:measure_build_mem", devices, n=n
    ):
        for kind, nbytes in rows:
            emit(
                f"build_mem/ndev={n_dev}/{kind}/n={n}",
                0.0,
                f"{int(nbytes) / 2**20:.3f}MB_per_device_peak",
            )


if __name__ == "__main__":
    run()
    run_build_mem()

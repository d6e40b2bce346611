"""Sharded range-adaptive hybrid sweep: devices x range distribution.

Extends fig14's shard-scaling story to the fused engine (core/sharded_hybrid):
for each fake-device count and each §6.4 range distribution, serve a batch
through the range-adaptive sharded engine and report ns/RMQ. The small/large
regimes exercise the single-constituent fast paths (sharded blocked / sharded
sparse table); medium mixes regimes and exercises the partition+scatter-back.
One batch-sharded-mode row per device count shows the replicated-structure /
sharded-queries dual; one 2D-mode row (structure x batch mesh, squarest
factoring) shows the product.

One process per device count on the CPU backend (XLA fixes the virtual
device count at first jax import); on a chip, one process over the devices
it holds (``common.per_device_count``).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from . import common
from .common import emit, make_queries

_BATCH = 8192


def measure(devices, n: int, batch: int):
    """Seconds per batch: [(mode, dist, seconds)] over a mesh of ``devices``."""
    from repro.core import sharded_hybrid
    from repro.launch.mesh import factor_2d

    devs = np.asarray(devices, dtype=object)
    mesh = Mesh(devs, ("shard",))
    mesh2d = Mesh(devs.reshape(factor_2d(len(devices))), ("struct", "qbatch"))
    rng = np.random.default_rng(0)
    x = rng.random(n, dtype=np.float32)
    rows = []
    for mode in ("shard_structure", "shard_batch", "shard_2d"):
        m, axes = (mesh2d, ("struct", "qbatch")) if mode == "shard_2d" else (mesh, ("shard",))
        s = sharded_hybrid.build(jnp.asarray(x), m, axes, 1024, mode=mode)
        dists = ("small", "medium", "large") if mode == "shard_structure" else ("medium",)
        for dist in dists:
            l, r = make_queries(rng, n, batch, dist)
            out = sharded_hybrid.query(s, l, r)  # warmup / compile
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(5):
                out = sharded_hybrid.query(s, l, r)
            jax.block_until_ready(out)
            rows.append((mode, dist, (time.perf_counter() - t0) / 5))
    return rows


def run():
    devices = [1, 2] if common.SMOKE else [1, 2, 4, 8]
    n = 1 << 16 if common.SMOKE else 1 << 20
    batch = 2048 if common.SMOKE else _BATCH
    for n_dev, rows in common.per_device_count(
        "benchmarks.sharded_hybrid:measure", devices, n=n, batch=batch
    ):
        for mode, dist, t in rows:
            tag = {"shard_batch": "qshard/", "shard_2d": "2d/"}.get(mode, "")
            emit(
                f"sharded_hybrid/shards={n_dev}/{tag}dist={dist}",
                t / batch,
                f"{t/batch*1e9:.1f}ns_per_rmq",
            )


if __name__ == "__main__":
    run()

"""Replaces paper Fig. 14/15 (GPU-generation / SM scaling, not measurable in
this container): scaling of the DISTRIBUTED RMQ engine with shard count,
measured per device count (``common.per_device_count``: virtual CPU devices
in a child process, or the chips this process holds).

Reproduced claim analogue: the blocked engine's throughput scales with
parallel resources (paper: RT cores/SMs; here: mesh shards), because the
query batch is embarrassingly parallel up to the two min all-reduces.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from . import common
from .common import emit, make_queries

_BATCH = 8192


def measure(devices, n: int) -> float:
    """Seconds per small-range batch of the distributed engine on ``devices``."""
    from repro.core import distributed
    from repro.launch.mesh import make_group_mesh, set_mesh

    mesh = make_group_mesh(devices)
    rng = np.random.default_rng(0)
    x = rng.random(n, dtype=np.float32)
    with set_mesh(mesh):
        s = distributed.build_sharded(jnp.asarray(x), mesh, ("shard",), 1024)
        qfn = distributed.make_query_fn(mesh, ("shard",))
        l, r = make_queries(rng, n, _BATCH, "small")
        lj, rj = jnp.asarray(l), jnp.asarray(r)
        out = qfn(s, lj, rj)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(5):
            out = qfn(s, lj, rj)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / 5


def run():
    devices = [1, 2] if common.SMOKE else [1, 2, 4, 8]
    n = 1 << 16 if common.SMOKE else 1 << 20
    for n_dev, t in common.per_device_count("benchmarks.mesh_scaling:measure", devices, n=n):
        emit(
            f"fig14/distributed-rmq/shards={n_dev}",
            t / _BATCH,
            f"{t/_BATCH*1e9:.1f}ns_per_rmq",
        )


if __name__ == "__main__":
    run()

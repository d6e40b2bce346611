"""Crossover suite: range-length regimes x engines + dispatch-count audit.

Reproduces the paper's central perf claim — the winner is *regime-dependent*
(blocked/RT-style fastest at small ranges, O(1) tables at large) — and
measures the two things this repo's fused/hybrid work adds on top:

  1. **Dispatch audit**: the fused tiled megakernel answers a whole query
     batch in ONE ``pallas_call`` with zero XLA gathers/selects after it,
     vs the legacy path's kernel + sparse-table interior + merge passes.
     Counted statically from the jaxpr, so it holds on CPU (interpret mode)
     exactly as on TPU.
  2. **Hybrid dominance**: across small/medium/large regimes the hybrid
     dispatcher must never be slower than the worst of its two constituent
     engines (it routes each query to the better one; a FAIL in the derived
     column means the routing threshold is mis-calibrated).

Off-TPU, Pallas kernels run as Python emulation — their wall-clock is
meaningless, so kernel-path rows emit the dispatch audit instead of time.
CSV rows follow the ``name,us_per_call,derived`` convention.
"""

from __future__ import annotations

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np

from repro import kernels
from repro.core import block_rmq, hybrid, lane_rmq, sparse_table

from . import common
from .common import emit, make_queries, time_fn

N = 1 << 16
BATCH = 1 << 13
DISTS = ["small", "medium", "large"]


def _jaxpr_audit(fn, *args):
    """(pallas_calls, xla_gathers, xla_selects) outside kernel bodies."""
    closed = jax.make_jaxpr(fn)(*args)

    def walk(jaxpr):
        pallas = gathers = selects = 0
        for eq in jaxpr.eqns:
            name = eq.primitive.name
            if name == "pallas_call":
                pallas += 1
                continue  # do not descend into the kernel body
            if name == "gather":
                gathers += 1
            if name == "select_n":
                selects += 1
            for v in eq.params.values():
                sub = None
                if isinstance(v, jex_core.ClosedJaxpr):
                    sub = v.jaxpr
                elif isinstance(v, jex_core.Jaxpr):
                    sub = v
                if sub is not None:
                    p, g, s = walk(sub)
                    pallas += p
                    gathers += g
                    selects += s
        return pallas, gathers, selects

    return walk(closed.jaxpr)


def run():
    rng = np.random.default_rng(0)
    # Smoke shrinks the array, not the batch: the dispatcher's fixed per-call
    # cost must stay amortized or per-query numbers measure dispatch latency.
    n, batch = (1 << 12, BATCH) if common.SMOKE else (N, BATCH)
    on_tpu = jax.default_backend() == "tpu"

    x = rng.random(n, dtype=np.float32)
    xj = jnp.asarray(x)
    blk = block_rmq.build(xj, 128)
    lane = lane_rmq.build(xj)
    st = sparse_table.build(xj)
    hyb = hybrid.build(xj, 128, use_kernels=on_tpu)
    kblk = kernels.ops.build(xj, 128, interpret=not on_tpu)

    # --- dispatch audit (static; backend-independent) --------------------
    l0, r0 = make_queries(rng, n, batch, "medium")
    l0j, r0j = jnp.asarray(l0), jnp.asarray(r0)
    for name, fn in [
        ("fused-tiled", lambda l, r: kernels.ops.query(kblk, l, r, interpret=not on_tpu)),
        ("legacy-2pass", lambda l, r: kernels.ops.query(kblk, l, r, fused=False, interpret=not on_tpu)),
    ]:
        p, g, s = _jaxpr_audit(fn, l0j, r0j)
        emit(
            f"crossover/dispatch/{name}",
            0.0,
            f"pallas_calls={p}_xla_gathers={g}_xla_selects={s}",
        )

    # --- regime sweep ----------------------------------------------------
    # All engines are timed at the same host boundary the dispatcher serves
    # (numpy queries in), so H2D transfer costs fall on every row equally.
    q_blk = jax.jit(lambda l, r: block_rmq.query(blk, l, r))
    q_lane = jax.jit(lambda l, r: lane_rmq.query(lane, l, r))
    q_st = jax.jit(lambda l, r: sparse_table.query(st, l, r))
    engines = [("RTXRMQ-block", q_blk), ("LANE", q_lane), ("ST", q_st)]
    if on_tpu:  # kernel wall-clock is only meaningful on hardware
        engines.append(("FUSED-K", lambda l, r: kernels.ops.query(kblk, l, r)))
        engines.append(
            ("LEGACY-K", lambda l, r: kernels.ops.query(kblk, l, r, fused=False))
        )

    for dist in DISTS:
        l, r = make_queries(rng, n, batch, dist)
        times = {}
        for name, fn in engines:
            t = time_fn(lambda a, b, fn=fn: fn(jnp.asarray(a), jnp.asarray(b)), l, r)
            times[name] = t
            emit(f"crossover/{name}/n={n}/{dist}", t / batch, f"{t/batch*1e9:.1f}ns_per_rmq")

        # Hybrid vs. its constituents: never slower than the worst of them.
        t_h = time_fn(lambda a, b: hybrid.query(hyb, a, b), l, r)
        short_name = "FUSED-K" if on_tpu else "RTXRMQ-block"
        worst = max(times[short_name], times["ST"])
        # Tolerance = timing noise floor: 5% on TPU; CPU containers show
        # ±~20% run-to-run on the ms-scale small-regime path, so hold the
        # regime-level claim there without false FAILs.
        tol = 1.05 if on_tpu else 1.25
        verdict = "PASS" if t_h <= worst * tol else "FAIL"
        emit(
            f"crossover/HYBRID/n={n}/{dist}",
            t_h / batch,
            f"{t_h/batch*1e9:.1f}ns_per_rmq_vs_worst_constituent={worst/batch*1e9:.1f}ns_{verdict}",
        )

    emit(f"crossover/threshold/n={n}", 0.0, f"range_len<={hyb.threshold}->blocked")


if __name__ == "__main__":
    run()

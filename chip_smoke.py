"""Smoke run of the RMQ service on TPU chips, through its user entry points.

    python chip_smoke.py             # one chip: hybrid build + serve, fused, updates
    python chip_smoke.py --chips 4   # four chips: sharded_hybrid, 4-replica fleet

Every phase checks every answer against the numpy oracle (``core/ref.py``)
and fails the run on any mismatch, failed request or degraded launch. The
lines before the last are smoke output (set-up seconds, resolved knobs,
serve summaries), not metrics. The last line is one JSON object naming the
device. Without a TPU the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# One chip: the largest power of two whose hybrid build fits. At 2^26 the
# doubling table's 27 rows pad to 32 (8 GiB) and the build holds 9.4 GiB of
# the 16 GiB HBM (tests/test_chip_compile.py); 2^27 would need 16 GiB for
# the table alone. nb = 2^19 blocks, so the short path uses the dma fetch.
N_HYBRID = 1 << 26
N_HYBRID_WHY = (
    "n = 2^26: the doubling table pads 27 -> 32 rows (8 GiB); the build "
    "holds 9.4 GiB of 16 GiB HBM (v5e compile, memory_analysis)"
)
N_RESIDENT = 1 << 20  # nb = 2^13 blocks: the largest resident-fetch size
N_UPDATE = 1 << 24
# Four chips: the sharded doubling table keeps indices and values, 32 GiB at
# 2^27 (8 GiB per chip): more than one chip holds. Its halo build and the
# served structures hold 9.3 GiB per chip (tests/test_chip_compile.py).
N_SHARDED = 1 << 27
N_SHARDED_WHY = (
    "n = 2^27: its (idx, val) doubling table is 32 GiB, 8 GiB per chip; the "
    "halo build holds 9.3 GiB per chip (v5e compile, memory_analysis)"
)
N_FLEET = 1 << 24

# What a compiled Pallas kernel lowers to.
KERNEL_CALL = "tpu_custom_call"

# (dist, clients, requests per client): the oracle scans each range in numpy,
# so large ranges get fewer requests to keep verification near a minute.
SERVE_MIX = (("small", 4, 32), ("medium", 4, 32), ("large", 4, 8))


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def check_stats(phase: str, stats) -> None:
    if not stats:
        raise SystemExit(f"{phase}: the serve CLI returned no server stats")
    for st in stats:
        if st.degraded_launches or st.failed_requests:
            raise SystemExit(
                f"{phase}: {st.degraded_launches} degraded launches, "
                f"{st.failed_requests} failed requests"
            )
    say(f"{phase}: 0 degraded launches, 0 failed requests on {len(stats)} server(s)")


def serve(phase: str, argv) -> list:
    from repro.launch import serve as serve_cli

    t0 = time.perf_counter()
    stats = serve_cli.main([str(a) for a in argv])
    say(f"{phase}: {time.perf_counter() - t0:.1f} s including build and warmup")
    return stats


def memory_report(jax) -> None:
    for d in jax.devices():
        m = d.memory_stats() or {}
        say(
            f"{d}: bytes_in_use={m.get('bytes_in_use')} "
            f"peak_bytes_in_use={m.get('peak_bytes_in_use')} bytes_limit={m.get('bytes_limit')}"
        )


def hybrid_phase(jax, n: int, seed: int) -> None:
    """Build ``hybrid`` the way the serve CLI does, prove the short path is
    the compiled kernel, and check one mixed batch against the oracle."""
    from repro.core import build as build_mod
    from repro.core import ref, registry
    from repro.serve.workload import make_queries

    say(N_HYBRID_WHY)
    rng = np.random.default_rng(seed)
    x = rng.random(n, dtype=np.float32)
    plan = registry.plan_for_serving(
        "hybrid", n, threshold="cached", kernel_config="cached"
    )
    say(
        f"hybrid plan: threshold={plan.meta['threshold']} "
        f"kernel_config={plan.meta['kernel_config']} use_kernels={plan.meta['use_kernels']}"
    )
    t0 = time.perf_counter()
    state = build_mod.execute(plan, x)
    jax.block_until_ready(jax.tree_util.tree_leaves(state.blocked))
    jax.block_until_ready(state.st.idx)
    say(f"hybrid build: {time.perf_counter() - t0:.1f} s (n={n})")

    l, r = make_queries(rng, n, 512, "small")
    t0 = time.perf_counter()
    short = state.short_fn  # the kernel query, bound to the structure
    lowered = jax.jit(short.func, static_argnames="config").lower(
        *short.args, l, r, **short.keywords
    )
    if KERNEL_CALL not in lowered.as_text():
        raise SystemExit(f"hybrid short path: no {KERNEL_CALL} in the lowered query")
    lowered.compile()
    say(f"hybrid short path: {KERNEL_CALL} present, compiled in {time.perf_counter() - t0:.1f} s")

    from repro.core import hybrid

    l, r = make_queries(rng, n, 512, "mixed")
    idx, val = hybrid.query(state, l, r)
    gold = ref.rmq_ref(x, l, r)
    if not (np.array_equal(np.asarray(idx), gold) and np.array_equal(np.asarray(val), x[gold])):
        raise SystemExit("hybrid: a mixed batch disagrees with the oracle")
    say("hybrid: 512 mixed queries bit-identical to the oracle")


def one_chip(jax) -> None:
    from repro.kernels.tuning import resolve_fetch

    hybrid_phase(jax, N_HYBRID, seed=0)
    gc.collect()
    for dist, clients, requests in SERVE_MIX:
        stats = serve(
            f"serve hybrid {dist}",
            ["--mode", "async", "--engine", "hybrid", "--n", N_HYBRID, "--dist", dist,
             "--clients", clients, "--requests", requests, "--req-batch", 16],
        )
        check_stats(f"serve hybrid {dist}", stats)
    say(f"fused128 at n={N_RESIDENT}: fetch={resolve_fetch('auto', N_RESIDENT // 128)}")
    serve(
        "oneshot fused128",
        ["--mode", "oneshot", "--engine", "fused128", "--n", N_RESIDENT, "--dist", "mixed",
         "--batch", 4096, "--batches", 4, "--verify", 4096],
    )
    stats = serve(
        "updates hybrid",
        ["--mode", "async", "--engine", "hybrid", "--n", N_UPDATE, "--dist", "mixed",
         "--clients", 4, "--requests", 32, "--req-batch", 16, "--mutate", 6],
    )
    check_stats("updates hybrid", stats)


def four_chips(jax) -> None:
    say(N_SHARDED_WHY)
    stats = serve(
        "sharded_hybrid shard_structure",
        ["--mode", "async", "--engine", "sharded_hybrid", "--n", N_SHARDED, "--dist", "mixed",
         "--clients", 4, "--requests", 8, "--req-batch", 16],
    )
    check_stats("sharded_hybrid shard_structure", stats)
    memory_report(jax)
    stats = serve(
        "fleet hybrid x4",
        ["--mode", "async", "--engine", "hybrid", "--n", N_FLEET, "--dist", "mixed",
         "--replicas", 4, "--clients", 4, "--requests", 32, "--req-batch", 16,
         "--mutate", 3],
    )
    check_stats("fleet hybrid x4", stats)
    memory_report(jax)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}", file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(jax.devices())} device(s)", file=sys.stderr)
        return 1

    from repro.launch.cache import enable_compile_cache

    hits = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            hits["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    say(f"compile cache at {enable_compile_cache()}")
    say(f"devices: {dev.device_kind} x {len(jax.devices())}")

    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(jax)
    say(
        f"all phases passed in {time.perf_counter() - t0:.1f} s; compile cache "
        f"{hits['hits']} hits, {hits['misses']} misses"
    )
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

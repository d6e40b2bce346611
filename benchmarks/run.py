"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig12,...] [--json OUT]

Prints ``name,us_per_call,derived`` CSV rows. ``--json OUT`` also writes the
results as ``{suite: {name: us_per_call}}`` JSON (e.g. BENCH_PR1.json) so the
perf trajectory is machine-trackable across PRs. ``--smoke`` shrinks sizes so
a suite finishes in seconds (CI smoke; see tools/check.sh).
"""

import argparse
import json
import subprocess


def _metrics_meta():
    """Snapshot of the process-global metrics registry (counters only —
    histograms here would be noise: every suite shares the process)."""
    from repro.obs import default_registry

    snap = default_registry().snapshot()
    out = {}
    for name, rows in snap["counters"].items():
        for row in rows:
            key = name
            if row["labels"]:
                key += "{" + ",".join(f"{k}={v}" for k, v in sorted(row["labels"].items())) + "}"
            out[key] = row["value"]
    return out or None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only",
        default="",
        help="comma list: fig12,fig13,fig10,fig14,table2,build_mem,roofline,"
        "crossover,sharded_hybrid,serve_latency,update_throughput,"
        "fault_overhead,fleet_scaling,kernel_tuning,bandwidth,obs_overhead",
    )
    ap.add_argument("--json", default="", metavar="OUT", help="also write results JSON")
    ap.add_argument("--smoke", action="store_true", help="tiny sizes, seconds-long run")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    if args.json:  # fail on an unwritable path BEFORE minutes of benchmarking
        try:
            open(args.json, "a").close()
        except OSError as e:
            ap.error(f"--json {args.json}: {e}")

    from . import (
        bandwidth,
        batch_scaling,
        common,
        fault_overhead,
        fleet_scaling,
        heatmap,
        hybrid_crossover,
        kernel_tuning,
        memory_usage,
        mesh_scaling,
        obs_overhead,
        roofline_report,
        serve_latency,
        sharded_hybrid,
        time_per_rmq,
        update_throughput,
    )

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    common.SMOKE = args.smoke

    suites = {
        "fig12": time_per_rmq.run,
        "fig13": batch_scaling.run,
        "fig10": heatmap.run,
        "table2": memory_usage.run,
        "build_mem": memory_usage.run_build_mem,
        "fig14": mesh_scaling.run,
        "roofline": roofline_report.run,
        "crossover": hybrid_crossover.run,
        "sharded_hybrid": sharded_hybrid.run,
        "serve_latency": serve_latency.run,
        "update_throughput": update_throughput.run,
        "fault_overhead": fault_overhead.run,
        "fleet_scaling": fleet_scaling.run,
        "kernel_tuning": kernel_tuning.run,
        "bandwidth": bandwidth.run,
        "obs_overhead": obs_overhead.run,
    }
    if only:
        unknown = only - set(suites)
        if unknown:
            ap.error(f"unknown suite(s) {sorted(unknown)}; have {sorted(suites)}")
    for name, fn in suites.items():
        if only and name not in only:
            continue
        print(f"# --- {name} ---")
        fn()

    if args.json:
        by_suite: dict = {}
        for name, us in common.RESULTS.items():
            suite, _, rest = name.partition("/")
            by_suite.setdefault(suite, {})[rest or suite] = us
        # Provenance: which tree and backend produced these numbers, which
        # fault schedule the injected-fault measurements used, and whether
        # the autotune cache was warm (a hit means zero timing sweeps ran).
        try:
            rev = subprocess.run(
                ["git", "rev-parse", "HEAD"], capture_output=True, text=True, timeout=10
            ).stdout.strip() or None
        except OSError:
            rev = None
        import jax

        from repro.core import packing

        by_suite["_meta"] = {
            "git_rev": rev,
            "fault_seed": fault_overhead.FAULT_SEED,
            "smoke": bool(args.smoke),
            "backend": jax.default_backend(),
            "device_count": len(jax.devices()),
            "jax_version": jax.__version__,
            "autotune_cache": dict(kernel_tuning.CACHE_STATE) or None,
            # Packed-layout stamp: which fused-word layouts this tree ships
            # and the measured byte ratios (populated when `bandwidth` ran).
            "layouts": ["unpacked"] + list(packing.PACKED_LAYOUTS),
            "bandwidth_report": dict(bandwidth.LAST_REPORT) or None,
            # Process-global metrics registry at run end: counters the
            # benchmarked subsystems incremented (WAL appends, checkpoints,
            # restores, ...) so a perf regression can be cross-checked
            # against the work actually done.
            "metrics": _metrics_meta(),
        }
        with open(args.json, "w") as f:
            json.dump(by_suite, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()

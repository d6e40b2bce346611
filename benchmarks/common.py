"""Shared benchmark utilities: the paper's query-range distributions (§6.4)
and timing helpers. CSV convention: ``name,us_per_call,derived``."""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

from repro.serve.workload import make_queries  # one source for the §6.4 regimes

__all__ = ["make_queries", "per_device_count", "time_fn", "emit", "RESULTS", "SMOKE"]

# Every emit() also lands here (name -> us_per_call) so the harness can dump
# machine-readable JSON (benchmarks/run.py --json) for cross-PR tracking.
RESULTS: dict = {}

# Set by `benchmarks.run --smoke`: suites shrink sizes/batches to finish in
# seconds (CI smoke via tools/check.sh).
SMOKE = False


def time_fn(fn, *args, repeats: int = 5, warmup: int = 2):
    """Median wall time of fn(*args) with block_until_ready, in seconds."""
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def emit(name: str, seconds: float, derived: str = ""):
    RESULTS[name] = seconds * 1e6
    print(f"{name},{seconds*1e6:.2f},{derived}")


def per_device_count(fn_path: str, counts, **kw):
    """Yield ``(n_dev, fn(devices, **kw))`` for each device count in ``counts``.

    ``fn_path`` is ``"module:function"``; the function takes the list of
    devices to use and returns a JSON-serialisable result. On the CPU backend
    each count runs in a child process with that many virtual devices (XLA
    fixes the count at its first import), and a child that fails raises here
    with its stderr. On an accelerator the parent holds the devices and a
    child could not get them, so the function runs in this process over the
    first ``n_dev`` devices; counts past the devices held are skipped.
    """
    mod, name = fn_path.split(":")
    if jax.default_backend() != "cpu":
        fn = getattr(importlib.import_module(mod), name)
        devs = jax.devices()
        for n_dev in counts:
            if n_dev <= len(devs):
                yield n_dev, fn(devs[:n_dev], **kw)
        return
    code = (
        "import json, sys, jax\n"
        f"from {mod} import {name}\n"
        f"print(json.dumps({name}(jax.devices(), **json.loads(sys.argv[1]))))\n"
    )
    for n_dev in counts:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
        env["PYTHONPATH"] = "src:."
        out = subprocess.run(
            [sys.executable, "-c", code, json.dumps(kw)],
            env=env,
            capture_output=True,
            text=True,
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"{fn_path} on {n_dev} device(s) failed (rc {out.returncode}):\n"
                + out.stderr[-4000:]
            )
        yield n_dev, json.loads(out.stdout.strip().splitlines()[-1])

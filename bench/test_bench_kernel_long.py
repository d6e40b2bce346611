"""CPU tests of the readers and cells for the hybrid without its long-path
table (``hybrid_n30``) and of the ``batch_medium`` mix.

They cover: ``kernel_long_share`` on counter deltas, and its silence where
the program has no such counter; ``fused_long_roofline`` on a synthetic and
on a recorded trace against ``rooflines.fused_bytes``; both new cells found
by name; and a traced tiny run of a table-free hybrid (kernel in interpret
mode) that reports the share. The tiny benchmark root and run come from
``test_bench_harness.py``.
"""

from __future__ import annotations

import functools
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

import test_bench_harness as bench_tests  # noqa: E402
from rmqbench import harness, rooflines  # noqa: E402
from rmqbench.trace import Trace  # noqa: E402

NEW_METRICS = ("kernel_long_share", "fused_long_roofline")
HBM = 819e9


def _ctx_with_counters(counters):
    """A harness context over a registry holding ``counters`` (name -> value
    at the window's start), each advanced afterwards by ``advance``."""
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    made = {name: reg.counter(name) for name in counters}
    for name, start in counters.items():
        made[name].inc(start)
    ctx = harness.Context(SimpleNamespace(config={}, mix={}), 0, 1.0)
    ctx.mark_registry(reg)
    return ctx, made


def test_kernel_long_share_is_the_counter_over_the_windows_queries():
    ctx, c = _ctx_with_counters({"serve_queries_total": 500, "serve_kernel_long_queries_total": 480})
    c["serve_queries_total"].inc(16384 * 3)
    c["serve_kernel_long_queries_total"].inc(16384 * 3 - 2)
    read = harness.load_reader(REPO, "kernel_long_share")
    assert read(ctx) == pytest.approx(100.0 * (16384 * 3 - 2) / (16384 * 3), rel=1e-12)


def test_kernel_long_share_with_a_table_reads_zero():
    ctx, c = _ctx_with_counters({"serve_queries_total": 0, "serve_kernel_long_queries_total": 0})
    c["serve_queries_total"].inc(100)
    assert harness.load_reader(REPO, "kernel_long_share")(ctx) == 0.0


def test_kernel_long_share_is_silent_without_the_counter_or_queries():
    read = harness.load_reader(REPO, "kernel_long_share")
    ctx, c = _ctx_with_counters({"serve_queries_total": 0})  # a program without it
    c["serve_queries_total"].inc(100)
    assert read(ctx) is None
    ctx, _ = _ctx_with_counters({"serve_queries_total": 7, "serve_kernel_long_queries_total": 3})
    assert read(ctx) is None  # no query in the window


def _pool_ctx(trace, threshold, pool, peaks=True):
    """A context whose window answered each request of ``pool`` once."""
    ctx = harness.Context(SimpleNamespace(config={}, mix={}), 0, 1.0)
    ctx.trace, ctx.threshold, ctx.pool = trace, threshold, [pool]
    ctx.peaks = {"hbm_bytes_per_s": HBM} if peaks else None
    ctx.records = [bench_tests.traffic.Record(0, i, 0.5, 0.6, pool[i][0].size, True) for i in range(len(pool))]
    return ctx


def _pool(n, seed=3):
    rng = np.random.default_rng(seed)
    return [bench_tests.traffic.make_queries(rng, n, 64, regime) for regime in ("large", "small")]


def test_fused_long_roofline_on_a_synthetic_trace():
    """Every query of a table-free hybrid (threshold n) counts; the kernel's
    own events are 3 ms on one of two devices."""
    n = 1 << 30
    pool = _pool(n)
    read = harness.load_reader(REPO, "fused_long_roofline")
    value = read(_pool_ctx(bench_tests._synthetic_trace(), n, pool))
    nbytes = sum(rooflines.fused_bytes(l, r) for l, r in pool)
    assert value == pytest.approx(100.0 * nbytes / (0.003 / 2 * HBM), rel=1e-12)
    assert 0 < value <= 100


def test_fused_long_roofline_counts_only_what_the_kernel_answered():
    n = 1 << 20
    pool = _pool(n)
    thr = 1024  # a hybrid with a table routes the rest to it
    value = harness.load_reader(REPO, "fused_long_roofline")(_pool_ctx(bench_tests._synthetic_trace(), thr, pool))
    nbytes = 0
    for l, r in pool:
        short = (r.astype(np.int64) - l + 1) <= thr
        nbytes += rooflines.fused_bytes(l[short], r[short])
    assert value == pytest.approx(100.0 * nbytes / (0.003 / 2 * HBM), rel=1e-12)


def test_fused_long_roofline_on_a_recorded_trace():
    doc = json.loads((BENCH / "testdata" / "v5e_trace_excerpt.json").read_text())
    tr = Trace(doc, *doc["window_ns"])
    pool = _pool(1 << 26)
    value = harness.load_reader(REPO, "fused_long_roofline")(_pool_ctx(tr, 1 << 26, pool))
    nbytes = sum(rooflines.fused_bytes(l, r) for l, r in pool)
    assert value == pytest.approx(100.0 * nbytes / (doc["expect"]["kernel_s"] * HBM), rel=1e-9)


def test_fused_long_roofline_is_silent_without_trace_peaks_or_kernel():
    read = harness.load_reader(REPO, "fused_long_roofline")
    pool = _pool(1 << 20)
    assert read(_pool_ctx(None, 1 << 20, pool)) is None
    assert read(_pool_ctx(bench_tests._synthetic_trace(), 1 << 20, pool, peaks=False)) is None
    assert read(_pool_ctx(bench_tests._synthetic_trace(), None, pool)) is None
    no_kernel = {"planes": {"/device:TPU:0": {"XLA Ops": [["%gather.3 = f32[8] gather()", 0, 1e6]]}}}
    assert read(_pool_ctx(Trace(no_kernel, 0.0, 1e7), 1 << 20, pool)) is None


# -- the new cells ----------------------------------------------------------------------------


def test_hybrid_n30_batch_large_loads_by_name():
    cell = harness.load_cell(REPO, "hybrid_n30.batch_large", trace=True)
    assert cell.config["n"] == 1 << 30 and cell.config["engine"] == "hybrid"
    assert cell.config["threshold"] is None and cell.config["kernel_config"] is None
    assert cell.config["reduced"] == [] and cell.chips == 1
    assert cell.mix["regime"] == "large" and cell.mix["queries_per_request"] == 16384
    assert set(NEW_METRICS) <= {m["name"] for m in cell.metrics}
    untraced = harness.load_cell(REPO, "hybrid_n30.batch_large", trace=False)
    assert {"rmq_per_s", "setup_s", "peak_hbm_gb"} <= {m["name"] for m in untraced.metrics}


def test_hybrid_n26_batch_medium_loads_by_name():
    cell = harness.load_cell(REPO, "hybrid_n26.batch_medium", trace=True)
    assert cell.config["name"] == "hybrid_n26" and cell.chips == 1
    assert cell.mix == {"loop": "closed", "clients": 4, "queries_per_request": 16384, "regime": "medium", "pool_per_client": 16}
    assert not set(NEW_METRICS) & {m["name"] for m in cell.metrics}
    # Every medium range lies over the sqrt(n) regime boundary at 2^26.
    for per_client in bench_tests.traffic.request_pool(2**31 + 99, 1 << 26, cell.mix):
        for l, r in per_client:
            assert ((r.astype(np.int64) - l + 1) > 8192).all()


def test_a_traced_table_free_tiny_run_reports_kernel_long_share(tmp_path, monkeypatch):
    """The harness over a hybrid whose plan found no room for its table:
    every answer correct, and the share is the long ranges' share."""
    from repro.core import build as build_mod

    monkeypatch.setattr(build_mod, "device_bytes_limit", lambda: 1)
    plan_hybrid = build_mod._PLANNERS["hybrid"]
    monkeypatch.setitem(build_mod._PLANNERS, "hybrid", functools.partial(plan_hybrid, use_kernels=True))
    root = bench_tests.make_root(tmp_path, regime="large")
    cfg = json.loads((root / "bench" / "configs" / "tiny.json").read_text())
    cfg["serve"] = {"max_batch": 64}  # few launch shapes to warm up in interpret mode
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny.cell")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    line, err = bench_tests.run_tiny(root, trace=True)
    assert line["correct"] is True, err
    share = line["metrics"]["kernel_long_share"]["value"]
    # 64-query requests uniform in [1, 4096]: nearly all over sqrt(4096) = 64.
    assert 90 < share <= 100
    assert "fused_long_roofline" not in line["metrics"]  # no device planes on the CPU

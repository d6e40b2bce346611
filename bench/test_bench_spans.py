"""CPU tests of the benchmark's readers of the program's spans.

They cover: the interval helpers of ``rmqbench/spans.py``; each of the six
readers (``worker_busy``, ``host_path_ms``, ``dispatch_host_ms``,
``idle_host_path``, ``gc_pause_ms``, ``compiles_in_window``) on a synthetic
span set and trace with known answers, and their silence on a program that
lacks the spans; traced tiny runs on one and on four virtual devices that
report all six; and ``compiles_in_window`` against the harness's own count.
The tiny benchmark root and run come from ``test_bench_harness.py``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

import test_bench_harness as bench_tests  # noqa: E402
from rmqbench import harness, traffic  # noqa: E402
from rmqbench import trace as trace_mod  # noqa: E402
from rmqbench.trace import Trace  # noqa: E402

SPAN_METRICS = ("worker_busy", "host_path_ms", "dispatch_host_ms", "idle_host_path", "gc_pause_ms", "compiles_in_window")


def test_interval_difference_and_overlap():
    from rmqbench.spans import minus, overlap

    a = [(0, 4), (6, 10), (12, 13)]
    b = [(1, 2), (3, 7), (9, 12.5)]
    assert minus(a, b) == [(0, 1), (2, 3), (7, 9), (12.5, 13)]
    assert overlap(a, b) == 1 + 1 + 1 + 1 + 0.5
    assert minus(a, []) == a and minus([], b) == [] and overlap(a, []) == 0


# -- the readers on a synthetic window ------------------------------------------------------


def _span(sid, name, parent, t0, t1):
    return SimpleNamespace(span_id=sid, name=name, parent_id=parent, t0=t0, t1=t1, attrs={})


def _synthetic_spans():
    """Two whole launch cycles (the first a mixed batch), one the window's
    close cuts, two collections and a compile, in a window of 1 s."""
    return [
        _span(10, "launch", 1, 0.00, 0.10),
        _span(100, "prepare", 10, 0.00, 0.02),
        _span(101, "h2d", 10, 0.02, 0.03),
        _span(102, "enqueue", 10, 0.03, 0.04),
        _span(103, "wait", 10, 0.04, 0.08),
        _span(104, "merge", 10, 0.08, 0.10),
        _span(11, "wait", 1, 0.10, 0.30),
        _span(12, "d2h", 1, 0.30, 0.32),
        _span(13, "scatter", 1, 0.32, 0.34),
        _span(14, "finish", 1, 0.34, 0.35),
        _span(20, "launch", 2, 0.50, 0.55),
        _span(200, "prepare", 20, 0.50, 0.51),
        _span(201, "h2d", 20, 0.51, 0.52),
        _span(202, "enqueue", 20, 0.52, 0.53),
        _span(21, "wait", 2, 0.55, 0.65),
        _span(22, "d2h", 2, 0.65, 0.66),
        _span(23, "scatter", 2, 0.66, 0.67),
        _span(24, "finish", 2, 0.67, 0.70),
        _span(30, "launch", 3, 0.95, 1.05),  # its finish lies past the close
        _span(300, "prepare", 30, 0.95, 0.96),
        _span(301, "enqueue", 30, 0.96, 0.97),
        _span(900, "gc", None, 0.40, 0.41),
        _span(901, "gc", None, 0.80, 0.805),
        _span(902, "compile", None, 0.20, 0.25),
    ]


def _synthetic_host_trace():
    """Idle gaps of 2-5 and 6-10 ms on TPU:0 and of 4-6 ms on TPU:1; the
    worker's host events, less its waits, cover 3 ms of TPU:0's gaps and
    none of TPU:1's."""
    ms = 1e6
    raw = {"planes": {
        "/device:TPU:0": {"XLA Ops": [["%a", 0, 2 * ms], ["%b", 5 * ms, 1 * ms]]},
        "/device:TPU:1": {"XLA Ops": [["%c", 0, 4 * ms], ["%d", 6 * ms, 4 * ms]]},
        "/host:CPU": {
            "python": [
                ["rmq.launch", 1 * ms, 3 * ms],  # 1 ms of the 2-5 gap: 3-4 is its wait
                ["rmq.prepare", 2 * ms, 1 * ms],  # inside the launch
                ["rmq.wait", 3 * ms, 1 * ms],  # a mixed batch's, inside the launch
                ["rmq.wait", 4 * ms, 1 * ms],  # the worker's: waiting is not host work
                ["rmq.d2h", 6 * ms, 1 * ms],
                ["rmq.scatter", 7 * ms, 0.5 * ms],
                ["bench.wait", 0, 10 * ms],
            ],
            "python ": [["rmq.gc", 8 * ms, 0.5 * ms]],
        },
    }}
    return Trace(raw, 0.0, 10 * ms)


SYNTHETIC_READINGS = {
    "worker_busy": 100.0 * (0.35 + 0.20 + 0.05),  # cycles clipped to the window
    "host_path_ms": 1e3 * (0.11 + 0.10) / 2,  # 150 ms less the mixed wait of 40; 100
    "dispatch_host_ms": 30.0,  # median of 60, 30 and 20
    "idle_host_path": 100.0 * (3 / 7 + 0) / 2,
    "gc_pause_ms": 15.0,
    "compiles_in_window": 1,
}


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_span_readers_on_a_synthetic_window(metric):
    ctx = SimpleNamespace(spans=_synthetic_spans(), t0=0.0, t1=1.0, trace=_synthetic_host_trace())
    value = harness.load_reader(REPO, metric)(ctx)
    assert value == pytest.approx(SYNTHETIC_READINGS[metric], rel=1e-12, abs=1e-9)


def test_span_readers_are_silent_on_a_program_without_the_spans(monkeypatch):
    """A program that predates the launch-cycle spans and the hooks gives
    the readers nothing: its flushes hold only ``launch`` and ``scatter``,
    its tracer names no hooked spans, its trace has no ``rmq.*`` events."""
    from repro.obs import trace as obs_trace

    monkeypatch.delattr(obs_trace, "HOOK_SPANS")
    old = [s for s in _synthetic_spans() if s.name in ("launch", "scatter")]
    raw = {"planes": {"/device:TPU:0": {"XLA Ops": [["%a", 0, 1e6]]}, "/host:CPU": {"python": [["np.asarray", 2e6, 1e6]]}}}
    ctx = SimpleNamespace(spans=old, t0=0.0, t1=1.0, trace=Trace(raw, 0.0, 1e7))
    for metric in SPAN_METRICS:
        assert harness.load_reader(REPO, metric)(ctx) is None, metric


# -- the readers in traced tiny runs ---------------------------------------------------------


def _with_device_planes(n_devices: int):
    """``trace_mod.load`` over a CPU profile, with ``n_devices`` stand-in TPU
    planes added that are busy while the worker waits on the device (the
    CPU backend writes no device planes). The host planes stay the run's."""
    real_load = trace_mod.load

    def load(prof_dir, p_anchor, t0, t1):
        tr = real_load(prof_dir, p_anchor, t0, t1)
        waits = [
            [name, s, d]
            for plane, lines in tr.raw["planes"].items() if plane.startswith("/host")
            for evs in lines.values() for name, s, d in evs if name == "rmq.wait"
        ]
        assert waits, "the run's profile holds no rmq.wait host events"
        for i in range(n_devices):
            tr.raw["planes"][f"/device:TPU:{i}"] = {"XLA Ops": waits}
        return Trace(tr.raw, tr.w0, tr.w1)

    return load


def _diagnostic(err: str, name: str) -> str:
    return next(line.split(" = ", 1)[1] for line in err.splitlines() if line.startswith(f"rmqbench: {name} = "))


def _assert_span_metrics(line: dict, err: str) -> None:
    assert line["correct"] is True, err
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(SPAN_METRICS) <= set(m), sorted(m)
    assert 0 < m["worker_busy"] <= 100 and 0 < m["idle_host_path"] <= 100
    assert m["host_path_ms"] > 0 and m["dispatch_host_ms"] > 0 and m["gc_pause_ms"] >= 0
    assert m["compiles_in_window"] == int(_diagnostic(err, "compiles_in_window"))


def test_a_traced_tiny_run_reports_the_span_metrics(tmp_path, monkeypatch):
    monkeypatch.setattr(trace_mod, "load", _with_device_planes(1))
    line, err = bench_tests.run_tiny(bench_tests.make_root(tmp_path), trace=True)
    _assert_span_metrics(line, err)
    assert line["metrics"]["compiles_in_window"]["value"] == 0


def test_compiles_in_window_counts_what_the_harness_counts(tmp_path, monkeypatch):
    """One fresh jit in the window, on the client's thread: the reader and
    the harness's own diagnostic both count it."""
    import jax

    real_loop = traffic.closed_loop

    def closed_loop(submit, *a, **k):
        calls = [0]

        def submit_and_compile_once(l, r):
            calls[0] += 1
            if calls[0] == 3:
                jax.jit(lambda v: v * 5 - 2)(np.arange(4.0)).block_until_ready()
            return submit(l, r)

        return real_loop(submit_and_compile_once, *a, **k)

    monkeypatch.setattr(traffic, "closed_loop", closed_loop)
    line, err = bench_tests.run_tiny(bench_tests.make_root(tmp_path), trace=True)
    assert line["metrics"]["compiles_in_window"]["value"] == int(_diagnostic(err, "compiles_in_window")) == 1


def _sharded_traced_main(root: str) -> None:
    """Entry of the four-device child: a traced run of the sharded cell."""
    import jax

    assert len(jax.devices()) == 4
    trace_mod.load = _with_device_planes(4)
    root_dir = bench_tests.make_root(Path(root), engine="sharded_hybrid", chips=4)
    line, err = bench_tests.run_tiny(root_dir, trace=True)
    print(json.dumps({"line": line, "err": err}))


def test_a_traced_sharded_run_reports_the_span_metrics(tmp_path):
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4", JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join([str(REPO / "src"), str(BENCH), env.get("PYTHONPATH", "")])
    code = f"import test_bench_spans as t; t._sharded_traced_main({str(tmp_path)!r})"
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=600, cwd=str(BENCH)
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    _assert_span_metrics(out["line"], out["err"])
    assert out["line"]["device"]["count"] == 4

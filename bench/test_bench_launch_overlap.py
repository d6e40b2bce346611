"""CPU tests of the ``launch_overlap`` reader.

They cover: the share on counter deltas over the window, and its silence
where the program has no ``serve_launches_overlapped_total`` counter or the
window served no launch; and a traced tiny run that reports it. The tiny
benchmark root and run come from ``test_bench_harness.py``.
"""

from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

import test_bench_harness as bench_tests  # noqa: E402
from rmqbench import harness  # noqa: E402

_BATCHES, _OVERLAPPED = "serve_batches_total", "serve_launches_overlapped_total"


@pytest.mark.parametrize(
    "start, advance, expected",
    [
        # counter -> its value at the window's start; its advance in the window
        ({_BATCHES: 9, _OVERLAPPED: 4}, {_BATCHES: 400, _OVERLAPPED: 399}, 100.0 * 399 / 400),
        ({_BATCHES: 9, _OVERLAPPED: 0}, {_BATCHES: 50}, 0.0),
        ({_BATCHES: 9}, {_BATCHES: 50}, None),  # a program without the counter
        ({_BATCHES: 9, _OVERLAPPED: 3}, {}, None),  # no launch in the window
    ],
    ids=["overlapped", "serial", "no-counter", "no-launch"],
)
def test_launch_overlap_is_the_counter_over_the_windows_launches(start, advance, expected):
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    made = {name: reg.counter(name) for name in start}
    for name, value in start.items():
        made[name].inc(value)
    ctx = harness.Context(SimpleNamespace(config={}, mix={}), 0, 1.0)
    ctx.mark_registry(reg)
    for name, value in advance.items():
        made[name].inc(value)
    value = harness.load_reader(REPO, "launch_overlap")(ctx)
    assert value == (pytest.approx(expected, rel=1e-12) if expected is not None else None)


def test_a_traced_tiny_run_reports_launch_overlap(tmp_path):
    line, err = bench_tests.run_tiny(bench_tests.make_root(tmp_path), trace=True)
    assert line["correct"] is True, err
    assert 0 <= line["metrics"]["launch_overlap"]["value"] <= 100

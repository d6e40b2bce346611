"""CPU tests of the chip benchmark's harness (``bench/``).

They cover: finding cells, configurations, mixes and metric readers by
name, and a cell added by data files alone; the rate and tail arithmetic;
the byte functions; the trace reduction on a synthetic and on a recorded
trace; the reference against a plain scan; the control and the faults a
cell can have, each driven through the rest of a run and seen to come out
not correct; and ``bench/run.py`` refusing a machine with no TPU.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

from rmqbench import harness, reference, rooflines, traffic  # noqa: E402
from rmqbench.trace import Trace, merge  # noqa: E402

TINY_N = 4096


# -- a tiny benchmark root, made of data files alone ---------------------------


def make_root(tmp: Path, *, engine: str = "hybrid", chips: int = 1, regime: str = "mixed") -> Path:
    """A checkout-like root whose cell ``tiny.cell`` exists only as data:
    a new configuration file, a new mix file and a new BENCHMARK.json entry;
    the metric readers are the benchmark's own."""
    (tmp / "bench").mkdir(parents=True)
    shutil.copytree(BENCH / "metrics", tmp / "bench" / "metrics")
    (tmp / "bench" / "configs").mkdir()
    (tmp / "bench" / "mixes").mkdir()
    base = "sharded_n27" if engine == "sharded_hybrid" else "hybrid_n26"
    cfg = json.loads((BENCH / "configs" / f"{base}.json").read_text())
    cfg.update(name="tiny", n=TINY_N, chips=chips, serve={"max_batch": 256})
    (tmp / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = {"loop": "closed", "clients": 2, "queries_per_request": 64, "regime": regime, "pool_per_client": 3}
    (tmp / "bench" / "mixes" / "tinymix.json").write_text(json.dumps(mix))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": "tiny", "source": "test", "file": "bench/configs/tiny.json", "reduced": ["n"], "why": "test"}
    )
    bench["workloads"].append(
        {"name": "tiny.cell", "config": "tiny", "traffic": "tinymix", "chips": chips, "why": "test"}
    )
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def run_tiny(root: Path, *, trace: bool = False, seconds: float = 0.5, seed: int = 2**31 + 17):
    import io

    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(
        root, "tiny.cell", seed, seconds, trace,
        t_start=time.perf_counter(), check_device=False, out=out, err=err,
    )
    assert rc == 0, err.getvalue()
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-1]), err.getvalue()


# -- finding things by name ------------------------------------------------------


def test_every_cell_finds_its_files_by_name():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        for trace in (False, True):
            cell = harness.load_cell(REPO, w["name"], trace=trace)
            assert cell.config["name"] == w["config"]
            assert cell.chips == cell.config["chips"] == w["chips"]
            names = {m["name"] for m in cell.metrics}
            if not trace:
                assert "setup_s" in names and len(names) >= 2
            else:
                assert names
            for name in names:
                assert callable(harness.load_reader(REPO, name))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_a_cell_added_by_data_files_alone_runs(tmp_path):
    root = make_root(tmp_path)
    cell = harness.load_cell(root, "tiny.cell", trace=False)
    assert cell.config["n"] == TINY_N and cell.mix["queries_per_request"] == 64
    line, err = run_tiny(root)
    assert line["correct"] is True, err
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"rmq_per_s", "p50_ms", "p95_ms", "setup_s"}  # no peak on CPU
    assert list(line)[-1] == "checks"
    assert line["checks"]["wrong_answers"] == {"value": 0, "limit": 0}
    assert "rmqbench: compiles_in_window = 0" in err


def test_a_traced_run_reports_the_per_layer_metrics(tmp_path):
    root = make_root(tmp_path)
    line, err = run_tiny(root, trace=True)
    assert line["correct"] is True, err
    # No device planes on the CPU: the trace metrics stay silent, not 0.
    assert {"build_s", "queue_wait_ms", "launch_fill"} <= set(line["metrics"])
    assert "device_idle" not in line["metrics"]
    assert 0 < line["metrics"]["launch_fill"]["value"] <= 100
    assert line["device"]["window_s"] > 0


def test_mixes_are_validated():
    ok = {"loop": "closed", "clients": 1, "queries_per_request": 8, "regime": "small", "pool_per_client": 2}
    assert traffic.check_mix(dict(ok)) == ok
    for bad in (dict(ok, loop="spiral"), dict(ok, regime="tiny"), dict(ok, clients=0), dict(ok, extra=1)):
        with pytest.raises(ValueError):
            traffic.check_mix(bad)
    with pytest.raises(ValueError):
        traffic.check_mix(dict(ok, loop="open"))  # only the closed loop exists


def test_request_pool_is_the_seeds_and_only_the_seeds():
    mix = {"loop": "closed", "clients": 2, "queries_per_request": 32, "regime": "mixed", "pool_per_client": 3}
    a = traffic.request_pool(2**31 + 5, 1 << 20, mix)
    b = traffic.request_pool(2**31 + 5, 1 << 20, mix)
    c = traffic.request_pool(7, 1 << 20, mix)
    for pa, pb, pc in zip(a, b, c):
        for (la, ra), (lb, rb), (lc, rc) in zip(pa, pb, pc):
            assert np.array_equal(la, lb) and np.array_equal(ra, rb)
            assert la.shape == lc.shape == (32,) and la.dtype == np.int32
            assert np.all((0 <= la) & (la <= ra) & (ra < 1 << 20))
            # Every seed asks for the same lengths, in another order and place.
            assert np.array_equal(np.sort(ra - la), np.sort(rc - lc))
    assert not np.array_equal(a[0][0][0], c[0][0][0])


@pytest.mark.parametrize("regime,lo,hi", [("small", 1, 8192), ("large", 1, 1 << 26)])
def test_regimes_keep_the_papers_lengths(regime, lo, hi):
    l, r = traffic.make_queries(np.random.default_rng(0), 1 << 26, 4096, regime)
    length = r.astype(np.int64) - l + 1
    assert lo <= length.min() and length.max() <= hi
    if regime == "small":  # LogNormal(log n^0.3, 0.3): median about 2^7.8
        assert 150 < np.median(length) < 260


# -- rate and tail ------------------------------------------------------------------


def _ctx_with(records, t0=0.0, t1=10.0):
    ctx = object.__new__(harness.Context)
    ctx.records, ctx.t0, ctx.t1, ctx.wrong_answers = records, t0, t1, 0
    return ctx


def _rec(t_sub, t_done, queries=100, ok=True):
    return traffic.Record(0, 0, t_sub, t_done, queries, ok)


def test_rate_is_all_answers_in_the_window_over_the_window():
    rmq_per_s = harness.load_reader(REPO, "rmq_per_s")
    recs = [_rec(t, t + 1.0) for t in range(10)]  # the last ends at 10.0
    recs.append(_rec(9.5, 10.5))  # answered after the close: not in the rate
    ctx = _ctx_with(recs)
    assert rmq_per_s(ctx) == pytest.approx(10 * 100 / 10.0)
    ctx.wrong_answers = 50
    assert rmq_per_s(ctx) == pytest.approx((1000 - 50) / 10.0)


def test_tail_is_over_every_request_and_refusals_miss_it():
    p50 = harness.load_reader(REPO, "p50_ms")
    p95 = harness.load_reader(REPO, "p95_ms")
    recs = [_rec(0.0, 0.001 * (i + 1)) for i in range(20)]  # 1..20 ms
    ctx = _ctx_with(recs)
    assert p50(ctx) == pytest.approx(10.0)
    assert p95(ctx) == pytest.approx(19.0)
    # A refused request is infinitely late: one in twenty moves the p95 to
    # the next rank, two put it on a refusal (no finite value).
    ctx = _ctx_with(recs[:19] + [_rec(0.0, math.inf, ok=False)])
    assert p95(ctx) == pytest.approx(19.0)
    ctx = _ctx_with(recs[:18] + [_rec(0.0, math.inf, ok=False)] * 2)
    assert p95(ctx) is None


def test_closed_loop_keeps_one_request_per_client_in_flight():
    """Every client has one request out at a time; every request issued in
    the window is recorded, a refused one as infinitely late."""
    import threading
    import types
    from concurrent.futures import Future

    out, peak, lock = [0], [0], threading.Lock()

    class Overloaded(RuntimeError):
        pass

    def submit(l, r):
        if int(l[0]) == 7:
            raise Overloaded("refused")
        fut = Future()
        with lock:
            out[0] += 1
            peak[0] = max(peak[0], out[0])

        def answer():
            with lock:
                out[0] -= 1
            fut.set_result(types.SimpleNamespace(idx=l, val=r))

        threading.Timer(0.01, answer).start()
        return fut

    q = lambda v: (np.full(4, v, np.int32), np.full(4, v, np.int32))  # noqa: E731
    pool = [[q(0), q(1)], [q(2)], [q(3), q(7)]]
    answers_seen = []

    class Sink:
        def add(self, key, idx, val):
            answers_seen.append(key)

    recs, t0, t1 = traffic.closed_loop(submit, pool, 0.3, Sink(), wait_s=5.0, overloaded=Overloaded)
    assert t1 - t0 == pytest.approx(0.3)
    assert peak[0] <= 3 and out[0] == 0
    assert all(t0 <= r.t_submit < t1 for r in recs)
    ok = [r for r in recs if r.ok]
    refused = [r for r in recs if not r.ok]
    assert len(ok) == len(answers_seen) > 3 * 10  # about 30 answers per client
    assert refused and all(r.client == 2 and r.pool_idx == 1 and r.t_done == math.inf for r in refused)
    assert all(r.t_done - r.t_submit >= 0.009 for r in ok)


# -- byte functions --------------------------------------------------------------------


def test_fused_bytes_on_known_queries():
    # Same row; adjacent rows (no interior); rows with whole blocks between.
    l = np.array([0, 100, 0])
    r = np.array([127, 200, 1000])
    assert rooflines.fused_bytes(l[:1], r[:1]) == 512 + 16
    assert rooflines.fused_bytes(l[1:2], r[1:2]) == 2 * 512 + 16
    assert rooflines.fused_bytes(l[2:], r[2:]) == 2 * 512 + 16 + 16
    assert rooflines.fused_bytes(l, r) == 528 + 1040 + 1056
    assert rooflines.fused_bytes(l[:1], r[:1], value_bytes=2) == 256 + 16


def test_long_bytes_on_known_queries():
    assert rooflines.long_bytes(np.arange(10), np.arange(10) + 5) == 10 * 32
    assert rooflines.long_bytes(np.zeros(0), np.zeros(0)) == 0


# -- trace reduction -----------------------------------------------------------------------


def _synthetic_trace():
    ms = 1e6
    raw = {"planes": {
        "/device:TPU:0": {
            "XLA Modules": [["jit_fused_query(1)", 0, 4 * ms], ["jit__long_query(2)", 6 * ms, 2 * ms]],
            "XLA Ops": [
                ['%fused_query.1 = f32[8] custom-call(), custom_call_target="tpu_custom_call"', 0, 3 * ms],
                ["%fusion.2 = f32[8] fusion()", 2 * ms, 2 * ms],  # overlaps the kernel: busy once
                ["%gather.3 = f32[8] gather()", 6 * ms, 2 * ms],
            ],
        },
        "/device:TPU:1": {
            "XLA Modules": [["jit_local_query(3)", 0, 10 * ms]],
            "XLA Ops": [["%all-reduce.4 = f32[8] all-reduce()", 1 * ms, 1 * ms], ["%fusion.5", 5 * ms, 1 * ms]],
        },
        "/host:CPU": {"python3": [["PjitFunction(_long_query)", 4.5 * ms, 1 * ms], ["bench.wait", 0, 10 * ms]]},
    }}
    return Trace(raw, 0.0, 10 * ms)


def test_merge_unions_overlapping_intervals():
    assert merge([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_trace_reduction_on_a_synthetic_trace():
    tr = _synthetic_trace()
    assert tr.devices == ["/device:TPU:0", "/device:TPU:1"]
    assert tr.window_s == pytest.approx(0.010)
    # TPU:0 busy 0-4 and 6-8 ms (6 ms); TPU:1 busy 1-2 and 5-6 ms (2 ms).
    assert tr.busy_s() == pytest.approx((0.006 + 0.002) / 2)
    assert tr.idle_share() == pytest.approx(1 - 0.004 / 0.010)
    kernel = harness.load_reader(REPO, "fused_roofline").__globals__["is_kernel"]
    assert tr.op_seconds(kernel) == pytest.approx(0.003 / 2)  # mean over devices
    assert tr.op_seconds(lambda m, op: "_long_query" in m) == pytest.approx(0.002 / 2)
    assert tr.op_seconds(lambda m, op: "all-reduce" in op) == pytest.approx(0.001 / 2)
    gaps = tr.idle_gaps()
    assert gaps[0] == ["PjitFunction(_long_query)", pytest.approx(0.002)]
    assert [g[1] for g in gaps] == pytest.approx([0.002, 0.002])
    top = dict(tr.top_ops())
    assert top["jit_fused_query/%fused_query.1"] == pytest.approx(0.0015)
    # Clipping to a window cuts events at its edges.
    clipped = Trace(tr.raw, 1e6, 3e6)
    assert clipped.busy_s() == pytest.approx((0.002 + 0.001) / 2)


def test_trace_reduction_on_a_recorded_trace():
    rec = BENCH / "testdata" / "v5e_trace_excerpt.json"
    doc = json.loads(rec.read_text())
    tr = Trace(doc, *doc["window_ns"])
    expect = doc["expect"]
    assert tr.devices == expect["devices"]
    assert tr.busy_s() == pytest.approx(expect["busy_s"], abs=2e-9)  # the mask counts whole ns
    kernel = harness.load_reader(REPO, "fused_roofline").__globals__["is_kernel"]
    assert tr.op_seconds(kernel) == pytest.approx(expect["kernel_s"], rel=1e-9)
    # The same sums by hand, from the raw events.
    ops = [e for e in doc["planes"][expect["devices"][0]]["XLA Ops"]]
    lo, hi = doc["window_ns"]
    ivs = sorted((max(s, lo), min(s + d, hi)) for _, s, d in ops if s + d > lo and s < hi)
    assert sum(b - a for a, b in merge(ivs)) * 1e-9 == pytest.approx(expect["busy_s"], abs=2e-9)


# -- the reference and the control --------------------------------------------------------


def _scan(x, l, r):
    idx = np.array([a + int(np.argmin(x[a : b + 1])) for a, b in zip(l, r)], np.int32)
    return idx, x[idx]


@pytest.mark.parametrize("n", [1, 31, 32, 1000, 5000])
def test_reference_is_the_leftmost_minimum(n):
    rng = np.random.default_rng(n)
    x = rng.integers(0, 7, n).astype(np.float32)  # many ties
    ref = reference.Reference(x)
    l = rng.integers(0, n, 3000)
    r = np.minimum(l + rng.integers(0, n, 3000) % (1 + rng.integers(0, 2 * reference.BLOCK + 3, 3000)), n - 1)
    l = np.concatenate([l, [0, n - 1, 0]]).astype(np.int32)
    r = np.concatenate([r, [n - 1, n - 1, 0]]).astype(np.int32)
    gi, gv = ref.query(l, r)
    si, sv = _scan(x, l, r)
    assert np.array_equal(gi, si) and np.array_equal(gv, sv)


def test_control_values_fail_the_comparison():
    x = np.random.default_rng(0).random(1 << 14, dtype=np.float32)
    l, r = traffic.make_queries(np.random.default_rng(1), x.size, 2048, "mixed")
    ref = reference.Reference(x)
    ci, cv = reference.Reference(reference.control_values(x)).query(l, r)
    assert reference.wrong_answers(ref, l, r, ci, cv) > 1000
    gi, gv = ref.query(l, r)
    assert reference.wrong_answers(ref, l, r, gi, gv) == 0


# -- the control and the faults, each through the rest of a run ----------------------------


def _control_query(state, l, r):
    """The reference over bfloat16-rounded values, in the program's place."""
    x = np.asarray(state.x)
    ctl = reference.Reference(reference.control_values(x))
    idx, val = ctl.query(np.asarray(l), np.asarray(r))
    return idx, val


def _half_batch(orig):
    def dispatch(l, r, *a):
        l, r = np.asarray(l), np.asarray(r)
        h = max(l.size // 2, 1)
        idx, val = orig(l[:h], r[:h], *a)
        idx = np.concatenate([np.asarray(idx), np.zeros(l.size - h, np.int32)])
        val = np.concatenate([np.asarray(val), np.zeros(l.size - h, np.asarray(val).dtype)])
        return idx, val

    return dispatch


def _altered(orig):
    def dispatch(l, r, *a):
        idx, val = orig(l, r, *a)
        idx = np.array(idx)
        idx[0] += 1  # one answer altered where it is produced
        return idx, np.asarray(val)

    return dispatch


@pytest.mark.parametrize("fault", ["control", "half_batch", "altered_answer"])
def test_the_control_and_each_fault_come_out_not_correct(tmp_path, monkeypatch, fault):
    from repro.core import hybrid, registry

    if fault == "control":
        spec = registry.ENGINES["hybrid"]
        monkeypatch.setitem(registry.ENGINES, "hybrid", spec._replace(query=_control_query))
    else:
        wrap = _half_batch if fault == "half_batch" else _altered
        monkeypatch.setattr(hybrid, "dispatch_by_length", wrap(hybrid.dispatch_by_length))
    line, err = run_tiny(make_root(tmp_path))
    assert line["correct"] is False, err
    assert line["checks"]["wrong_answers"]["value"] > 0
    assert "rmqbench check: wrong_answers" in err.splitlines()[-2]


def _sharded_fault_main(root: str) -> None:
    """Entry of the four-device child: the sharded cell with its pmin
    exchange left out (each device keeps its own shard's answer)."""
    import jax

    assert len(jax.devices()) == 4
    jax.lax.pmin = lambda x, axis_name: x  # the exchange between chips left out
    line, err = run_tiny(make_root(Path(root), engine="sharded_hybrid", chips=4))
    print(json.dumps(line))


def test_the_sharded_cell_without_its_exchange_comes_out_not_correct(tmp_path):
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4", JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join([str(REPO / "src"), str(BENCH), env.get("PYTHONPATH", "")])
    code = f"import test_bench_harness as t; t._sharded_fault_main({str(tmp_path)!r})"
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=600, cwd=str(BENCH)
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["checks"]["wrong_answers"]["value"] > 0


# -- the entry point ------------------------------------------------------------------------


def _run_py(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hybrid_n26.batch_small",
         "--seed", str(2**31 + 99), "--seconds", "1", "--trace", "0"],
        cwd=str(cwd), env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_py_refuses_a_cpu_and_prints_no_result():
    proc = _run_py(REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_run_py_refuses_a_directory_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _run_py(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""

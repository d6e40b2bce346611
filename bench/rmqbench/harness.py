"""One run of one benchmark cell: set-up, a timed window, the check, the line.

Everything that belongs to one configuration, traffic mix or metric is data
found by name: ``BENCHMARK.json`` names the cell's configuration and traffic,
``bench/configs/<config>.json`` holds the deployment, ``bench/mixes/
<traffic>.json`` the traffic, and ``bench/metrics/<metric>.py`` the reader of
each metric. A run:

1. checks the chips (a TPU, as many as the cell asks for);
2. makes the array from the seed on the device, builds the engine the way
   ``launch/serve.py`` does (``registry.plan_for_serving``, then
   ``core.build.execute``), starts an ``RMQServer`` and warms every launch
   shape it can emit, then sends each client's first request once;
3. drives ``RMQServer.submit`` from the mix's clients for ``--seconds``,
   under the profiler when ``--trace 1``;
4. reads the device memory peak, frees the program's state and judges every
   answer against the plain reference (``reference.py``);
5. prints the checks on standard error and the result as the last line of
   standard output.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from collections import Counter
from pathlib import Path
from typing import List, NamedTuple, Optional

import numpy as np

from . import traffic
from .peaks import peak_row
from .reference import Reference

WAIT_PAST_CLOSE_S = 60.0


class Cell(NamedTuple):
    workload: dict  # the BENCHMARK.json entry
    config: dict  # bench/configs/<config>.json
    mix: dict  # bench/mixes/<traffic>.json
    metrics: List[dict]  # the BENCHMARK.json metric entries this cell reports
    chips: int


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_cell(root: Path, name: str, *, trace: bool) -> Cell:
    """Find a cell and its files by the names in ``root/BENCHMARK.json``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise ValueError(f"no workload {name!r} in BENCHMARK.json; have {sorted(by_name)}")
    w = by_name[name]
    conf_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf_entry["file"]).read_text())
    mix = traffic.check_mix(json.loads((root / "bench" / "mixes" / f"{w['traffic']}.json").read_text()))
    kind = "per_layer" if trace else "end_to_end"
    metrics = [m for m in bench[kind] if name in m.get("workloads", (name,))]
    return Cell(w, config, mix, metrics, int(w["chips"]))


def load_reader(root: Path, metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"rmqbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_chips(jax, chips: int) -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found {len(devs)}")


class Context:
    """What a metric's reader may read: the window's records, the server's
    counters and spans, the reduced device trace and the cell's data."""

    def __init__(self, cell: Cell, seed: int, seconds: float):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.config, self.mix = cell.config, cell.mix
        self.records: List[traffic.Record] = []
        self.t0 = self.t1 = 0.0
        self.setup_s = self.build_s = None
        self.peak_bytes: Optional[int] = None
        self.threshold: Optional[int] = None
        self.pool = None
        self.peaks = None  # the device's row of the peak table
        self.trace = None  # trace.Trace of the window (--trace 1)
        self.spans = []  # the program's spans that started in the window
        self.wrong_answers = 0
        self._registry = None
        self._before = {}

    # -- the server's registry, as deltas over the window --------------------

    def mark_registry(self, registry) -> None:
        self._registry = registry
        self._before = {
            ("c", name, tuple(sorted(c.labels.items()))): c.value for name, c in registry.counters()
        }
        self._before.update(
            {("h", name, tuple(sorted(h.labels.items()))): h.count for name, h in registry.histograms()}
        )

    def counter_delta(self, name: str, **labels) -> float:
        key = tuple(sorted((k, str(v)) for k, v in labels.items()))
        total = 0.0
        for n, c in self._registry.counters():
            if n == name and all(kv in c.labels.items() for kv in key):
                total += c.value - self._before.get(("c", n, tuple(sorted(c.labels.items()))), 0.0)
        return total

    def histogram_window(self, name: str, **labels) -> np.ndarray:
        """Observations made since ``mark_registry`` (exact while the
        histogram's reservoir holds every observation)."""
        out = []
        for n, h in self._registry.histograms():
            if n == name and all((k, str(v)) in h.labels.items() for k, v in labels.items()):
                vals = h.values()
                if h.count > h.capacity:
                    raise RuntimeError(f"histogram {name} overflowed its reservoir")
                out.extend(vals[self._before.get(("h", n, tuple(sorted(h.labels.items()))), 0) :])
        return np.asarray(out, float)

    # -- the window's requests -------------------------------------------------

    def latency_percentile_ms(self, p: float) -> Optional[float]:
        """Nearest-rank ``p``-th percentile of the latency of every request
        issued in the window, from when it was sent until its answer came;
        a refused, failed or unanswered request counts as infinitely late
        (None if the percentile lands on one)."""
        lat = sorted(rec.t_done - rec.t_submit for rec in self.records)
        if not lat:
            return None
        v = lat[max(math.ceil(p / 100.0 * len(lat)) - 1, 0)]
        return v * 1e3 if math.isfinite(v) else None

    def window_queries(self, t_from: float = -math.inf, t_to: float = math.inf):
        """(l, r) of each answered request submitted in ``[t_from, t_to)``."""
        for rec in self.records:
            if rec.ok and t_from <= rec.t_submit < t_to:
                yield self.pool[rec.client][rec.pool_idx]


def _profile_options(jax):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # no per-call Python events: they slow the host
    opts.host_tracer_level = 2
    return opts


def _block(jax, tree) -> None:
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            leaf.block_until_ready()


def run(
    root: Path,
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float,
    check_device: bool = True,
    out=None,
    err=None,
) -> int:
    """One run of ``workload``; prints the result line. Returns the exit code."""
    out = out if out is not None else sys.stdout
    err = err if err is not None else sys.stderr
    cell = load_cell(root, workload, trace=trace)
    readers = {m["name"]: load_reader(root, m["name"]) for m in cell.metrics}

    import jax

    if check_device:
        try:
            check_chips(jax, cell.chips)
        except NoChip as e:
            print(f"rmqbench: {e}", file=err)
            return 2
    ctx = Context(cell, seed, seconds)
    result = _run_cell(jax, cell, ctx, seed, seconds, trace, t_start)
    metrics = {}
    for m in cell.metrics:
        value = readers[m["name"]](ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    devs = jax.devices()[: cell.chips]
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": int(ctx.peak_bytes or 0),
    }
    line = {
        "correct": result["correct"],
        "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": metrics,
        "device": device,
    }
    if trace and ctx.trace is not None:
        device["busy_s"] = ctx.trace.busy_s()
        device["window_s"] = ctx.trace.window_s
        line["breakdown"] = ctx.trace.breakdown()
    line["checks"] = result["checks"]
    for k, v in result["diagnostics"].items():
        print(f"rmqbench: {k} = {v}", file=err)
    for k, v in result["checks"].items():
        print(f"rmqbench check: {k} = {v['value']} (limit {v['limit']})", file=err)
    err.flush()
    print(json.dumps(line), file=out, flush=True)
    return 0


def make_data(jax, config: dict, seed: int):
    """The array, from the seed, on the device in one jitted call."""
    import jax.numpy as jnp

    if config["value_dtype"] != "float32" or config["values"] != "uniform01":
        raise ValueError(f"unsupported values {config['values']}/{config['value_dtype']}")
    key_seed = int(np.random.SeedSequence(seed).generate_state(1, np.uint32)[0])
    make = jax.jit(
        lambda key: jax.random.uniform(key, (config["n"],), jnp.float32),
    )
    return make(jax.random.key(key_seed))


def _run_cell(jax, cell: Cell, ctx: Context, seed, seconds, trace, t_start) -> dict:
    from repro.core import build as build_mod
    from repro.core import registry
    from repro.launch.mesh import make_group_mesh
    from repro.obs import Tracer, set_tracer
    from repro.serve import RMQServer, ServeConfig, ServerOverloaded

    from . import trace as trace_mod

    config, mix = cell.config, cell.mix
    spec = registry.get(config["engine"])
    kw = {"threshold": config["threshold"]}
    if "kernel_config" in spec.build_kwargs:
        kw["kernel_config"] = config["kernel_config"]
    if config.get("mode") is not None:
        kw["mode"] = config["mode"]
    # The mesh is passed explicitly and never made ambient (``jax.set_mesh``):
    # the server's worker threads launch outside any such context, so a
    # warm-up under one would compile programs that the window never reuses.
    mesh = axes = None
    if spec.needs_mesh:
        devs = jax.devices()
        if len(devs) == cell.chips:
            mesh, axes = registry.default_mesh()
        else:
            mesh, axes = make_group_mesh(devs[: cell.chips]), ("shard",)
    tracer = Tracer(enabled=True, capacity=1 << 18) if trace else None
    if tracer is not None:
        set_tracer(tracer)  # before the server: it takes the tracer it is built with
    compiles = {"n": 0, "on": False}

    def on_compile(event, *_a, **_k):
        if compiles["on"] and event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    diagnostics = {}
    try:
        x = make_data(jax, config, seed)
        plan = registry.plan_for_serving(config["engine"], config["n"], mesh, axes, **kw)
        ctx.threshold = plan.meta.get("threshold")
        t = time.perf_counter()
        state = build_mod.execute(plan, x)
        _block(jax, state)
        ctx.build_s = time.perf_counter() - t
        scfg = ServeConfig(n=config["n"], **config["serve"])
        qfn = lambda l, r: spec.query(state, l, r)  # noqa: E731
        srv = RMQServer(qfn, scfg, warmup_bounds=build_mod.warmup_bounds(plan))
        srv.warmup()
        ctx.pool = traffic.request_pool(seed, config["n"], mix)
        ctx.peaks = peak_row(jax.devices()[0].device_kind) if jax.devices()[0].platform == "tpu" else None
        srv.start()
        try:
            # Each client's first request once, through the served path.
            warm = [srv.submit(*ctx.pool[c][0]) for c in range(mix["clients"])]
            for f in warm:
                f.result(timeout=600)
            gc.collect()
            ctx.mark_registry(srv.metrics)
            if tracer is not None:
                tracer.clear()
            answers = traffic.Answers()
            prof_dir = None
            if trace:
                prof_dir = tempfile.mkdtemp(prefix="rmqbench-trace-")
                jax.profiler.start_trace(prof_dir, profiler_options=_profile_options(jax))
            compiles["on"] = True
            p_anchor = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.anchor"):
                pass
            records, t0, t1 = traffic.closed_loop(
                srv.submit, ctx.pool, seconds, answers,
                wait_s=WAIT_PAST_CLOSE_S, overloaded=ServerOverloaded,
            )
            compiles["on"] = False
            if trace:
                jax.profiler.stop_trace()
        finally:
            srv.close(timeout=WAIT_PAST_CLOSE_S)
        ctx.records, ctx.t0, ctx.t1 = records, t0, t1
        ctx.setup_s = t0 - t_start
        ctx.peak_bytes = _peak_bytes(jax, cell.chips)
        if tracer is not None:
            ctx.spans = [s for s in tracer.spans() if t0 <= s.t0 < t1]
        if trace:
            ctx.trace = trace_mod.load(prof_dir, p_anchor, t0, t1)
            shutil.rmtree(prof_dir, ignore_errors=True)
        x_host = np.asarray(jax.device_get(x))
        del state, qfn, srv, x
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        if tracer is not None:
            set_tracer(None)
    gc.collect()
    diagnostics["compiles_in_window"] = compiles["n"]
    diagnostics["requests_in_window"] = len(ctx.records)
    diagnostics["answers_per_second"] = np.histogram(
        [rec.t_done - ctx.t0 for rec in ctx.records if rec.t_done <= ctx.t1],
        bins=max(int(round(ctx.t1 - ctx.t0)), 1), range=(0.0, ctx.t1 - ctx.t0),
    )[0].tolist()
    diagnostics["build_s"] = ctx.build_s
    diagnostics["setup_s"] = ctx.setup_s
    t = time.perf_counter()
    checks = judge(x_host, ctx.pool, answers, ctx.records)
    ctx.wrong_answers = checks["wrong_answers"]["value"]
    diagnostics["reference_s"] = time.perf_counter() - t
    attempted = len(ctx.records)
    failed = sum(1 for rec in ctx.records if not rec.ok)
    correct = attempted > 0 and all(v["value"] <= v["limit"] for v in checks.values())
    return {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": failed,
        "checks": checks,
        "diagnostics": diagnostics,
    }


def _peak_bytes(jax, chips: int) -> Optional[int]:
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def judge(x_host: np.ndarray, pool, answers: traffic.Answers, records) -> dict:
    """Every answer against the plain reference; the numbers compared, each
    with its limit (the most it may read)."""
    ref = Reference(x_host)
    answered = Counter((rec.client, rec.pool_idx) for rec in records if rec.ok)
    gold, wrong = {}, 0
    for key, (idx, val) in answers.first.items():
        l, r = pool[key[0]][key[1]]
        gold[key] = ref.query(l, r)
        # Every later answer to this entry equalled the first unless listed.
        wrong += _count_wrong(gold[key], idx, val) * answered[key]
    for key, idx, val in answers.differing:
        wrong += _count_wrong(gold[key], idx, val) - _count_wrong(gold[key], *answers.first[key])
    unanswered = sum(1 for rec in records if not rec.ok)
    return {
        "wrong_answers": {"value": int(wrong), "limit": 0},
        "failed_requests": {"value": int(unanswered), "limit": 0},
    }


def _count_wrong(gold, idx, val) -> int:
    gi, gv = gold
    idx, val = np.asarray(idx), np.asarray(val)
    if idx.shape != gi.shape or val.shape != gv.shape:
        return int(gi.size)
    return int(np.count_nonzero((idx != gi) | (val != gv)))

"""Observability tests: span tracer, metrics registry, serve-layer wiring.

Covers the DESIGN.md §14 contract directly: same-thread ambient nesting and
explicit cross-thread parenting, ring-buffer overflow keeping the newest
spans, the disabled tracer allocating nothing on the hot path (tracemalloc
probe, the hybrid dispatch's spans included), Chrome-trace JSON schema,
exact histogram percentiles; the profiler mirror of enabled spans and the
gc/compile hooks a global tracer holds; and — end to end through a real
threaded ``RMQServer`` — that every served request exports a complete span
chain, that each launch spans its cycle (dispatch phases under ``launch``;
``wait``/``d2h``/``scatter``/``finish`` under ``flush``), and that the
metrics registry exactly reconciles with the ``ServeStats`` snapshot
rendered from it.
"""

import gc
import json
import threading
import tracemalloc
from pathlib import Path

import numpy as np
import pytest

from repro.core import ref
from repro.obs import (
    NULL_TRACER,
    MetricsRegistry,
    Tracer,
    current_span,
    merge_snapshots,
    set_tracer,
    verify_request_chains,
)
from repro.obs import trace as obs_trace
from repro.serve import RMQServer, ServeConfig


@pytest.fixture
def tracer():
    """A fresh enabled tracer installed globally for the test's duration."""
    t = Tracer(enabled=True, capacity=4096)
    prev = set_tracer(t)
    try:
        yield t
    finally:
        set_tracer(prev)


@pytest.fixture
def local_tracer():
    """A fresh enabled tracer that is not the global one, so it holds no
    process hooks: it records exactly the spans a test opens."""
    return Tracer(enabled=True, capacity=4096)


def _oracle_engine(x):
    def qfn(l, r):
        idx = ref.rmq_ref(x, l, r).astype(np.int32)
        return idx, x[idx]

    return qfn


# --- tracer core ------------------------------------------------------------


def test_span_ambient_nesting_same_thread(local_tracer):
    tracer = local_tracer
    with tracer.span("outer") as outer:
        assert current_span() is outer
        with tracer.span("inner") as inner:
            assert inner.parent_id == outer.span_id
        assert current_span() is outer
    assert current_span() is None
    names = [s.name for s in tracer.spans()]
    assert names == ["inner", "outer"]  # finish order: innermost first


def test_span_forced_root_and_explicit_parent(tracer):
    with tracer.span("ambient"):
        root = tracer.start("request", parent=0)  # 0 = force a root
        assert root.parent_id is None
        child = tracer.start("queue", parent=root)
        assert child.parent_id == root.span_id
        by_id = tracer.start("resolve", parent=root.span_id)
        assert by_id.parent_id == root.span_id


def test_cross_thread_parenting_is_explicit(tracer):
    """Ambient context never leaks across threads; parent= carries chains."""
    root = tracer.start("flush", parent=0)
    seen = {}

    def worker():
        seen["ambient"] = current_span()  # fresh thread: nothing current
        with tracer.span("launch", parent=root) as sp:
            seen["parent"] = sp.parent_id
            seen["thread"] = sp.thread

    t = threading.Thread(target=worker, name="pool-w9")
    t.start()
    t.join()
    tracer.finish(root)
    assert seen["ambient"] is None
    assert seen["parent"] == root.span_id
    assert seen["thread"] == "pool-w9"


def test_ring_buffer_overflow_keeps_newest():
    t = Tracer(enabled=True, capacity=8)
    for i in range(20):
        t.instant(f"s{i}")
    spans = t.spans()
    assert len(spans) == 8
    assert [s.name for s in spans] == [f"s{i}" for i in range(12, 20)]
    assert t.dropped == 12
    t.clear()
    assert t.spans() == [] and t.dropped == 0


def test_span_ctx_records_error_attr(local_tracer):
    tracer = local_tracer
    with pytest.raises(ValueError):
        with tracer.span("launch"):
            raise ValueError("boom")
    (sp,) = tracer.spans()
    assert sp.attrs["error"] == "ValueError"
    assert sp.t1 is not None


def test_set_attr_noop_outside_span(tracer):
    obs_trace.set_attr("k", 1)  # nothing current: must not raise
    with tracer.span("s") as sp:
        obs_trace.set_attr("k", 2)
    assert sp.attrs == {"k": 2}


def _numpy_path(l, r):
    """A stand-in engine path: answers on the host, no device."""
    return np.asarray(l, np.int32), np.asarray(r, np.float32)


def test_disabled_tracer_allocates_nothing():
    from repro.core import hybrid

    t = NULL_TRACER
    assert obs_trace.get_tracer() is NULL_TRACER
    l = np.arange(64, dtype=np.int32)
    uniform, mixed = l + 1, np.where(l % 2 == 0, l + 1, 63)

    def dispatch(r):
        hybrid.dispatch_by_length(l, r, 8, _numpy_path, _numpy_path, np.float32)

    # Warm every code path once, then assert the steady state is alloc-free.
    with t.span("x"):
        pass
    t.start("x")
    t.instant("x")
    dispatch(uniform)
    dispatch(mixed)
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for i in range(200):
            with t.span("hot"):
                pass
            s = t.start("hot")
            s.set_attr("k", 1)
            t.finish(s)
            t.instant("hot")
            if i % 10 == 0:  # prepare/h2d/enqueue, and wait/merge when mixed
                dispatch(uniform)
                dispatch(mixed)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    growth = sum(
        st.size_diff
        for st in after.compare_to(before, "lineno")
        if st.size_diff > 0 and any("obs/trace" in f.filename for f in st.traceback)
    )
    assert growth == 0, f"disabled tracer allocated {growth} bytes"


def test_chrome_trace_export_schema(local_tracer, tmp_path):
    tracer = local_tracer
    with tracer.span("flush", attrs={"reason": "size"}):
        with tracer.span("launch", attrs={"engine": "hybrid", "cfg": object()}):
            pass
    path = tmp_path / "t.json"
    n = tracer.export(str(path))
    assert n == 2
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    ms = [e for e in evs if e["ph"] == "M"]
    assert {e["name"] for e in xs} == {"flush", "launch"}
    for e in xs:
        assert e["pid"] == 1 and e["cat"] == "repro"
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert isinstance(e["args"]["span_id"], int)
    launch = next(e for e in xs if e["name"] == "launch")
    flush = next(e for e in xs if e["name"] == "flush")
    assert launch["args"]["parent_id"] == flush["args"]["span_id"]
    assert launch["args"]["engine"] == "hybrid"
    assert isinstance(launch["args"]["cfg"], str)  # non-scalar attrs stringified
    assert ms and all(e["args"]["name"] for e in ms)  # thread names labelled


# --- the profiler's clock and the process hooks -----------------------------


def _host_event_names(prof_dir: Path) -> list:
    import jax

    (path,) = sorted(prof_dir.rglob("*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(path))
    return [
        e.name
        for plane in pd.planes
        if plane.name.startswith("/host")
        for line in plane.lines
        for e in line.events
    ]


def test_enabled_spans_land_in_the_profiler_trace(local_tracer, tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with local_tracer.span("x"):
            with local_tracer.span("y"):
                pass
        root = local_tracer.start("flush", parent=0)  # crosses threads: buffer only
        local_tracer.finish(root)
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(tmp_path)
    assert names.count("rmq.x") == 1 and names.count("rmq.y") == 1
    assert "rmq.flush" not in names
    assert [s.name for s in local_tracer.spans()] == ["y", "x", "flush"]


def _fresh_compile():
    import jax
    import jax.numpy as jnp

    jax.jit(lambda v: v * 3 + 1)(jnp.arange(3.0)).block_until_ready()


def test_global_tracer_records_gc_and_compile_spans(tracer):
    tracer.clear()
    gc.collect()
    full = [s for s in tracer.spans() if s.name == "gc" and s.attrs["generation"] == 2]
    assert len(full) == 1
    assert full[0].parent_id is None and full[0].t1 >= full[0].t0
    assert isinstance(full[0].attrs["collected"], int)
    _fresh_compile()
    compiles = [s for s in tracer.spans() if s.name == "compile"]
    assert compiles
    for s in compiles:
        assert s.t1 - s.t0 == pytest.approx(s.attrs["seconds"], abs=1e-3)
    assert set(obs_trace.HOOK_SPANS) == {"gc", "compile"}


def test_swapping_the_tracer_out_removes_its_hooks():
    prev = set_tracer(None)
    base = len(gc.callbacks)
    first, second = Tracer(enabled=True), Tracer(enabled=True)
    try:
        set_tracer(first)
        assert len(gc.callbacks) == base + 1
        set_tracer(second)  # a swap moves the one hook to the new tracer
        assert len(gc.callbacks) == base + 1
        gc.collect()
        _fresh_compile()
        assert not [s for s in first.spans() if s.name in obs_trace.HOOK_SPANS]
        assert {"gc", "compile"} <= {s.name for s in second.spans()}
        set_tracer(None)
        assert len(gc.callbacks) == base
        second.clear()
        gc.collect()
        _fresh_compile()
        assert second.spans() == []
    finally:
        set_tracer(prev)


# --- metrics registry -------------------------------------------------------


def test_counter_gauge_identity_and_labels():
    reg = MetricsRegistry()
    a = reg.counter("reqs", outcome="ok")
    b = reg.counter("reqs", outcome="ok")
    c = reg.counter("reqs", outcome="bad")
    assert a is b and a is not c
    a.inc()
    a.inc(2)
    c.inc(5)
    assert a.value == 3 and c.value == 5
    assert reg.counter_total("reqs") == 8
    assert reg.counter_total("reqs", outcome="bad") == 5
    g = reg.gauge("depth")
    g.set(4)
    g.add(-1)
    assert g.value == 3


def test_histogram_exact_percentiles_match_numpy():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    rng = np.random.default_rng(3)
    vals = rng.random(999) * 0.1
    for v in vals:
        h.observe(float(v))
    assert h.count == 999
    assert h.sum == pytest.approx(float(vals.sum()))
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(float(np.percentile(vals, q)))
    assert h.percentiles((50, 99)) == pytest.approx(
        [float(np.percentile(vals, 50)), float(np.percentile(vals, 99))]
    )
    # Bucket counts account for every observation (last bucket = +inf).
    snap = reg.snapshot()["histograms"]["lat"][0]
    assert sum(snap["buckets"]["counts"]) == 999
    assert len(snap["buckets"]["counts"]) == len(snap["buckets"]["le"]) + 1


def test_histogram_empty_and_reservoir_bound():
    h = MetricsRegistry().histogram("lat", capacity=64)
    assert h.percentile(99) == 0.0 and h.mean() == 0.0
    for i in range(1000):
        h.observe(i * 1e-3)
    assert h.count == 1000  # count/sum stay exact past capacity
    assert h.sum == pytest.approx(sum(i * 1e-3 for i in range(1000)))
    assert len(h.values()) == 64  # reservoir stays bounded


def test_merge_snapshots_relabels_per_replica():
    regs = {str(i): MetricsRegistry() for i in range(2)}
    regs["0"].counter("reqs").inc(3)
    regs["1"].counter("reqs").inc(4)
    regs["1"].histogram("lat").observe(0.5)
    merged = merge_snapshots({k: r.snapshot() for k, r in regs.items()})
    rows = merged["counters"]["reqs"]
    assert {(r["labels"]["replica"], r["value"]) for r in rows} == {("0", 3.0), ("1", 4.0)}
    assert merged["histograms"]["lat"][0]["labels"]["replica"] == "1"


# --- serve-layer wiring -----------------------------------------------------


def _serve_some(tracer, n=512, reqs=12):
    rng = np.random.default_rng(0)
    x = rng.random(n).astype(np.float32)
    cfg = ServeConfig(deadline_s=0.002, max_batch=256, n=n, workers=2)
    srv = RMQServer(_oracle_engine(x), cfg)
    futs = []
    with srv:
        for i in range(reqs):
            a = rng.integers(0, n, 5)
            b = rng.integers(0, n, 5)
            futs.append(srv.submit(np.minimum(a, b), np.maximum(a, b)))
        for f in futs:
            f.result(timeout=60)
    return srv


def test_server_exports_complete_request_chains(tracer):
    srv = _serve_some(tracer, reqs=12)
    complete, problems = verify_request_chains(tracer.spans())
    assert problems == []
    assert complete == 12
    # The same chains survive a Chrome-trace round trip.
    complete2, problems2 = verify_request_chains(tracer.to_chrome_trace())
    assert (complete2, problems2) == (12, [])
    launches = [s for s in tracer.spans() if s.name == "launch"]
    assert launches and all("engine" in s.attrs and "pool" in s.attrs for s in launches)
    del srv


def test_served_hybrid_launches_span_their_cycle(tracer):
    import jax.numpy as jnp

    from repro.core import hybrid

    n = 4096
    x = np.random.default_rng(3).random(n).astype(np.float32)
    state = hybrid.build(jnp.asarray(x), threshold=64)
    srv = RMQServer(lambda l, r: hybrid.query(state, l, r), ServeConfig(n=n, max_batch=64, workers=1))
    l = np.arange(0, 2048, 64)
    batches = {"uniform": l + 10, "mixed": np.where(np.arange(l.size) % 2 == 0, l + 10, l + 1000)}
    with srv:
        for r in batches.values():  # one request per flush
            res = srv.submit(l, r).result(timeout=120)
            np.testing.assert_array_equal(res.idx, ref.rmq_ref(x, l, r))
    spans = tracer.spans()
    assert verify_request_chains(spans) == (2, [])
    kids = {}
    for s in spans:
        kids.setdefault(s.parent_id, []).append(s)
    flushes = sorted((s for s in spans if s.name == "flush"), key=lambda s: s.t0)
    assert len(flushes) == 2
    for fs, kind in zip(flushes, batches):
        cycle = [s.name for s in sorted(kids[fs.span_id], key=lambda s: s.t0)]
        assert cycle == ["coalesce", "launch", "wait", "d2h", "scatter", "finish"]
        (launch,) = [s for s in kids[fs.span_id] if s.name == "launch"]
        phases = [s.name for s in sorted(kids[launch.span_id], key=lambda s: s.t0)]
        if kind == "mixed":  # each half launches, then comes back, in turn; the answer stays on the host
            assert phases == ["prepare"] + ["h2d", "enqueue"] * 2 + ["wait", "merge"] * 2
        else:
            assert phases == ["prepare", "h2d", "enqueue"]
        assert "split_short" not in launch.attrs and "short" in launch.attrs
        finish = next(s for s in kids[fs.span_id] if s.name == "finish")
        assert fs.t1 >= finish.t1  # the flush ends after its last child


def test_verify_request_chains_flags_gaps(tracer):
    _serve_some(tracer, reqs=4)
    rows = [
        {"name": s.name, "span_id": s.span_id, "parent_id": s.parent_id, "attrs": dict(s.attrs)}
        for s in tracer.spans()
    ]
    broken = [r for r in rows if r["name"] != "scatter"]
    complete, problems = verify_request_chains(broken)
    assert complete == 0 and problems  # every chain now reports its gap
    assert all("missing" in p for p in problems)


def test_metrics_reconcile_with_servestats(tracer):
    srv = _serve_some(tracer, reqs=16)
    st = srv.stats()
    reg = srv.metrics
    assert (
        reg.counter_total("serve_requests_total", outcome="served")
        == st.served_requests
    )
    assert reg.counter_total("serve_queries_total") == st.served_queries
    assert reg.counter_total("serve_batches_total") == st.n_batches
    assert (
        reg.counter_total("serve_requests_total", outcome="rejected")
        == st.rejected_requests
    )
    assert reg.counter_total("serve_launches_total", pool="primary") >= st.n_batches
    h = reg.histogram("serve_total_s")
    assert h.count == st.served_requests
    assert h.percentile(50) == pytest.approx(st.p50_total_s)
    assert h.percentile(99) == pytest.approx(st.p99_total_s)
    assert reg.histogram("serve_queue_wait_s").percentile(50) == pytest.approx(
        st.p50_queue_s
    )


def test_server_traces_are_off_by_default():
    """No tracer installed -> the server records nothing and allocates no
    span objects (the global is the disabled singleton)."""
    assert obs_trace.get_tracer() is NULL_TRACER or not obs_trace.get_tracer().enabled
    srv = _serve_some(NULL_TRACER, reqs=3)
    assert srv.stats().served_requests == 3


def test_durable_observer_composes_user_trace_and_fault(tracer, tmp_path):
    """DurableEngine._observer stacks all three concerns deterministically:
    the user observer fires first for every stage, the ``patch_applied``
    trace marker lands at the apply_deltas boundary, and the fault site
    fires LAST — so user callback and trace marker both witness a completed
    stage even on an apply that injection kills."""
    import jax.numpy as jnp

    from repro import update as update_mod
    from repro.fault import DurableEngine

    rng = np.random.default_rng(7)
    x = rng.random(256).astype(np.float32)
    events = []

    def fault(site):
        events.append(("fault", site))
        if site == "patch_apply":
            # The trace marker must already be committed when injection runs.
            assert any(s.name == "patch_applied" for s in tracer.spans())

    d = DurableEngine.create(
        "sparse_table", jnp.asarray(x), str(tmp_path / "dur"), fault=fault
    )
    log = update_mod.DeltaLog()
    log.point(3, 0.25)
    d.apply(log, observer=lambda stage, state: events.append(("user", stage)))
    d.close()

    user_stages = [s for kind, s in events if kind == "user"]
    assert "apply_deltas" in user_stages  # user observer saw every stage
    i_user = events.index(("user", "apply_deltas"))
    i_fault = events.index(("fault", "patch_apply"))
    assert i_user < i_fault  # user first, injection last
    names = [s.name for s in tracer.spans()]
    assert "journal_append" in names and "patch_applied" in names
    # No trace, no fault -> the user observer passes through IDENTICALLY.
    set_tracer(None)
    try:
        d2 = DurableEngine(d.online, str(tmp_path / "dur"))
        user = lambda stage, state: None
        assert d2._observer(user) is user
        assert d2._observer(None) is None
    finally:
        set_tracer(tracer)


def test_deadline_trajectory_single_entry_rendering():
    from repro.serve.server import ServeStats

    base = dict(
        served_requests=1, served_queries=1, rejected_requests=0, n_batches=1,
        mean_batch_requests=1.0, mean_batch_queries=1.0, padded_sizes=(1,),
        p50_queue_s=0.0, p99_queue_s=0.0, p50_total_s=0.0, p99_total_s=0.0,
        throughput_qps=1.0,
    )
    one = ServeStats(**base, deadline_trajectory=(0.0015,))
    s = one.summary()
    assert "1.50 ms" in s and "1 adjusted flush" in s  # no 1.50->1.50 arrow
    two = ServeStats(**base, deadline_trajectory=(0.0015, 0.0008))
    assert "->" in two.summary() or "→" in two.summary()
    none = ServeStats(**base, deadline_trajectory=())
    assert "adaptive deadline" not in none.summary()

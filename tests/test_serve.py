"""Serve-subsystem tests: micro-batcher, server loop, registry capabilities.

The pure coalesce/pad/scatter core is tested directly against the numpy
oracle; the threaded server is tested with generous deadlines (no timing
races) and with a numpy-only fake engine where device execution would only
add noise. End-to-end scatter-back under mixed range distributions runs
through the real registry ``hybrid`` engine.
"""

import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hybrid, ref, registry
from repro.serve import (
    RMQServer,
    ServeConfig,
    ServerClosed,
    ServerOverloaded,
    batcher,
)
from repro.serve.workload import make_queries


def _oracle_engine(x):
    """A (l, r) -> (idx, val) engine that is literally the oracle."""

    def qfn(l, r):
        idx = ref.rmq_ref(x, l, r).astype(np.int32)
        return idx, x[idx]

    return qfn


def _bounded(rng, n, b):
    a = rng.integers(0, n, b)
    c = rng.integers(0, n, b)
    return np.minimum(a, c).astype(np.int32), np.maximum(a, c).astype(np.int32)


# --- pure batcher core ------------------------------------------------------


def test_bucket_powers_of_two():
    assert [batcher.bucket(b) for b in (1, 2, 3, 4, 5, 127, 128, 129)] == [
        1, 2, 4, 4, 8, 128, 128, 256,
    ]
    with pytest.raises(ValueError):
        batcher.bucket(0)


def test_coalesce_pads_to_bucket_and_preserves_order():
    ls = [np.array([1, 2, 3], np.int32), np.array([7], np.int32), np.array([4, 5], np.int32)]
    rs = [np.array([9, 9, 9], np.int32), np.array([8], np.int32), np.array([6, 7], np.int32)]
    mb = batcher.coalesce(ls, rs)
    assert mb.n_queries == 6
    assert mb.l.shape == (8,)  # bucket(6)
    assert mb.spans == ((0, 3), (3, 1), (4, 2))
    np.testing.assert_array_equal(mb.l[:6], [1, 2, 3, 7, 4, 5])
    np.testing.assert_array_equal(mb.r[:6], [9, 9, 9, 8, 6, 7])
    np.testing.assert_array_equal(mb.l[6:], 0)  # trivial (0, 0) pad queries
    np.testing.assert_array_equal(mb.r[6:], 0)


def test_scatter_back_roundtrip_vs_oracle():
    rng = np.random.default_rng(0)
    n = 512
    x = rng.integers(0, 4, n).astype(np.float32)  # tie-heavy
    ls, rs = zip(*[_bounded(rng, n, b) for b in (3, 8, 1, 5)])
    mb = batcher.coalesce(ls, rs)
    idx = ref.rmq_ref(x, mb.l, mb.r)
    parts = batcher.scatter_back(mb, idx, x[idx])
    assert len(parts) == 4
    for (l, r), (pi, pv) in zip(zip(ls, rs), parts):
        gold = ref.rmq_ref(x, l, r)
        np.testing.assert_array_equal(pi, gold)
        np.testing.assert_array_equal(pv, x[gold])


# --- server: coalescing, deadline, padding buckets --------------------------


def test_microbatcher_coalesces_across_clients():
    rng = np.random.default_rng(1)
    n = 256
    x = rng.random(n).astype(np.float32)
    # Generous deadline: all requests submitted well inside it -> ONE batch.
    with RMQServer(_oracle_engine(x), ServeConfig(deadline_s=0.5, max_batch=1024, n=n)) as srv:
        subs = [(*_bounded(rng, n, 4 + c), c) for c in range(3)]
        futs = [(l, r, srv.submit(l, r)) for l, r, _ in subs]
        results = [(l, r, f.result(timeout=30)) for l, r, f in futs]
    st = srv.stats()
    assert st.n_batches == 1, st
    assert st.served_requests == 3
    assert st.served_queries == 4 + 5 + 6
    assert st.padded_sizes == (16,)  # bucket(15)
    for l, r, res in results:
        np.testing.assert_array_equal(res.idx, ref.rmq_ref(x, l, r))


def test_deadline_flush_without_filling_batch():
    x = np.arange(64, 0, -1).astype(np.float32)
    cfg = ServeConfig(deadline_s=0.05, max_batch=4096, n=64)
    with RMQServer(_oracle_engine(x), cfg) as srv:
        t0 = time.perf_counter()
        res = srv.submit(np.array([3], np.int32), np.array([60], np.int32)).result(timeout=30)
        wall = time.perf_counter() - t0
    # Flushed by the deadline (batch nowhere near max_batch), not stuck.
    assert srv.stats().n_batches == 1
    assert res.timing.queue_s >= 0.04  # held for coalescing ~the full deadline
    assert wall < 10
    np.testing.assert_array_equal(res.idx, [60])  # min of descending array


def test_padding_bucket_selection_and_bounded_shapes():
    rng = np.random.default_rng(2)
    n = 128
    x = rng.random(n).astype(np.float32)
    # deadline=0: every request flushes alone -> padded shape == bucket(size).
    with RMQServer(_oracle_engine(x), ServeConfig(deadline_s=0.0, max_batch=64, n=n)) as srv:
        for size in (1, 3, 5, 9, 33):
            l, r = _bounded(rng, n, size)
            srv.submit(l, r).result(timeout=30)
    st = srv.stats()
    assert st.padded_sizes == (1, 4, 8, 16, 64)
    # The jit-cache bound: every shape a power of two, at most log2(max)+1 of them.
    assert all(s & (s - 1) == 0 for s in st.padded_sizes)
    assert len(st.padded_sizes) <= int(np.log2(batcher.bucket(64))) + 1


def test_max_batch_splits_flushes():
    rng = np.random.default_rng(3)
    n = 64
    x = rng.random(n).astype(np.float32)
    # 3 requests of 4 queries against max_batch=8: the third overflows -> 2 batches.
    with RMQServer(_oracle_engine(x), ServeConfig(deadline_s=0.5, max_batch=8, n=n)) as srv:
        futs = []
        for _ in range(3):
            l, r = _bounded(rng, n, 4)
            futs.append(srv.submit(l, r))
        for f in futs:
            f.result(timeout=30)
    st = srv.stats()
    assert st.n_batches == 2
    assert max(st.padded_sizes) <= 8


def test_regime_split_counts_in_stats():
    """Per-launch (short, long) sub-batch sizes surface in ServeStats, with
    the batcher's trivial (0, 0) pad queries excluded from the counts."""
    rng = np.random.default_rng(7)
    n = 2048
    x = rng.random(n, dtype=np.float32)
    s = hybrid.build(jnp.asarray(x), 128, use_kernels=False, threshold=16)
    qfn = lambda l, r: hybrid.query(s, l, r)

    # 5 short (len <= 16) + 3 long queries in one request: bucket(8) = 8, no
    # pad; then a 3-query all-short request: bucket(3) = 4, one pad query.
    l1 = np.array([0, 5, 9, 100, 200, 300, 400, 500], np.int32)
    r1 = np.array([3, 20, 9, 115, 210, 1300, 1400, 1500], np.int32)
    l2 = np.array([1, 2, 3], np.int32)
    r2 = np.array([4, 5, 6], np.int32)
    with RMQServer(qfn, ServeConfig(deadline_s=0.0, max_batch=64, n=n)) as srv:
        srv.submit(l1, r1).result(timeout=60)
        srv.submit(l2, r2).result(timeout=60)
    st = srv.stats()
    assert st.regime_splits == ((5, 3), (3, 0))
    assert st.short_queries == 8 and st.long_queries == 3
    assert st.mixed_batches == 1
    assert "regime split 8 short / 3 long" in st.summary()


def test_regime_splits_empty_for_single_path_engine():
    x = np.arange(32, 0, -1).astype(np.float32)
    with RMQServer(_oracle_engine(x), ServeConfig(deadline_s=0.0, n=32)) as srv:
        srv.submit(np.array([0], np.int32), np.array([31], np.int32)).result(timeout=30)
    st = srv.stats()
    assert st.regime_splits == ()
    assert st.short_queries == 0 and st.mixed_batches == 0
    assert "regime split" not in st.summary()


def test_warmup_bounds_from_plan_compiles_each_regime():
    """Plan-derived warmup probes: every probe batch the plan prescribes is
    issued at every padded size, and the probes route one per regime."""
    from repro.core import build as build_mod

    n = 512
    plan = registry.plan_for_serving("hybrid", n, threshold=32)
    x = np.random.default_rng(0).random(n, dtype=np.float32)
    calls = []

    def qfn(l, r):
        calls.append((l.size, int(r[0] - l[0] + 1)))
        return _oracle_engine(x)(l, r)

    srv = RMQServer(
        qfn,
        ServeConfig(max_batch=8, n=n),
        warmup_bounds=build_mod.warmup_bounds(plan),
    )
    srv.warmup()
    # Sizes 1, 2, 4, 8; per size one length-32 (short regime) and one
    # length-n (long regime) probe.
    assert calls == [
        (s, ln) for s in (1, 2, 4, 8) for ln in (32, n)
    ]


def test_scatter_back_mixed_dists_through_hybrid_engine():
    """End-to-end through the real registry engine under all three §6.4 regimes."""
    rng = np.random.default_rng(4)
    n = 4096
    x = rng.integers(0, 9, n).astype(np.float32)  # dense ties
    spec = registry.get("hybrid")
    state = registry.build_for_serving("hybrid", jnp.asarray(x))
    qfn = lambda l, r: spec.query(state, l, r)

    results = []
    lock = threading.Lock()

    def client(c, dist):
        crng = np.random.default_rng(100 + c)
        for _ in range(5):
            l, r = make_queries(crng, n, 1 + crng.integers(1, 12), dist)
            with lock:
                results.append((l, r, srv.submit(l, r)))

    with RMQServer(qfn, ServeConfig(deadline_s=0.02, max_batch=256, n=n)) as srv:
        threads = [
            threading.Thread(target=client, args=(c, d))
            for c, d in enumerate(("small", "medium", "large"))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        done = [(l, r, f.result(timeout=120)) for l, r, f in results]
    assert len(done) == 15
    for l, r, res in done:
        gold = ref.rmq_ref(x, l, r)
        np.testing.assert_array_equal(res.idx, gold)
        np.testing.assert_array_equal(res.val, x[gold])
    assert srv.stats().n_batches < 15  # actually coalesced across clients


# --- adaptive deadline -------------------------------------------------------


def test_adaptive_deadline_shrinks_under_load_then_grows_when_idle():
    """Size-triggered flushes halve the effective deadline (down to the
    floor); near-empty deadline flushes grow it back. The trajectory is
    recorded per flush in ServeStats."""
    rng = np.random.default_rng(11)
    n = 64
    x = rng.random(n).astype(np.float32)
    cfg = ServeConfig(
        deadline_s=0.008,
        deadline_min_s=0.001,
        deadline_max_s=0.032,
        adaptive_deadline=True,
        max_batch=8,
        n=n,
    )
    with RMQServer(_oracle_engine(x), cfg) as srv:
        for _ in range(4):  # 8-query requests: every flush is size-triggered
            l, r = _bounded(rng, n, 8)
            srv.submit(l, r).result(timeout=30)
        # Idle: a single 1-query request flushes by deadline and grows it.
        l, r = _bounded(rng, n, 1)
        srv.submit(l, r).result(timeout=30)
    traj = srv.stats().deadline_trajectory
    assert traj[:4] == (
        pytest.approx(0.004),
        pytest.approx(0.002),
        pytest.approx(0.001),
        pytest.approx(0.001),  # clamped at deadline_min_s
    )
    assert traj[4] == pytest.approx(0.0015)  # grew by 1.5x from the floor


def test_adaptive_deadline_defaults_and_validation():
    cfg = ServeConfig(deadline_s=0.008, adaptive_deadline=True)
    assert cfg.deadline_bounds() == (0.001, 0.032)
    with pytest.raises(ValueError):
        ServeConfig(deadline_s=0.0, adaptive_deadline=True)
    with pytest.raises(ValueError):
        ServeConfig(deadline_s=0.002, deadline_min_s=0.004, adaptive_deadline=True)
    with pytest.raises(ValueError):
        ServeConfig(deadline_s=0.002, deadline_max_s=0.001, adaptive_deadline=True)


def test_fixed_deadline_records_no_trajectory():
    x = np.ones(8, np.float32)
    with RMQServer(_oracle_engine(x), ServeConfig(deadline_s=0.0, n=8)) as srv:
        one = np.zeros(1, np.int32)
        srv.submit(one, one).result(timeout=30)
    assert srv.stats().deadline_trajectory == ()


# --- server: edges, admission control, validation ---------------------------


def test_empty_request_resolves_immediately():
    x = np.ones(8, np.float32)
    with RMQServer(_oracle_engine(x), ServeConfig(n=8)) as srv:
        res = srv.submit(np.zeros(0, np.int64), np.zeros(0, np.int64)).result(timeout=5)
        assert res.idx.shape == (0,) and res.val.shape == (0,)
    assert srv.stats().n_batches == 0  # never reached the engine


def test_admission_control_backpressure():
    x = np.ones(8, np.float32)
    release = threading.Event()

    def slow_engine(l, r):
        release.wait(30)
        idx = ref.rmq_ref(x, l, r).astype(np.int32)
        return idx, x[idx]

    cfg = ServeConfig(deadline_s=0.0, max_batch=4, max_pending=2, n=8)
    with RMQServer(slow_engine, cfg) as srv:
        one = np.zeros(1, np.int32)
        f1 = srv.submit(one, one)
        f2 = srv.submit(one, one)
        with pytest.raises(ServerOverloaded):
            srv.submit(one, one)  # 2 in flight >= max_pending
        release.set()
        f1.result(timeout=30)
        f2.result(timeout=30)
        # Completion drains in-flight: admission opens again.
        srv.submit(one, one).result(timeout=30)
    st = srv.stats()
    assert st.rejected_requests == 1
    assert st.served_requests == 3


def test_submit_validation():
    x = np.ones(16, np.float32)
    with RMQServer(_oracle_engine(x), ServeConfig(max_batch=8, n=16)) as srv:
        one = np.zeros(1, np.int32)
        with pytest.raises(ValueError):  # l > r
            srv.submit(np.array([5], np.int32), np.array([2], np.int32))
        with pytest.raises(ValueError):  # negative
            srv.submit(np.array([-1], np.int32), one)
        with pytest.raises(ValueError):  # r >= n
            srv.submit(one, np.array([16], np.int32))
        with pytest.raises(TypeError):  # float bounds
            srv.submit(np.array([0.5]), np.array([1.5]))
        with pytest.raises(ValueError):  # oversized vs max_batch
            srv.submit(np.zeros(9, np.int32), np.zeros(9, np.int32))
        with pytest.raises(ValueError):  # shape mismatch
            srv.submit(np.zeros(2, np.int32), np.zeros(3, np.int32))
    # Without a configured n, the int32 index range is still enforced.
    with RMQServer(_oracle_engine(x), ServeConfig()) as unbounded:
        with pytest.raises(ValueError):
            unbounded.submit(np.zeros(1, np.int32), np.array([2**31], np.int64))


def test_submit_after_close_raises():
    x = np.ones(8, np.float32)
    srv = RMQServer(_oracle_engine(x), ServeConfig(n=8)).start()
    srv.close()
    with pytest.raises(ServerClosed):
        srv.submit(np.zeros(1, np.int32), np.zeros(1, np.int32))


def test_engine_failure_fails_batch_but_server_survives():
    calls = []

    def flaky(l, r):
        calls.append(len(l))
        if len(calls) == 1:
            raise RuntimeError("engine down")
        idx = np.zeros(len(l), np.int32)
        return idx, np.zeros(len(l), np.float32)

    with RMQServer(flaky, ServeConfig(deadline_s=0.0, max_batch=8, n=8)) as srv:
        one = np.zeros(1, np.int32)
        bad = srv.submit(one, one)
        with pytest.raises(RuntimeError):
            bad.result(timeout=30)
        ok = srv.submit(one, one).result(timeout=30)  # still serving
        assert ok.idx.shape == (1,)


# --- query-path dtype guard (hybrid dispatch boundary) ----------------------


def test_dispatch_rejects_float_bounds():
    with pytest.raises(TypeError):
        hybrid.dispatch_by_length(
            np.array([0.0]), np.array([1.0]), 4, None, None, np.float32
        )


def test_dispatch_rejects_out_of_int32_bounds():
    with pytest.raises(ValueError):
        hybrid.dispatch_by_length(
            np.array([0], np.int64), np.array([2**31], np.int64), 4, None, None, np.float32
        )
    with pytest.raises(ValueError):
        hybrid.dispatch_by_length(
            np.array([-1], np.int64), np.array([3], np.int64), 4, None, None, np.float32
        )


def test_make_queries_int32_boundary():
    rng = np.random.default_rng(0)
    for dist in ("small", "medium", "large"):
        l, r = make_queries(rng, 1 << 16, 64, dist)
        assert l.dtype == np.int32 and r.dtype == np.int32
        assert (l >= 0).all() and (l <= r).all() and (r < (1 << 16)).all()
    with pytest.raises(ValueError):
        make_queries(rng, 2**31 + 5, 4, "small")


def test_make_queries_mixed_draws_every_regime():
    n = 1 << 20
    l, r = make_queries(np.random.default_rng(3), n, 600, "mixed")
    assert l.dtype == np.int32 and r.dtype == np.int32
    assert (l >= 0).all() and (l <= r).all() and (r < n).all()
    length = r.astype(np.int64) - l + 1
    assert (length < 4 * n**0.3).sum() > 100  # small
    assert ((length > n**0.6 / 4) & (length < 4 * n**0.6)).sum() > 100  # medium
    assert (length > 8 * n**0.6).sum() > 100  # large


# --- registry capability metadata -------------------------------------------


def test_serveable_names_excludes_oracles():
    names = registry.serveable_names()
    assert "exhaustive" not in names
    assert set(names) <= set(registry.names())
    for flagship in ("hybrid", "sharded_hybrid", "fused128", "distributed"):
        assert flagship in names


def test_capability_metadata_drives_flags():
    sh = registry.get("sharded_hybrid")
    assert "shard_batch" in sh.modes and sh.needs_mesh
    assert {"block_size", "threshold", "mode"} <= set(sh.build_kwargs)
    hy = registry.get("hybrid")
    assert "threshold" in hy.build_kwargs and not hy.needs_mesh and hy.modes == ()
    assert registry.get("distributed").needs_mesh
    assert "block_size" in registry.get("fused128").build_kwargs


def test_build_for_serving_validates_kwargs():
    x = jnp.arange(256.0)
    with pytest.raises(ValueError):
        registry.build_for_serving("lca", x, threshold=7)  # undeclared kwarg
    with pytest.raises(ValueError):
        registry.build_for_serving("sharded_hybrid", x, mode="shard_everything")
    with pytest.raises(ValueError):
        registry.build_for_serving("exhaustive", x)  # not serveable
    state = registry.build_for_serving("hybrid", x, threshold=32)
    assert state.threshold == 32


def test_distributed_registry_engine_matches_oracle():
    rng = np.random.default_rng(6)
    n = 777
    x = rng.integers(0, 5, n).astype(np.float32)
    spec = registry.get("distributed")
    s = spec.build(jnp.asarray(x))
    l, r = _bounded(rng, n, 50)
    idx, val = spec.query(s, l, r)
    gold = ref.rmq_ref(x, l, r)
    np.testing.assert_array_equal(np.asarray(idx), gold)
    np.testing.assert_array_equal(np.asarray(val), x[gold])


# --- PR 7 regressions -------------------------------------------------------


def test_coalesce_rejects_mismatched_inputs():
    """Silent-truncation regression: coalesce used to zip() unequal l/r lists
    (dropping the excess requests' queries on the floor) and accept ragged
    per-request bounds. Both must be loud errors now."""
    good = [np.array([1, 2], np.int32)]
    with pytest.raises(ValueError, match="l-arrays vs"):
        batcher.coalesce(good + [np.array([3], np.int32)], [np.array([4, 5], np.int32)])
    with pytest.raises(ValueError, match="equal-length"):
        batcher.coalesce(good, [np.array([4, 5, 6], np.int32)])
    with pytest.raises(ValueError, match="1-D"):
        batcher.coalesce([np.array([[1]], np.int32)], [np.array([[2]], np.int32)])


def test_poisson_client_streams_do_not_collide_across_seeds():
    """Seed-collision regression: client c under base seed s used to draw
    from default_rng(s + c), so (seed=0, client=1) and (seed=1, client=0)
    shared a stream. Sequence seeding must keep every (seed, client) pair
    independent."""
    from repro.serve.workload import run_poisson_clients

    def collect(seed):
        reqs = {}

        def make_request(rng, c):
            reqs.setdefault(c, []).append(rng.integers(0, 1 << 30, 4).tolist())
            return np.zeros(1, np.int32), np.zeros(1, np.int32)

        def submit(_l, _r):
            return None

        run_poisson_clients(2, 3, 0.0, make_request, submit, seed=seed)
        return reqs

    a = collect(0)
    b = collect(1)
    assert a[1] != b[0]  # the old seed+c scheme made exactly these equal
    assert a[0] != a[1] and b[0] != b[1]  # clients within a run independent


def test_submit_min_version_gates_on_stale_servers():
    from repro.serve import StaleVersion
    from repro.update import DeltaLog
    from repro.update.engines import make_online

    x = np.arange(64.0, dtype=np.float32)
    online = make_online("sparse_table", x)
    with RMQServer(online=online, config=ServeConfig(deadline_s=1e-4)) as srv:
        l = np.array([0], np.int32)
        r = np.array([63], np.int32)
        res = srv.submit(l, r, min_version=0).result(timeout=60)
        assert res.version == 0
        with pytest.raises(StaleVersion):
            srv.submit(l, r, min_version=1)
        log = DeltaLog()
        log.point(3, -1.0)
        srv.submit_update(log).result(timeout=60)
        res = srv.submit(l, r, min_version=1).result(timeout=60)
        assert res.version >= 1 and res.idx[0] == 3
    # min_version is meaningless without an MVCC engine.
    with RMQServer(_oracle_engine(x), ServeConfig(deadline_s=1e-4)) as srv:
        with pytest.raises(ValueError):
            srv.submit(l, r, min_version=0)


# --- the launch pipeline: launch k+1 issued before launch k completes --------


@functools.partial(jax.jit, static_argnames="spin")
def _spin_echo(l, r, spin):
    """(l, r) as the answers (idx, val), after ``spin`` steps of device work,
    so the call returns device arrays well before they are ready."""
    acc = jax.lax.fori_loop(0, spin, lambda i, c: c * 0.5 + 1.0, jnp.zeros(1 << 14, jnp.float32))
    z = (acc[0] * 0).astype(jnp.int32)
    return l + z, (r + z).astype(jnp.float32)


class _GatedEngine:
    """A fake engine answering ``(idx, val) = (l, r)`` from ``_spin_echo``,
    as unready device arrays or, with ``host``, as numpy arrays. Every call
    waits for ``release``, so a test can queue microbatches behind the
    first (``entered`` is set once one is waiting); then it records which
    of the ``watched`` futures were done."""

    def __init__(self, host: bool = False, spin: int = 100_000):
        self.host, self.spin = host, spin
        self.entered, self.release = threading.Event(), threading.Event()
        self.watched = []
        self.calls = []  # per engine call: done flags of ``watched``
        jax.block_until_ready(_spin_echo(jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.int32), spin=spin))

    def __call__(self, l, r):
        self.entered.set()
        self.release.wait(60)
        self.calls.append([f.done() for f in self.watched])
        idx, val = _spin_echo(jnp.asarray(l), jnp.asarray(r), spin=self.spin)
        return (np.asarray(idx), np.asarray(val)) if self.host else (idx, val)


def _request(i):
    l = np.arange(4 * i, 4 * i + 4, dtype=np.int32)
    return l, l + 1


def _wait_until(cond, timeout=30.0):
    t_end = time.perf_counter() + timeout
    while not cond():
        assert time.perf_counter() < t_end, "timed out"
        time.sleep(0.001)


def _queue_two_launches(srv, eng):
    """Request 0 parked in the engine's first call, request 1 flushed into
    the microbatch queue behind it -> their futures."""
    futs = [srv.submit(*_request(0))]
    assert eng.entered.wait(30)
    futs.append(srv.submit(*_request(1)))
    _wait_until(lambda: srv._mbq.qsize() == 1)
    eng.watched = futs
    return futs


def _assert_own_answers(futs):
    for i, f in enumerate(futs):
        res = f.result(timeout=60)
        l, r = _request(i)
        assert np.array_equal(res.idx, l) and np.array_equal(res.val, r.astype(np.float32))


@pytest.mark.parametrize("host", [False, True], ids=["device-arrays", "host-arrays"])
def test_next_launch_is_issued_before_the_held_one_completes(host):
    """With device answers still computing, the engine is called for launch
    1 while launch 0's future is unresolved, and launch 1 counts as
    overlapped. With host answers launch 0 is completed at once, before
    launch 1 is issued: the serial cycle, never counted."""
    eng = _GatedEngine(host=host)
    with RMQServer(eng, ServeConfig(deadline_s=0.0, max_batch=4, n=64)) as srv:
        futs = _queue_two_launches(srv, eng)
        eng.release.set()
        _assert_own_answers(futs)
        overlapped = srv.metrics.counter("serve_launches_overlapped_total").value
    assert eng.calls == [[False, False], [host, False]]
    assert overlapped == (0 if host else 1)
    assert srv.stats().n_batches == 2


@pytest.mark.parametrize("kind", ["error", "crash"])
def test_issue_fault_on_the_next_launch_leaves_the_held_one_answered(kind):
    """A fault as launch 1 is issued, with launch 0 in flight: launch 0 is
    answered, launch 1's request is retried (after the worker restarts,
    for a crash) and answered too; no future is left unresolved."""
    from repro.fault import FaultPlan, FaultSpec

    eng = _GatedEngine()
    plan = FaultPlan(seed=5, specs={"worker_query": FaultSpec(at=(2,), kind=kind)})
    cfg = ServeConfig(deadline_s=0.0, max_batch=4, n=64, max_retries=1, worker_backoff_s=0.005)
    with RMQServer(eng, cfg, fault_plan=plan) as srv:
        futs = _queue_two_launches(srv, eng)
        eng.release.set()
        _assert_own_answers(futs)
        st = srv.stats()
    # The fault fires before the engine call: the engine saw launch 0, then
    # launch 1's retry, once launch 0 was answered.
    assert eng.calls == [[False, False], [True, False]]
    assert st.retried_requests == 1 and st.failed_requests == 0
    assert st.worker_restarts == (1 if kind == "crash" else 0)


def test_close_completes_the_launch_in_flight():
    """close() while launch 0 is in flight and launch 1 queued: the worker
    completes both before it stops, so both are answered, not closed."""
    eng = _GatedEngine()
    srv = RMQServer(eng, ServeConfig(deadline_s=0.0, max_batch=4, n=64)).start()
    futs = _queue_two_launches(srv, eng)
    closer = threading.Thread(target=srv.close)
    closer.start()
    _wait_until(lambda: srv._mbq.qsize() == 2)  # launch 1, then the stop
    eng.release.set()
    closer.join(60)
    assert not closer.is_alive()
    _assert_own_answers(futs)
    assert srv.metrics.counter("serve_launches_overlapped_total").value == 1

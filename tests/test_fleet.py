"""Replica-fleet tests: rollout propagation, bounded lag, read-your-writes,
regime routing, and crash -> restore -> rejoin (DESIGN.md §11).

In-process tests run small ``hybrid`` fleets on the default single device
(device-group carving is a mesh-engine concern, covered by the 8-fake-device
subprocess test at the bottom). Every query is verified against the host
oracle of the version it was answered at — the same invariant the serve and
chaos suites enforce.
"""

import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from repro.fault.inject import FaultPlan, FaultSpec
from repro.serve import ServeConfig
from repro.serve.fleet import FleetConfig, FleetSession, RMQFleet, run_fleet_soak
from repro.update import DeltaLog

N = 2048


def _x(seed=0, n=N):
    return np.random.default_rng(seed).standard_normal(n).astype(np.float32)


def _cfg(**kw):
    kw.setdefault("replicas", 3)
    kw.setdefault("max_version_lag", 2)
    kw.setdefault(
        "server", ServeConfig(workers=1, deadline_s=2e-4, max_retries=8)
    )
    return FleetConfig(**kw)


def _point(i, v):
    log = DeltaLog()
    log.point(i, v)
    return log


def _verify(res, ox, l, r):
    for j in range(l.size):
        seg = ox[l[j] : r[j] + 1]
        assert res.idx[j] == l[j] + int(np.argmin(seg))


# --- config ------------------------------------------------------------------


def test_fleet_config_validation():
    with pytest.raises(ValueError):
        FleetConfig(replicas=0)
    with pytest.raises(ValueError):
        FleetConfig(max_version_lag=0)
    with pytest.raises(ValueError):
        FleetConfig(replicas=2, affinities=("short",))  # wrong arity
    with pytest.raises(ValueError):
        FleetConfig(replicas=2, affinities=("short", "sideways"))
    assert FleetConfig(replicas=4).resolved_affinities() == (
        "short", "long", "short", "long",
    )
    assert FleetConfig(replicas=1).resolved_affinities() == (None,)


def test_session_floor_is_monotonic():
    s = FleetSession()
    assert s.last_vid == -1
    s.observe(3)
    s.observe(1)  # stale observation must not lower the floor
    assert s.last_vid == 3


# --- rollouts ----------------------------------------------------------------


def test_rollout_reaches_every_replica_and_respects_lag_bound():
    x = _x()
    fleet = RMQFleet.build("hybrid", x, config=_cfg())
    try:
        cur = x.copy()
        expected = {fleet.head_vid: cur.copy()}
        for k in range(6):
            i, v = 37 * (k + 1) % N, float(-10.0 - k)
            res = fleet.submit_update(_point(i, v)).result(timeout=60)
            cur[i] = np.float32(v)
            expected[res.version] = cur.copy()
        assert fleet.wait_settled(timeout=60)
        head = fleet.head_vid
        assert head == 6
        # Every replica converged to the head and vids stayed aligned.
        for rep in fleet.replicas:
            assert rep.active
            assert rep.engine.current_vid == head
        assert fleet.tracker.max_lag_seen <= fleet.config.max_version_lag
        # Each replica answers the head oracle through its own server.
        rng = np.random.default_rng(1)
        l = rng.integers(0, N, 16).astype(np.int32)
        r = np.minimum(N - 1, l + rng.integers(0, N // 2, 16)).astype(np.int32)
        for rep in fleet.replicas:
            res = rep.server.submit(l, r, min_version=head).result(timeout=60)
            assert res.version == head
            _verify(res, expected[head], l, r)
    finally:
        fleet.close()


def test_update_future_resolves_at_first_publish_and_raises_session_floor():
    fleet = RMQFleet.build("hybrid", _x(), config=_cfg())
    try:
        sess = fleet.session()
        res = fleet.submit_update(_point(5, -50.0), session=sess).result(timeout=60)
        assert res.version == 1
        # The ack point moved the floor before the future resolved.
        assert sess.last_vid == 1
    finally:
        fleet.close()


def test_append_rollout_raises_routing_floor():
    x = _x()
    fleet = RMQFleet.build("hybrid", x, config=_cfg(replicas=2))
    try:
        tail = np.full(8, -99.0, np.float32)
        log = DeltaLog()
        log.append(tail)
        res = fleet.submit_update(log).result(timeout=60)
        grown = np.concatenate([x, tail])
        # A query past the old length is only valid at the grown version; the
        # front door must route it to a replica that has published it.
        l = np.array([0], np.int32)
        r = np.array([grown.shape[0] - 1], np.int32)
        out = fleet.submit(l, r).result(timeout=60)
        assert out.version >= res.version
        _verify(out, grown, l, r)
        # Beyond the head is a client error, not a routing wait.
        with pytest.raises(ValueError):
            fleet.submit(l, np.array([grown.shape[0]], np.int32))
    finally:
        fleet.close()


def test_read_your_writes_under_forced_lag():
    """One replica is made artificially slow to apply; a session that awaited
    its update must still read it back immediately (routed to a fresh
    replica), every time."""
    x = _x()
    fleet = RMQFleet.build("hybrid", x, config=_cfg(replicas=2, max_version_lag=4))
    try:
        slow = fleet.replicas[1].engine
        real_apply = slow.apply

        def slow_apply(deltas, **kw):
            time.sleep(0.15)
            return real_apply(deltas, **kw)

        slow.apply = slow_apply  # instance attribute shadows the bound method
        sess = fleet.session()
        cur = x.copy()
        for k in range(3):
            i, v = 101 * (k + 1) % N, float(-20.0 - k)
            res = fleet.submit_update(_point(i, v), session=sess).result(timeout=60)
            cur[i] = np.float32(v)
            assert sess.last_vid == res.version
            l = np.array([max(0, i - 3)], np.int32)
            r = np.array([min(N - 1, i + 3)], np.int32)
            out = fleet.submit(l, r, session=sess).result(timeout=60)
            # Never answered below the session floor, and correct at its
            # version (which must include the session's own write).
            assert out.version >= res.version
            _verify(out, cur, l, r)
        assert fleet.wait_settled(timeout=60)
    finally:
        fleet.close()


# --- regime routing ----------------------------------------------------------


def test_regime_routing_prefers_affinity_pools():
    x = _x()
    fleet = RMQFleet.build(
        "hybrid", x, config=_cfg(replicas=2, threshold=32), threshold=32
    )
    try:
        assert fleet.threshold == 32
        assert [rep.affinity for rep in fleet.replicas] == ["short", "long"]
        rng = np.random.default_rng(2)
        for _ in range(8):  # clearly short batches: lengths <= 8
            l = rng.integers(0, N - 8, 4).astype(np.int32)
            r = (l + rng.integers(0, 8, 4)).astype(np.int32)
            _verify(fleet.submit(l, r).result(timeout=60), x, l, r)
        for _ in range(8):  # clearly long batches: lengths >= 256
            l = rng.integers(0, N - 512, 4).astype(np.int32)
            r = (l + 256 + rng.integers(0, 256, 4)).astype(np.int32)
            _verify(fleet.submit(l, r).result(timeout=60), x, l, r)
        st = fleet.stats()
        assert st.requests == 16
        assert st.affinity_hits == 16 and st.affinity_misses == 0
        assert st.routed == (8, 8)  # short pool got the short half, long the long
    finally:
        fleet.close()


def test_majority_regime_classifies_mixed_batches():
    fleet = RMQFleet.build("hybrid", _x(), config=_cfg(replicas=2, threshold=32))
    try:
        l = np.zeros(3, np.int32)
        assert fleet._classify(l, np.array([1, 2, 500], np.int32)) == "short"
        assert fleet._classify(l, np.array([1, 500, 600], np.int32)) == "long"
    finally:
        fleet.close()


# --- crash / restore ---------------------------------------------------------


def test_mid_rollout_crash_auto_revives_with_vid_continuity(tmp_path):
    """The rollout_apply fault kills one replica mid-rollout; auto-revive
    restores it from its WAL (checkpoint + journal, then fleet-history
    catch-up) and it rejoins at the fleet head with its vid timeline
    intact."""
    x = _x()
    # 4th check = first replica picking up rollout 2 (3 replicas).
    plan = FaultPlan(0, {"rollout_apply": FaultSpec(at=(4,))})
    fleet = RMQFleet.build(
        "hybrid", x, config=_cfg(), durable_root=str(tmp_path), fault_plan=plan
    )
    try:
        cur = x.copy()
        expected = {0: cur.copy()}
        for k in range(5):
            i, v = 53 * (k + 1) % N, float(-30.0 - k)
            res = fleet.submit_update(_point(i, v)).result(timeout=60)
            cur[i] = np.float32(v)
            expected[res.version] = cur.copy()
        assert plan.fired()["rollout_apply"] == 1
        # Auto-revive runs on a daemon thread; give it a bounded window.
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            st = fleet.stats()
            if st.restores >= 1 and st.active == 3:
                break
            time.sleep(0.05)
        st = fleet.stats()
        assert st.crashes == 1 and st.restores == 1 and st.active == 3
        assert fleet.wait_settled(timeout=60)
        head = fleet.head_vid
        for rep in fleet.replicas:
            # first_vid continuity: the restored engine continued the SAME
            # timeline (vid == number of rollouts), not a fresh one from 0.
            assert rep.engine.current_vid == head == 5
        l = np.arange(0, 64, dtype=np.int32)
        r = l + 32
        for rep in fleet.replicas:
            res = rep.server.submit(l, r, min_version=head).result(timeout=60)
            _verify(res, expected[head], l, r)
    finally:
        fleet.close()


def test_external_crash_then_restore_catches_up_from_history(tmp_path):
    x = _x()
    fleet = RMQFleet.build(
        "hybrid", x, config=_cfg(), durable_root=str(tmp_path)
    )
    try:
        cur = x.copy()
        res = fleet.submit_update(_point(7, -40.0)).result(timeout=60)
        cur[7] = np.float32(-40.0)
        assert fleet.wait_settled(timeout=60)
        fleet.crash_replica(1)
        assert not fleet.replicas[1].active
        assert 1 not in fleet.tracker.vids()  # dead keys can't wedge the barrier
        # Updates continue without the dead replica (fanout excludes it).
        for k in range(3):
            i, v = 211 * (k + 1) % N, float(-41.0 - k)
            fleet.submit_update(_point(i, v)).result(timeout=60)
            cur[i] = np.float32(v)
        assert fleet.wait_settled(timeout=60)
        fleet.restore_replica(1)
        rep = fleet.replicas[1]
        assert rep.active and rep.restores == 1
        assert rep.engine.current_vid == fleet.head_vid == 4
        l = np.array([0], np.int32)
        r = np.array([N - 1], np.int32)
        res = rep.server.submit(l, r, min_version=4).result(timeout=60)
        _verify(res, cur, l, r)
        # And it takes part in the next rollout normally.
        fleet.submit_update(_point(3, -99.0)).result(timeout=60)
        cur[3] = np.float32(-99.0)
        assert fleet.wait_settled(timeout=60)
        assert rep.engine.current_vid == 5
    finally:
        fleet.close()


def test_restore_replica_requires_durable_root():
    fleet = RMQFleet.build("hybrid", _x(), config=_cfg(replicas=2))
    try:
        fleet.crash_replica(1)
        with pytest.raises(RuntimeError):
            fleet.restore_replica(1)
        # The in-memory fleet keeps serving on the survivor.
        l = np.array([0], np.int32)
        out = fleet.submit(l, np.array([100], np.int32)).result(timeout=60)
        assert out.idx.shape == (1,)
    finally:
        fleet.close()


# --- acceptance soak ---------------------------------------------------------


def test_fleet_soak_in_process():
    """The check.sh gate's soak, scaled down: mutate-while-serving with an
    injected mid-rollout crash AND an external crash + restore; zero lost,
    zero mismatches, zero RYW violations, lag within bound."""
    report = run_fleet_soak(
        engine="hybrid", replicas=3, n=1 << 11, requests=60, updates=4, seed=3
    )
    assert report.ok, report.summary()
    assert report.crashes >= 2 and report.restores >= 2


_CHILD_FLEET8 = textwrap.dedent(
    """
    from repro.serve.fleet import run_fleet_soak
    report = run_fleet_soak(
        engine="sharded_hybrid", replicas=3, n=4096, requests=48, updates=4,
        qbatch=4, seed=1, max_lag=2,
    )
    assert report.ok, report.summary()
    print("FLEET8_OK", report.summary())
    """
)


def _run_child(code):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    return subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=420,
    )


def test_sharded_fleet_on_8_device_mesh():
    """3 sharded_hybrid replicas on disjoint device groups carved from an
    8-fake-device mesh: full soak with crash + restore, oracle-verified."""
    out = _run_child(_CHILD_FLEET8)
    assert "FLEET8_OK" in out.stdout, out.stderr[-3000:]


_CHILD_PLACEMENT = textwrap.dedent(
    """
    import tempfile
    import jax, numpy as np
    from repro.serve import ServeConfig
    from repro.serve.fleet import FleetConfig, RMQFleet
    from repro.update import DeltaLog

    def devices(rep):
        leaves = jax.tree_util.tree_leaves(rep.engine.store.current.state)
        return {d.id for a in leaves if isinstance(a, jax.Array) for d in a.devices()}

    x = np.random.default_rng(0).standard_normal(2048).astype(np.float32)
    cfg = FleetConfig(replicas=3, max_version_lag=2, server=ServeConfig(workers=1, max_retries=8))
    fleet = RMQFleet.build("hybrid", x, config=cfg, durable_root=tempfile.mkdtemp())
    try:
        assert [devices(r) for r in fleet.replicas] == [{0}, {1}, {2}]
        log = DeltaLog().point(5, -9.0)
        log.append(np.ones(200, np.float32))  # growth: leaves re-uploaded whole
        fleet.submit_update(log).result(timeout=120)
        assert fleet.wait_settled(timeout=120)
        fleet.crash_replica(2)
        fleet.restore_replica(2)
        assert [devices(r) for r in fleet.replicas] == [{0}, {1}, {2}]
        for rep in fleet.replicas:
            res = rep.server.submit(
                np.array([0], np.int32), np.array([2247], np.int32), min_version=1
            ).result(timeout=60)
            assert res.idx[0] == 5, res.idx
        print("PLACEMENT_OK")
    finally:
        fleet.close()
    """
)


def test_single_device_replicas_build_and_serve_on_their_own_device():
    """Replica i of a single-device engine lives on device i — after build,
    after a publish that re-uploads its leaves, and after crash + restore."""
    out = _run_child(_CHILD_PLACEMENT)
    assert "PLACEMENT_OK" in out.stdout, out.stderr[-3000:]

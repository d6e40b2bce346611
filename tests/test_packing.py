"""Packed (value, index) word encoding + its engine integration (§13).

Covers the ISSUE's packed-structure acceptance surface:

* encoding properties — order isomorphism (word ``min`` == exact leftmost
  argmin), round-trips, extreme keys, duplicate runs, n=1, packed32 misfits;
* quantized bucket collisions — the exact fallback must resolve in-bucket
  ties bit-identically to the unpacked oracle;
* online overflow semantics — a batch the build-time spec cannot encode
  triggers a structural rebuild (never a wrong patch), bit-identical to a
  from-scratch packed build of the mutated array;
* durable round-trips — the concrete ``PackSpec`` survives checkpoint +
  restore, including after an overflow rebuild re-biased the key range;
* cache schema v3 — layout-scoped calibration/tuning slots and the v2
  migration;
* an 8-fake-device subprocess sweep — packed mesh engines bit-identical to
  the single-host oracle, packed halos and patches included.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import block_rmq, calib_cache, packing, sparse_table
from repro.core import build as build_mod
from repro.kernels import tuning
from repro.update.deltas import DeltaLog
from repro.update.engines import make_online


def _oracle(x: np.ndarray, l: np.ndarray, r: np.ndarray):
    idx = np.empty(l.shape, np.int64)
    for k, (a, b) in enumerate(zip(l, r)):
        idx[k] = a + int(np.argmin(x[a : b + 1]))  # argmin = leftmost
    return idx, x[idx]


def _random_ranges(rng, n: int, m: int):
    l = rng.integers(0, n, m)
    r = rng.integers(0, n, m)
    return np.minimum(l, r), np.maximum(l, r)


# --- encoding properties ------------------------------------------------------


@pytest.mark.parametrize("layout", ["packed64", "packed32"])
@pytest.mark.parametrize(
    "data",
    [
        "float_dupes",
        "int_extremes",
        "all_equal",
        "descending",
        "single",
    ],
)
def test_word_min_is_exact_leftmost_argmin(layout, data):
    """min over packed words == the leftmost exact argmin, on adversarial
    key sets: duplicate runs, negative keys, int32 extremes, n=1."""
    rng = np.random.default_rng(3)
    if data == "float_dupes":
        x = rng.choice(np.array([-2.5, -1.0, 0.5, 3.75], np.float32), 257)
    elif data == "int_extremes":
        x = rng.integers(-1000, 1000, 256).astype(np.int32)
        x[17] = -1000
        x[200] = -1000  # duplicated min: leftmost must win
    elif data == "all_equal":
        x = np.full(64, -7.0, np.float32)
    elif data == "descending":
        x = np.arange(100, 0, -1).astype(np.float32)
    else:
        x = np.array([42.0], np.float32)
    n = x.shape[0]
    if layout == "packed32" and x.dtype == np.float32:
        pytest.skip("float keys span the full bitcast range; packed32 is int-range data")
    spec = packing.spec_for(jnp.asarray(x), n, layout)
    words = packing.pack_np(spec, x, np.arange(n, dtype=np.int32))
    for _ in range(50):
        a, b = sorted(rng.integers(0, n, 2))
        w = words[a : b + 1].min()
        want = a + int(np.argmin(x[a : b + 1]))
        assert packing.unpack_idx_np(spec, np.array([w]))[0] == want
        got_v = packing.unpack_val_np(spec, np.array([w]))[0]
        assert got_v == x[want]


def test_int32_min_max_keys_roundtrip():
    """The full int32 key range survives pack/unpack exactly (packed64)."""
    x = np.array([np.iinfo(np.int32).min, 0, np.iinfo(np.int32).max], np.int32)
    spec = packing.spec_for(jnp.asarray(x), 3, "packed64")
    w = packing.pack_np(spec, x, np.arange(3, dtype=np.int32))
    assert list(packing.unpack_val_np(spec, w)) == list(x)
    assert list(packing.unpack_idx_np(spec, w)) == [0, 1, 2]
    assert w[0] == w.min()  # int32 min is the smallest key


def test_pad_word_never_wins():
    """pad_word is the word-domain maximum: a real word always beats it."""
    x = np.array([np.iinfo(np.int32).max], np.int32)
    for layout in ("packed64", "packed32"):
        spec = packing.spec_for(jnp.asarray(x), 128, layout)
        w = packing.pack_np(spec, x, np.zeros(1, np.int32))
        assert w[0] < packing.pad_word(spec)


def test_packed32_misfit_is_loud():
    """A key range packed32 cannot hold raises at spec time (explicit
    layout) and at pack time (post-build out-of-range writes) — never a
    silent wrong encoding."""
    wide = jnp.asarray(np.array([-(2**30), 2**30], np.int32))
    with pytest.raises(ValueError):
        packing.spec_for(wide, 2, "packed32")
    narrow = np.array([5, 9, 7], np.int32)
    spec = packing.spec_for(jnp.asarray(narrow), 3, "packed32")
    with pytest.raises(OverflowError):
        packing.pack_np(
            spec, np.array([np.iinfo(np.int32).max], np.int32), np.zeros(1, np.int32)
        )


def test_spec_for_auto_resolution():
    """auto -> packed32 when the key span fits, else packed64; deterministic."""
    narrow = jnp.asarray(np.arange(100, dtype=np.int32))
    s1 = packing.spec_for(narrow, 100, "auto")
    assert s1.layout == "packed32"
    assert s1 == packing.spec_for(narrow, 100, "auto")
    floats = jnp.asarray(np.random.default_rng(0).standard_normal(100).astype(np.float32))
    assert packing.spec_for(floats, 100, "auto").layout == "packed64"


# --- quantized collisions -----------------------------------------------------


def test_quantized_bucket_collisions_resolve_exactly():
    """Values packed into the SAME bucket (spread far below the bucket
    width) must still answer with the exact leftmost argmin — the fallback
    compares raw values, the bucket only prunes."""
    rng = np.random.default_rng(11)
    n = 1 << 10
    # A wide coarse ramp + per-element jitter far below bucket resolution:
    # many in-bucket collisions, including across block boundaries.
    x = (np.repeat(np.linspace(0, 1000, 8), n // 8) + rng.random(n) * 1e-4).astype(
        np.float32
    )
    s = build_mod.build("hybrid", jnp.asarray(x), packed="quantized", use_kernels=False)
    from repro.core import hybrid

    l, r = _random_ranges(rng, n, 256)
    qi, qv = hybrid.query(s, l, r)
    oi, ov = _oracle(x, l, r)
    np.testing.assert_array_equal(np.asarray(qi), oi)
    np.testing.assert_array_equal(np.asarray(qv), ov)


def test_quantized_value_drift_patches_without_rebuild():
    """Quantized bucket clipping is weakly monotone, so value writes far
    outside the build-time grid still PATCH (never rebuild) and stay exact."""
    rng = np.random.default_rng(5)
    n = 512
    x = rng.random(n, dtype=np.float32)
    online = make_online("hybrid", jnp.asarray(x), packed="quantized")
    log = DeltaLog()
    log.point(37, -1e6)  # far below qmin: clips to bucket 0
    log.point(300, 1e6)  # far above: clips to the top bucket
    res = online.apply(log)
    assert res.patched
    xm = x.copy()
    xm[37], xm[300] = -1e6, 1e6
    l, r = _random_ranges(rng, n, 128)
    qi, qv = online.query(online.store.current.state, l, r)
    oi, ov = _oracle(xm, l, r)
    np.testing.assert_array_equal(np.asarray(qi), oi)
    np.testing.assert_array_equal(np.asarray(qv), ov)


# --- online overflow -> structural rebuild -----------------------------------


def _leaves(tree):
    return [l for l in jax.tree_util.tree_leaves(tree) if isinstance(l, jax.Array)]


def _assert_bit_identical(state, want_state):
    got, want = _leaves(state), _leaves(want_state)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packed32_value_overflow_rebuilds():
    """A write outside the packed32 key range cannot patch in place: under
    ``packed='auto'`` the engine rebuilds with a re-resolved spec (packed64
    here), bit-identical to a from-scratch packed build of the mutated
    array. An *explicit* packed32 request fail-stops instead (below)."""
    rng = np.random.default_rng(7)
    n = 1 << 10
    x = rng.integers(-500, 500, n).astype(np.int32)  # auto -> packed32
    online = make_online("hybrid", jnp.asarray(x), packed="auto")
    log = DeltaLog()
    log.point(n // 3, 10**8)  # far outside the build-time key span
    res = online.apply(log)
    assert not res.patched  # OverflowError -> structural rebuild
    xm = x.copy()
    xm[n // 3] = 10**8
    want = build_mod.build(
        "hybrid",
        jnp.asarray(xm),
        packed="auto",
        threshold=int(online.store.current.state.threshold),
        use_kernels=False,
    )
    _assert_bit_identical(online.store.current.state, want)
    # ... and the rebuilt engine keeps patching incrementally.
    log2 = DeltaLog()
    log2.point(5, -400)
    assert online.apply(log2).patched
    # An EXPLICIT packed32 request cannot silently widen: the rebuild
    # fail-stops loudly instead of changing the asked-for layout.
    strict = make_online("hybrid", jnp.asarray(x), packed="packed32")
    log3 = DeltaLog()
    log3.point(0, 10**8)
    with pytest.raises(ValueError, match="packed32"):
        strict.apply(log3)


def test_packed32_append_past_index_field_rebuilds():
    """Appends that outgrow ``idx_bits`` rebuild; packed64 never does."""
    rng = np.random.default_rng(9)
    n = 100  # idx_bits_for(100) = 7 -> capacity 128
    x = rng.integers(0, 50, n).astype(np.int32)
    online = make_online("hybrid", jnp.asarray(x), packed="packed32")
    log = DeltaLog()
    log.append(rng.integers(0, 50, 40).astype(np.int32))  # n=140 > 2**7
    assert not online.apply(log).patched

    xf = rng.standard_normal(n).astype(np.float32)  # auto -> packed64
    online64 = make_online("hybrid", jnp.asarray(xf), packed="auto")
    log = DeltaLog()
    log.append(rng.standard_normal(40).astype(np.float32))
    assert online64.apply(log).patched  # 32-bit index field: no overflow


# --- durable round-trip -------------------------------------------------------


def test_durable_packed_restore_bit_identical_after_overflow_rebuild(tmp_path):
    """The concrete spec must survive checkpoints: after an overflow rebuild
    re-biased the key range, ``spec_for`` over the restored array would pick
    a different (equally valid) bias — restore must come back bit-identical
    to the live engine, so the snapshot carries the spec itself."""
    from repro.fault.durable import DurableEngine

    rng = np.random.default_rng(13)
    n = 512
    x = rng.integers(-100, 100, n).astype(np.int32)  # auto -> packed32
    eng = DurableEngine.create("packed_hybrid", jnp.asarray(x), str(tmp_path))
    log = DeltaLog()
    log.point(17, 10**7)  # overflow -> rebuild under a wider spec
    assert not eng.apply(log).patched
    eng.checkpoint()
    log2 = DeltaLog()  # a journaled suffix the restore must replay
    log2.point(400, -99)
    assert eng.apply(log2).patched
    eng2 = DurableEngine.restore(str(tmp_path))
    assert eng2.online.current_vid == eng.online.current_vid
    _assert_bit_identical(
        eng2.online.store.current.state, eng.online.store.current.state
    )
    l, r = _random_ranges(rng, n, 64)
    xm = x.copy().astype(np.int64)
    xm[17], xm[400] = 10**7, -99
    qi, qv = eng2.online.query(eng2.online.store.current.state, l, r)
    oi, _ = _oracle(xm, l, r)
    np.testing.assert_array_equal(np.asarray(qi), oi)


# --- cache schema v3 ----------------------------------------------------------


def test_cache_key_v3_layout_suffix():
    base = calib_cache.cache_key(1024, 128, backend="cpu", n_devices=1)
    assert calib_cache.cache_key(
        1024, 128, backend="cpu", n_devices=1, layout="unpacked"
    ) == base  # default layout keeps v2 keys byte-identical
    packed = calib_cache.cache_key(
        1024, 128, backend="cpu", n_devices=1, layout="packed32"
    )
    assert packed == base + "/layout=packed32"


def test_cache_v2_file_migrates_to_v3(tmp_path):
    """A v2 file loads (thresholds intact, kernel entries stamped with the
    unpacked layout) and the next store rewrites it as v3."""
    path = tmp_path / "calib.json"
    thr_key = "n=1024/bs=128/backend=cpu/ndev=1"
    krn_key = "kernel/n=4096/batch=64/backend=cpu/ndev=1"
    path.write_text(
        json.dumps(
            {
                "version": 2,
                "entries": {
                    thr_key: 48,
                    krn_key: {"tile": 8, "fetch": "resident", "block_size": 128},
                },
            }
        )
    )
    assert calib_cache.load_entry(thr_key, path) == 48
    krn = calib_cache.load_entry(krn_key, path)
    assert krn["layout"] == "unpacked"
    cfg = tuning.config_from_entry(krn)
    assert cfg is not None and cfg.layout == "unpacked"
    calib_cache.store_entry(thr_key + "/layout=packed32", 32, path)
    data = json.loads(path.read_text())
    assert data["version"] == calib_cache.CACHE_VERSION
    assert calib_cache.load_entry(thr_key + "/layout=packed32", path) == 32
    assert calib_cache.load_entry(thr_key, path) == 48  # migrated entry kept


def test_tuned_layout_winner_round_trips(tmp_path):
    """A swept winner carrying a packed layout persists and reloads with the
    layout intact (config v3), through the same get_config policy path the
    hybrid build uses."""
    path = tmp_path / "calib.json"
    won = tuning.KernelConfig(tile=16, fetch="resident", block_size=128, layout="packed32")
    key = tuning.tuning_key(4096, 64, backend="cpu", n_devices=1)
    calib_cache.store_entry(key, dict(won._asdict()), path)
    got = tuning.get_config(
        4096, 64, policy="cached", backend="cpu", n_devices=1, path=path
    )
    assert got == won


def test_candidate_configs_layout_feasibility():
    """The swept layout axis excludes what can never run: packed64 has no
    kernel path (int64 words), quantized has no dma strategy (the exact
    fallback needs its resident plane), and packed32's one kernel is timed
    once per tile."""
    cands = tuning.candidate_configs(4096, 128, layouts=tuning.TUNE_LAYOUTS)
    assert {c.fetch for c in cands if c.layout == "packed32"} == {"dma"}
    assert any(c.layout == "quantized" and c.fetch == "resident" for c in cands)
    assert not any(c.layout == "packed64" for c in cands)
    assert not any(c.layout == "quantized" and c.fetch == "dma" for c in cands)


# --- 8-fake-device conformance sweep -----------------------------------------

_CHILD_PACKED_MESH = textwrap.dedent(
    """
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import build as build_mod
    from repro.core import block_rmq, sharded_hybrid
    from repro.launch.mesh import make_mesh
    from repro.update.deltas import DeltaLog
    from repro.update.engines import make_online

    assert len(jax.devices()) == 8
    rng = np.random.default_rng(0)
    n = 1 << 11
    mesh = make_mesh((8,), ("shard",))

    for layout, x in (
        ("packed32", rng.integers(-1000, 1000, n).astype(np.int32)),
        ("packed64", rng.standard_normal(n).astype(np.float32)),
    ):
        xj = jnp.asarray(x)
        oracle = block_rmq.build(xj, 128)
        l = rng.integers(0, n, 256); r = rng.integers(0, n, 256)
        l, r = np.minimum(l, r), np.maximum(l, r)
        oi, ov = block_rmq.query(oracle, jnp.asarray(l), jnp.asarray(r))
        for mode in ("shard_structure", "shard_batch", "shard_2d"):
            s = sharded_hybrid.build(
                xj, mesh, ("shard",), 128, threshold=64, mode=mode, packed=layout
            )
            qi, qv = sharded_hybrid.query(s, l, r)
            assert np.array_equal(np.asarray(qi), np.asarray(oi)), (layout, mode)
            assert np.array_equal(np.asarray(qv), np.asarray(ov)), (layout, mode)

        # Online packed mesh patch: bit-identical to a rebuild of the
        # mutated array (same spec: mutations stay inside the key range).
        eng = make_online(
            "sharded_hybrid", xj, mesh=mesh, axis_names=("shard",),
            threshold=64, packed=layout,
        )
        log = DeltaLog()
        log.point(3, x[5])       # duplicate the min-side value across shards
        log.point(n - 7, x[5])
        res = eng.apply(log)
        assert res.patched, (layout, "expected incremental patch")
        xm = x.copy(); xm[3] = x[5]; xm[n - 7] = x[5]
        plan = build_mod.plan_for(
            "sharded_hybrid", xm.shape[0], mesh=mesh, axis_names=("shard",),
            block_size=128, threshold=64, packed=layout,
        )
        fresh = build_mod.execute(plan, jnp.asarray(xm))
        got = [t for t in jax.tree_util.tree_leaves(eng.store.current.state)
               if isinstance(t, jax.Array)]
        want = [t for t in jax.tree_util.tree_leaves(fresh)
                if isinstance(t, jax.Array)]
        assert len(got) == len(want)
        for a, b in zip(got, want):
            assert a.shape == b.shape and np.array_equal(np.asarray(a), np.asarray(b)), layout
    print("PACKED_MESH_OK")
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


def test_packed_mesh_conformance_8_devices():
    """packed32 + packed64 sharded hybrids (all three modes) bit-identical to
    the single-host oracle on an 8-device mesh, and the packed SPMD patch
    bit-identical to a from-scratch packed build of the mutated array."""
    out = _run_child(_CHILD_PACKED_MESH)
    assert "PACKED_MESH_OK" in out.stdout, out.stderr[-3000:]


def test_quantized_rejected_on_mesh():
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("shard",))
    x = jnp.asarray(np.random.default_rng(0).standard_normal(256).astype(np.float32))
    with pytest.raises(ValueError, match="single-host"):
        build_mod.build(
            "sharded_hybrid", x, mesh=mesh, axis_names=("shard",), packed="quantized"
        )


# --- bandwidth accounting gate ------------------------------------------------


def test_bandwidth_gate_ratios():
    """The benchmark suite's byte accounting meets the ISSUE bars at a small
    n (the ratios are size-independent; check.sh runs the full n=2**16 gate):
    packed32 moves <= 60% of unpacked bytes on the long-path query AND the
    doubling merge — i.e. >= 1.5x bytes/query reduction."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    try:
        from benchmarks import bandwidth
    finally:
        sys.path.pop(0)
    rep = bandwidth.report(1 << 12)
    assert rep["packed32_resolved"] == "packed32"
    assert rep["gate_query_ratio"] <= 0.6
    assert rep["gate_merge_ratio"] <= 0.6
    assert rep["unpacked_query_bytes"] / rep["packed32_query_bytes"] >= 1.5

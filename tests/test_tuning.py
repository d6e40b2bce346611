"""The megakernel autotuner (kernels/tuning.py) and its persistent cache.

Mirrors tests/test_calibration.py: deterministic sweeps via a fake
``hybrid._measure`` (the one timing seam), cache hit / miss / stale /
corrupt behavior through ``calib_cache``'s generic entries, and the
determinism contract — untuned/default paths never touch the cache and stay
bit-identical before vs after a cache write.
"""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import build as build_mod
from repro.core import calib_cache, hybrid
from repro.kernels import tuning


def _fail_measure(*a, **k):
    pytest.fail("timing sweep ran despite a warm cache / default policy")


# --- candidate product -------------------------------------------------------


def test_candidate_configs_pinned_block_size():
    cands = tuning.candidate_configs(1 << 12, 128)
    assert all(c.block_size == 128 for c in cands)
    assert len(cands) == len(set(cands)) == len(tuning.TUNE_TILES) * 2
    # The resolved default is always a member (the winner can't lose to it).
    nb = (1 << 12) // 128
    default = tuning.KernelConfig(
        tuning.DEFAULT_TILE, tuning.resolve_fetch("auto", nb), 128
    )
    assert default in cands


def test_candidate_configs_exclude_resident_past_ceiling():
    n = (tuning.RESIDENT_NB_CEILING + 1) * 128  # nb just past the ceiling
    cands = tuning.candidate_configs(n, 128)
    assert cands and all(c.fetch == "dma" for c in cands)


def test_candidate_configs_sweep_block_sizes_by_default():
    cands = tuning.candidate_configs(1 << 12)
    assert {c.block_size for c in cands} == set(tuning.TUNE_BLOCK_SIZES)


def test_resolve_fetch():
    assert tuning.resolve_fetch("auto", tuning.RESIDENT_NB_CEILING) == "resident"
    assert tuning.resolve_fetch("auto", tuning.RESIDENT_NB_CEILING + 1) == "dma"
    assert tuning.resolve_fetch("dma", 4) == "dma"
    with pytest.raises(ValueError):
        tuning.resolve_fetch("mmap", 4)


# --- key + entry schema ------------------------------------------------------


def test_tuning_key_namespace_and_fields():
    key = tuning.tuning_key(65536, 4096, backend="tpu", n_devices=8)
    assert key == "kernel/n=65536/batch=4096/backend=tpu/ndev=8"
    # Disjoint from the threshold keys in the same file.
    assert not key.startswith("n=")
    others = {
        tuning.tuning_key(65537, 4096, backend="tpu", n_devices=8),
        tuning.tuning_key(65536, 2048, backend="tpu", n_devices=8),
        tuning.tuning_key(65536, 4096, backend="cpu", n_devices=8),
        tuning.tuning_key(65536, 4096, backend="tpu", n_devices=1),
    }
    assert key not in others and len(others) == 4


def test_config_from_entry_rejects_malformed():
    good = {"tile": 8, "fetch": "dma", "block_size": 128}
    assert tuning.config_from_entry(good) == tuning.KernelConfig(8, "dma", 128)
    for bad in (
        None,
        41,
        "dma",
        {"tile": 8},
        {"tile": 8, "fetch": "mmap", "block_size": 128},
        {"tile": 0, "fetch": "dma", "block_size": 128},
        {"tile": 8, "fetch": "dma", "block_size": 100},
        {"tile": "x", "fetch": "dma", "block_size": 128},
    ):
        assert tuning.config_from_entry(bad) is None, bad


# --- sweep + autotune via the fake timing seam -------------------------------


def _fake_measure_preferring(want):
    """A deterministic _measure: the wanted config times fastest."""

    def fake(kind, fn, lj, rj, repeats):
        tag = f"kernel/tile={want.tile}/fetch={want.fetch}/bs={want.block_size}"
        return 0.5 if kind == tag else 1.0

    return fake


def test_autotune_picks_the_fastest_candidate(monkeypatch):
    want = tuning.KernelConfig(16, "dma", 128)
    monkeypatch.setattr(hybrid, "_measure", _fake_measure_preferring(want))
    got = tuning.autotune(1 << 12, 64, block_size=128, interpret=True)
    assert got == want


def test_autotune_tie_breaks_deterministically(monkeypatch):
    """All-equal timings: the first candidate in product order wins, so the
    tuned result is reproducible on a machine with flat measurements."""
    monkeypatch.setattr(hybrid, "_measure", lambda *a, **k: 1.0)
    cands = tuning.candidate_configs(1 << 12, 128)
    got = tuning.autotune(1 << 12, 64, block_size=128, interpret=True)
    assert got == cands[0]


def test_sweep_times_every_candidate_through_the_seam(monkeypatch):
    seen = []
    monkeypatch.setattr(
        hybrid, "_measure", lambda kind, *a, **k: seen.append(kind) or 1.0
    )
    results = tuning.sweep(1 << 12, 64, block_size=128, interpret=True)
    assert len(results) == len(seen) == len(tuning.candidate_configs(1 << 12, 128))


# --- persistent cache lifecycle ---------------------------------------------


def test_tuned_policy_sweeps_once_then_hits(tmp_path, monkeypatch):
    p = tmp_path / "cal.json"
    want = tuning.KernelConfig(16, "resident", 128)
    monkeypatch.setattr(hybrid, "_measure", _fake_measure_preferring(want))
    kw = dict(block_size=128, backend="cpu", n_devices=1, path=p)
    cfg = tuning.get_config(1 << 12, 64, policy="tuned", interpret=True, **kw)
    assert cfg == want
    # Persisted under the kernel/ namespace as a JSON dict.
    key = tuning.tuning_key(1 << 12, 64, backend="cpu", n_devices=1)
    assert calib_cache.load_entry(key, path=p) == dict(want._asdict())
    # Warm cache: zero timing sweeps.
    monkeypatch.setattr(hybrid, "_measure", _fail_measure)
    cfg2 = tuning.get_config(1 << 12, 64, policy="tuned", **kw)
    assert cfg2 == want


def test_cached_policy_never_measures(tmp_path, monkeypatch):
    p = tmp_path / "cal.json"
    monkeypatch.setattr(hybrid, "_measure", _fail_measure)
    kw = dict(block_size=128, backend="cpu", n_devices=1, path=p)
    # Miss: default fallback, no sweep.
    assert tuning.get_config(1 << 12, 64, policy="cached", **kw) == (
        tuning.default_config(128)
    )
    # Hit: the stored winner.
    key = tuning.tuning_key(1 << 12, 64, backend="cpu", n_devices=1)
    calib_cache.store_entry(key, {"tile": 16, "fetch": "dma", "block_size": 128}, p)
    assert tuning.get_config(1 << 12, 64, policy="cached", **kw) == (
        tuning.KernelConfig(16, "dma", 128)
    )


def test_stale_version_and_corrupt_entries_are_misses(tmp_path, monkeypatch):
    p = tmp_path / "cal.json"
    key = tuning.tuning_key(1 << 12, 64, backend="cpu", n_devices=1)
    monkeypatch.setattr(hybrid, "_measure", _fail_measure)
    kw = dict(block_size=128, backend="cpu", n_devices=1, path=p)
    # Stale file version: every entry is a miss.
    p.write_text(
        json.dumps(
            {
                "version": calib_cache.CACHE_VERSION + 1,
                "entries": {key: {"tile": 16, "fetch": "dma", "block_size": 128}},
            }
        )
    )
    assert tuning.get_config(1 << 12, 64, policy="cached", **kw) == (
        tuning.default_config(128)
    )
    # Corrupt file: miss, and a later store recovers it.
    p.write_text("definitely{not json")
    assert tuning.get_config(1 << 12, 64, policy="cached", **kw) == (
        tuning.default_config(128)
    )
    calib_cache.store_entry(key, {"tile": 4, "fetch": "dma", "block_size": 128}, p)
    assert tuning.get_config(1 << 12, 64, policy="cached", **kw) == (
        tuning.KernelConfig(4, "dma", 128)
    )
    # Malformed entry under a valid version: miss, not a crash.
    calib_cache.store_entry(key, {"tile": "eight"}, p)
    assert tuning.get_config(1 << 12, 64, policy="cached", **kw) == (
        tuning.default_config(128)
    )


def test_threshold_and_kernel_entries_share_one_file(tmp_path):
    """The kernel/ namespace coexists with int thresholds in the same file."""
    p = tmp_path / "cal.json"
    tkey = calib_cache.cache_key(1024, 128, backend="cpu", n_devices=1)
    kkey = tuning.tuning_key(1024, 64, backend="cpu", n_devices=1)
    calib_cache.store(tkey, 77, path=p)
    calib_cache.store_entry(kkey, {"tile": 8, "fetch": "dma", "block_size": 128}, p)
    assert calib_cache.load(tkey, path=p) == 77
    assert tuning.config_from_entry(calib_cache.load_entry(kkey, path=p)) == (
        tuning.KernelConfig(8, "dma", 128)
    )


# --- determinism: untuned paths are machine-state independent ----------------


def test_default_policy_never_touches_the_cache(tmp_path, monkeypatch):
    monkeypatch.setattr(hybrid, "_measure", _fail_measure)
    monkeypatch.setattr(
        calib_cache, "load_entry", lambda *a, **k: pytest.fail("cache read")
    )
    assert tuning.get_config(1 << 12, 64, policy=None) == tuning.default_config(128)
    assert tuning.get_config(
        1 << 12, 64, policy=None, block_size=256
    ) == tuning.default_config(256)


def test_untuned_build_bit_identical_before_and_after_cache_write(
    tmp_path, monkeypatch
):
    """kernel_config=None builds must not see a cache write (machine-state
    independence: the default path gives the same bits on every host)."""
    monkeypatch.setenv(calib_cache.ENV_VAR, str(tmp_path / "cal.json"))
    rng = np.random.default_rng(21)
    n = 2048
    x = jnp.asarray(rng.integers(0, 4, n).astype(np.float32))
    a = rng.integers(0, n, 64)
    b = rng.integers(0, n, 64)
    l, r = jnp.asarray(np.minimum(a, b)), jnp.asarray(np.maximum(a, b))

    def run():
        state, cfg = build_mod.build("fused", x, block_size=128)
        from repro import kernels

        i, v = kernels.ops.query(state, l, r, config=cfg, interpret=True)
        return cfg, np.asarray(i), np.asarray(v)

    cfg1, i1, v1 = run()
    # A tuned winner lands in the cache (different geometry than the default).
    calib_cache.store_entry(
        tuning.tuning_key(n, backend="cpu", n_devices=1),
        {"tile": 16, "fetch": "dma", "block_size": 256},
        tmp_path / "cal.json",
    )
    cfg2, i2, v2 = run()
    assert cfg1 == cfg2 == tuning.default_config(128)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_array_equal(v1, v2)


def test_fused_plan_carries_resolved_config(tmp_path):
    """The BuildPlan meta exposes the resolved geometry (serving prints it,
    warmup and benchmarks read it)."""
    plan = build_mod.plan_for("fused", 4096, kernel_config=(4, "dma", 128))
    assert plan.meta["kernel_config"] == tuning.KernelConfig(4, "dma", 128)
    assert plan.meta["block_size"] == 128
    # A tuned config's block size drives the build when none is pinned.
    plan2 = build_mod.plan_for("fused", 4096, kernel_config=(8, "auto", 256))
    assert plan2.meta["block_size"] == 256


def test_pinned_dma_variant_survives_serving_policy(tmp_path, monkeypatch):
    """The fused128_dma registry engine pins fetch="dma"; the serving layer's
    cached/tuned policy kwarg must not silently unpin it."""
    from repro.core import registry

    monkeypatch.setenv(calib_cache.ENV_VAR, str(tmp_path / "cal.json"))
    plan = registry.plan_for_serving("fused128_dma", 4096, kernel_config="cached")
    assert plan.meta["kernel_config"] == tuning.KernelConfig(8, "dma", 128)
    # The unpinned engine honors the policy (cold cache -> default).
    plan2 = registry.plan_for_serving("fused128", 4096, kernel_config="cached")
    assert plan2.meta["kernel_config"] == tuning.default_config(128)


def test_hybrid_kernel_config_resolved_only_with_kernels(tmp_path, monkeypatch):
    monkeypatch.setattr(hybrid, "_measure", _fail_measure)
    plan = build_mod.plan_for("hybrid", 4096, use_kernels=False, kernel_config=None)
    assert plan.meta["kernel_config"] is None
    plan2 = build_mod.plan_for("hybrid", 4096, use_kernels=True, kernel_config=None)
    assert plan2.meta["kernel_config"] == tuning.default_config(128)

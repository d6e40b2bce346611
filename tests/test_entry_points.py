"""Entry-point plumbing: where the compile cache lives, and the benchmark
runner that measures per device count without hiding a failure."""

import os

import pytest

from repro.launch import cache


@pytest.fixture
def updates(monkeypatch):
    calls = {}
    monkeypatch.setattr(cache.jax.config, "update", lambda k, v: calls.__setitem__(k, v))
    return calls


def test_compile_cache_defaults_to_the_checkout(monkeypatch, updates):
    monkeypatch.delenv(cache.CACHE_ENV, raising=False)
    path = cache.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache") == updates["jax_compilation_cache_dir"]
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert cache.enable_compile_cache() == path  # fixed: same on every call


def test_compile_cache_env_wins(monkeypatch, updates, tmp_path):
    monkeypatch.setenv(cache.CACHE_ENV, str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in updates  # JAX reads the env itself
    assert updates["jax_enable_compilation_cache"] is True


def test_per_device_count_raises_on_a_failed_child():
    from benchmarks import common

    with pytest.raises(RuntimeError, match="failed"):
        list(common.per_device_count("benchmarks.common:no_such_function", [1]))


def test_per_device_count_runs_each_count():
    from benchmarks import common

    out = list(common.per_device_count("builtins:len", [1, 2]))
    assert out == [(1, 1), (2, 2)]

"""Pallas kernel sweeps: shapes x dtypes, assert_allclose vs ref.py oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ref as core_ref
from repro.core.block_rmq import maxval
from repro.kernels import block_min, ops, rmq_partials
from repro.kernels import ref as kref

SHAPES = [(4, 128), (7, 128), (16, 256), (3, 512), (32, 128)]
DTYPES = [jnp.float32, jnp.int32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_block_min_kernel(shape, dtype, rng):
    nb, bs = shape
    x = rng.integers(-100, 100, (nb, bs)).astype(np.float32)
    xj = jnp.asarray(x).astype(dtype)
    val, idx = block_min(xj, interpret=True)
    gval, gidx = kref.block_min_ref(xj)
    np.testing.assert_allclose(np.asarray(val), np.asarray(gval))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(gidx))


@pytest.mark.parametrize("tile_rows", [1, 3, 8])
def test_block_min_tiling(tile_rows, rng):
    x = jnp.asarray(rng.standard_normal((13, 128)).astype(np.float32))
    val, idx = block_min(x, tile_rows=tile_rows, interpret=True)
    gval, gidx = kref.block_min_ref(x)
    np.testing.assert_allclose(np.asarray(val), np.asarray(gval))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(gidx))


@pytest.mark.parametrize("shape", [(8, 128), (4, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_rmq_partials_kernel(shape, dtype, rng):
    nb, bs = shape
    x = rng.integers(0, 40, (nb, bs)).astype(np.float32)
    xj = jnp.asarray(x).astype(dtype)
    b = 64
    bl = rng.integers(0, nb, b)
    br = np.minimum(bl + rng.integers(0, nb, b), nb - 1)
    bl, br = np.minimum(bl, br), np.maximum(bl, br)
    ls = rng.integers(0, bs, b)
    re = rng.integers(0, bs, b)
    le = np.where(bl == br, np.maximum(ls, re), bs - 1)
    re2 = np.where(bl == br, np.maximum(ls, re), re)
    args = [jnp.asarray(a, jnp.int32) for a in (bl, br, ls, le, re2)]
    val, idx = rmq_partials(xj, *args, interpret=True)
    gval, gidx = kref.rmq_partials_ref(xj, *args)
    np.testing.assert_allclose(
        np.asarray(val).astype(np.float32), np.asarray(gval).astype(np.float32)
    )
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(gidx))


@pytest.mark.parametrize("n,bs", [(1000, 128), (4096, 256), (700, 128), (130, 128)])
def test_kernelized_engine_end_to_end(n, bs, rng):
    x = rng.integers(0, 30, n).astype(np.float32)
    l = rng.integers(0, n, 64)
    r = rng.integers(0, n, 64)
    l, r = np.minimum(l, r), np.maximum(l, r)
    s = ops.build(jnp.asarray(x), bs, interpret=True)
    idx, val = ops.query(s, jnp.asarray(l), jnp.asarray(r), interpret=True)
    gold = core_ref.rmq_ref(x, l, r)
    np.testing.assert_array_equal(np.asarray(idx), gold)
    np.testing.assert_allclose(np.asarray(val), x[gold])


def test_kernel_vs_pure_jnp_engine(rng):
    """ops.query must agree with core.block_rmq.query bit-for-bit."""
    from repro.core import block_rmq

    n = 3000
    x = rng.standard_normal(n).astype(np.float32)
    l = rng.integers(0, n, 128)
    r = rng.integers(0, n, 128)
    l, r = np.minimum(l, r), np.maximum(l, r)
    s1 = ops.build(jnp.asarray(x), 128, interpret=True)
    s2 = block_rmq.build(jnp.asarray(x), 128)
    i1, v1 = ops.query(s1, jnp.asarray(l), jnp.asarray(r), interpret=True)
    i2, v2 = block_rmq.query(s2, jnp.asarray(l), jnp.asarray(r))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


# --- interpret vs compiled ----------------------------------------------------


def test_interpret_follows_the_backend(monkeypatch):
    from repro.kernels import tiling

    assert tiling.resolve_interpret(None) is True  # the CPU backend interprets
    assert tiling.resolve_interpret(True, tile=3, row=256) is True
    monkeypatch.setattr(tiling.jax, "default_backend", lambda: "tpu")
    assert tiling.resolve_interpret(None, tile=8, row=128) is False
    monkeypatch.setattr(tiling.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        tiling.resolve_interpret(None)  # never interpret quietly on a device


@pytest.mark.parametrize("tile,row", [(4, 128), (8, 256)])
def test_compiled_launch_rejects_shapes_the_tpu_refuses(tile, row):
    from repro.kernels import tiling

    with pytest.raises(ValueError):
        tiling.resolve_interpret(False, tile=tile, row=row)


@pytest.mark.parametrize("engine,kw", [("hybrid", {"threshold": 64}), ("fused", {})])
def test_plan_rejects_a_block_size_the_chip_cannot_launch(monkeypatch, engine, kw):
    """On a TPU a kernel engine with 256-wide blocks is refused when it is
    planned, before the build, not at its first warm-up launch."""
    from repro.core import build as build_mod

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="128-lane"):
        build_mod.plan_for(engine, 1 << 12, block_size=256, **kw)
    plan = build_mod.plan_for(engine, 1 << 12, block_size=128, **kw)
    assert plan.meta["block_size"] == 128

"""Hybrid dispatcher: bit-identical results + correct scatter-back ordering."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import block_rmq, hybrid, ref, sharded_hybrid
from repro.launch.mesh import make_mesh


def _mixed_batch(rng, n, b, threshold):
    """Half short ranges (<= threshold), half long, interleaved randomly."""
    length_short = rng.integers(1, threshold + 1, b // 2)
    length_long = rng.integers(threshold + 1, n + 1, b - b // 2)
    length = np.concatenate([length_short, length_long])
    rng.shuffle(length)
    l = rng.integers(0, np.maximum(n - length + 1, 1), b)
    r = np.minimum(l + length - 1, n - 1)
    return l, r


@pytest.mark.parametrize("n", [300, 1000, 4096])
def test_hybrid_bit_identical_to_blocked(n, rng):
    x = rng.integers(0, 11, n).astype(np.float32)  # dense ties
    s = hybrid.build(jnp.asarray(x), 128, use_kernels=False)
    sb = block_rmq.build(jnp.asarray(x), 128)
    l, r = _mixed_batch(rng, n, 256, s.threshold)
    hi, hv = hybrid.query(s, l, r)
    bi, bv = block_rmq.query(sb, jnp.asarray(l), jnp.asarray(r))
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(bi))
    np.testing.assert_array_equal(np.asarray(hv), np.asarray(bv))


def test_hybrid_kernel_path_matches_oracle(rng):
    """Short ranges through the fused Pallas megakernel (interpret off-TPU)."""
    n = 1500
    x = rng.integers(-5, 6, n).astype(np.float32)
    s = hybrid.build(jnp.asarray(x), 128, use_kernels=True)
    l, r = _mixed_batch(rng, n, 64, s.threshold)
    hi, hv = hybrid.query(s, l, r)
    gold = ref.rmq_ref(x, l, r)
    np.testing.assert_array_equal(np.asarray(hi), gold)
    np.testing.assert_allclose(np.asarray(hv), x[gold])


def test_scatter_back_ordering():
    """Known alternating short/long pattern: outputs stay in batch order."""
    n = 1024
    x = np.arange(n, 0, -1).astype(np.float32)  # strictly decreasing: min at r
    s = hybrid.build(jnp.asarray(x), 128, use_kernels=False, threshold=8)
    # Even positions short (len 2 <= 8), odd positions long (len 512 > 8).
    b = 40
    l = np.empty(b, np.int64)
    r = np.empty(b, np.int64)
    l[0::2] = np.arange(20) * 3
    r[0::2] = l[0::2] + 1
    l[1::2] = np.arange(20) * 5
    r[1::2] = l[1::2] + 511
    idx, val = hybrid.query(s, l, r)
    np.testing.assert_array_equal(np.asarray(idx), r)  # min of decreasing = r
    np.testing.assert_allclose(np.asarray(val), x[r])


def test_all_short_and_all_long_batches(rng):
    """Single-sided batches must not call the other engine's path at all."""
    n = 2048
    x = rng.standard_normal(n).astype(np.float32)
    s = hybrid.build(jnp.asarray(x), 128, use_kernels=False, threshold=64)
    for lo, hi in [(1, 64), (65, n)]:  # all-short, then all-long
        length = rng.integers(lo, hi + 1, 50)
        l = rng.integers(0, np.maximum(n - length + 1, 1), 50)
        r = np.minimum(l + length - 1, n - 1)
        idx, val = hybrid.query(s, l, r)
        gold = ref.rmq_ref(x, l, r)
        np.testing.assert_array_equal(np.asarray(idx), gold)
        np.testing.assert_allclose(np.asarray(val), x[gold])


def test_empty_batch_returns_empty_without_launching():
    """Regression: an empty batch used to pad to a phantom (0, 0) query and
    launch a kernel for nothing. It must return empty (idx, val) early."""
    s = hybrid.build(jnp.arange(64.0), 128, use_kernels=False)
    boom = lambda *a: (_ for _ in ()).throw(AssertionError("launched on empty batch"))
    s = s._replace(short_fn=boom, long_fn=boom)
    idx, val = hybrid.query(s, np.zeros(0, np.int64), np.zeros(0, np.int64))
    assert idx.shape == (0,) and val.shape == (0,)
    assert idx.dtype == jnp.int32
    assert val.dtype == s.x.dtype


def test_threshold_default_and_calibrate_smoke():
    s = hybrid.build(jnp.zeros(10_000, jnp.float32), 128, use_kernels=False)
    assert s.threshold == 100  # sqrt(n) default
    # 0 (all-long) and 4096 (all-short) are honest degenerate measurements.
    thr = hybrid.calibrate(4096, batch=256, use_kernels=False, repeats=1)
    assert 0 <= thr <= 4096


def _batch(rng, n, b, threshold, kind):
    """``b`` ranges, all short (<= threshold), all long, or both interleaved."""
    if kind == "mixed":
        return _mixed_batch(rng, n, b, threshold)
    lo, hi = (1, threshold) if kind == "uniform_short" else (threshold + 1, n)
    length = rng.integers(lo, hi + 1, b)
    l = rng.integers(0, np.maximum(n - length + 1, 1), b)
    return l, np.minimum(l + length - 1, n - 1)


@pytest.mark.parametrize("kind", ["uniform_short", "uniform_long", "mixed"])
@pytest.mark.parametrize("engine", ["hybrid", "sharded_hybrid"])
def test_answers_come_back_where_they_were_merged(engine, kind, rng):
    """Bit-identical to the oracle either way; a mixed batch's answers are
    merged on the host and returned there (never sent back to the device),
    a uniform batch's are the launch's own device arrays."""
    n, threshold = 2048, 64
    x = rng.integers(0, 11, n).astype(np.float32)  # dense ties: leftmost rule
    if engine == "hybrid":
        s = hybrid.build(jnp.asarray(x), 128, use_kernels=False, threshold=threshold)
        query = hybrid.query
    else:  # a one-device mesh, as the conformance suite builds it
        mesh = make_mesh((1,), ("shard",))
        s = sharded_hybrid.build(jnp.asarray(x), mesh, ("shard",), 128, threshold=threshold)
        query = sharded_hybrid.query
    l, r = _batch(rng, n, 200, threshold, kind)
    idx, val = query(s, l, r)
    gold = ref.rmq_ref(x, l, r)
    np.testing.assert_array_equal(np.asarray(idx), gold)
    np.testing.assert_array_equal(np.asarray(val), x[gold])
    assert idx.dtype == np.int32 and val.dtype == np.float32
    if kind == "mixed":
        assert type(idx) is np.ndarray and type(val) is np.ndarray
    else:
        assert isinstance(idx, jax.Array) and isinstance(val, jax.Array)

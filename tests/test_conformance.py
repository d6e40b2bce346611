"""Engine-conformance harness: EVERY registry engine through one oracle sweep.

New engines get this coverage by enrollment in ``repro.core.registry`` — no
new test files. Each scenario builds (x, l, r) and every engine must return
exact leftmost-tie argmin indices plus the matching values:

  * duplicate-heavy arrays (leftmost-tie stress),
  * n = 1 and non-power-of-two n,
  * single-element (l == r) and full-array (0, n-1) ranges,
  * all three §6.4 range distributions (small / medium / large),
  * float32 and int32 value dtypes.

Sizes are kept modest so the interpret-mode Pallas engine (``fused128``)
stays seconds-fast off-TPU; the big-n sweeps live in tests/test_rmq_engines.
"""

import zlib

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import ref, registry
from repro.serve.workload import make_queries


def _bounded(rng, n, b):
    a = rng.integers(0, n, b)
    c = rng.integers(0, n, b)
    return np.minimum(a, c), np.maximum(a, c)


def _dup_heavy(rng, n, dtype):
    """Values drawn from 3 levels: nearly every query range has tied minima."""
    return rng.integers(0, 3, n).astype(dtype)


def _scn_dup_heavy(rng):
    n = 512
    return (_dup_heavy(rng, n, np.float32), *_bounded(rng, n, 64))


def _scn_n1(rng):
    return np.array([7.0], np.float32), np.zeros(3, np.int64), np.zeros(3, np.int64)


def _scn_non_pow2_n(rng):
    n = 1057
    return (rng.integers(-9, 9, n).astype(np.float32), *_bounded(rng, n, 48))


def _scn_single_element_ranges(rng):
    n = 700
    pts = rng.integers(0, n, 48)
    return _dup_heavy(rng, n, np.float32), pts.copy(), pts.copy()


def _scn_full_array_ranges(rng):
    n = 513
    b = 8
    return (
        _dup_heavy(rng, n, np.float32),
        np.zeros(b, np.int64),
        np.full(b, n - 1, np.int64),
    )


def _scn_dist(dist):
    def scn(rng):
        n = 1000
        x = rng.integers(0, 9, n).astype(np.float32)
        l, r = make_queries(rng, n, 64, dist)
        return x, l, r

    scn.__name__ = f"_scn_dist_{dist}"
    return scn


def _scn_int32_values(rng):
    n = 800
    return (rng.integers(-50, 50, n).astype(np.int32), *_bounded(rng, n, 64))


SCENARIOS = {
    "dup_heavy_ties": _scn_dup_heavy,
    "n1": _scn_n1,
    "non_pow2_n": _scn_non_pow2_n,
    "single_element_ranges": _scn_single_element_ranges,
    "full_array_ranges": _scn_full_array_ranges,
    "dist_small": _scn_dist("small"),
    "dist_medium": _scn_dist("medium"),
    "dist_large": _scn_dist("large"),
    "int32_values": _scn_int32_values,
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
@pytest.mark.parametrize("engine", registry.names())
def test_engine_conformance(engine, scenario):
    rng = np.random.default_rng(zlib.crc32(scenario.encode()))
    x, l, r = SCENARIOS[scenario](rng)
    gold = ref.rmq_ref(x, l, r)

    eng = registry.get(engine)
    s = eng.build(jnp.asarray(x))
    idx, val = eng.query(s, jnp.asarray(l), jnp.asarray(r))
    idx = np.asarray(idx)
    val = np.asarray(val)

    assert np.issubdtype(idx.dtype, np.integer), (engine, idx.dtype)
    np.testing.assert_array_equal(idx, gold, err_msg=f"{engine}/{scenario}")
    np.testing.assert_array_equal(val, x[gold], err_msg=f"{engine}/{scenario}")


def test_fused_dma_past_resident_ceiling():
    """The DMA fetch strategy must stay bit-identical to the oracle at an nb
    8x past the resident-table VMEM ceiling (the whole point of megakernel
    v2), through the single ``fused_query`` entry point.

    The structure is built with the pure-jnp builder (the kernel build adds
    only the augmented tables, derived here on the fly); the query path
    under test is exactly the megakernel.
    """
    from repro.core import block_rmq
    from repro.kernels import tuning
    from repro.kernels.fused_query import fused_query

    bs = 128
    nb = 8 * tuning.RESIDENT_NB_CEILING  # 2^16 blocks, n = 2^23
    n = nb * bs
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.integers(0, 5, n).astype(np.float32))  # dense ties
    s = block_rmq.build(x, bs)
    assert s.x_blocks.shape[0] == nb > tuning.RESIDENT_NB_CEILING

    a = rng.integers(0, n, 24)
    b = rng.integers(0, n, 24)
    l, r = np.minimum(a, b), np.maximum(a, b)
    # Ranges that stress the interior tables at this scale, plus the edges.
    l = np.concatenate([l, [0, 0, n - 1, 5]])
    r = np.concatenate([r, [n - 1, bs, n - 1, n - 5]])
    xh = np.asarray(x)
    gold = ref.rmq_ref(xh, l, r)

    # This test builds the bare structure, so the augmented interior tables
    # are intentionally absent: opt into the on-the-fly derivation.
    qi, qv = fused_query(
        s.x_blocks, s.bmin_val, s.bmin_gidx, s.st.idx,
        jnp.asarray(l), jnp.asarray(r), fetch="dma", interpret=True,
        materialize_interior=True,
    )
    np.testing.assert_array_equal(np.asarray(qi), gold)
    np.testing.assert_array_equal(np.asarray(qv), xh[gold])
    # "auto" must resolve to the dma strategy past the ceiling and agree.
    ai, av = fused_query(
        s.x_blocks, s.bmin_val, s.bmin_gidx, s.st.idx,
        jnp.asarray(l), jnp.asarray(r), fetch="auto", interpret=True,
        materialize_interior=True,
    )
    np.testing.assert_array_equal(np.asarray(ai), gold)
    np.testing.assert_array_equal(np.asarray(av), xh[gold])


def test_sharded_hybrid_modes_match_single_device():
    """Both distribution modes agree with the oracle on a 1-device mesh."""
    from repro.core import sharded_hybrid
    from repro.launch.mesh import make_mesh

    rng = np.random.default_rng(5)
    n = 1500
    x = rng.integers(0, 6, n).astype(np.float32)
    l, r = _bounded(rng, n, 100)
    gold = ref.rmq_ref(x, l, r)
    mesh = make_mesh((1,), ("shard",))
    for mode in sharded_hybrid.MODES:
        s = sharded_hybrid.build(jnp.asarray(x), mesh, ("shard",), 128, mode=mode)
        idx, val = sharded_hybrid.query(s, l, r)
        np.testing.assert_array_equal(np.asarray(idx), gold, err_msg=mode)
        np.testing.assert_array_equal(np.asarray(val), x[gold], err_msg=mode)


def test_sharded_hybrid_empty_batch():
    from repro.core import sharded_hybrid

    # Explicit dtype: packed64 builds elsewhere in the suite enable x64,
    # under which a bare arange(256.0) would widen to float64.
    s = sharded_hybrid.build(jnp.arange(256.0, dtype=jnp.float32))
    # A launch on an empty batch would be a phantom kernel: forbid it outright.
    boom = lambda *a: (_ for _ in ()).throw(AssertionError("launched on empty batch"))
    s = s._replace(short_fn=boom, long_fn=boom)
    idx, val = sharded_hybrid.query(s, np.zeros(0, np.int64), np.zeros(0, np.int64))
    assert idx.shape == (0,) and val.shape == (0,)
    assert idx.dtype == jnp.int32 and val.dtype == jnp.float32


def test_sharded_hybrid_rejects_unknown_mode():
    from repro.core import sharded_hybrid

    with pytest.raises(ValueError):
        sharded_hybrid.build(jnp.zeros(16), mode="shard_everything")

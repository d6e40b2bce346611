"""The hybrid without its doubling table over the raw array.

Where that table (~32 words per element) does not fit the device beside the
rest of the build, ``core.build``'s hybrid planner builds none, and the fused
kernel answers every range: long ones through its interior lookups on the
table over block minima. The rule is checked against explicit memory limits;
the table-free hybrid is built and served at small n in interpret mode and
held to ``core/ref.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build as build_mod
from repro.core import hybrid, ref, registry
from repro.serve import RMQServer, ServeConfig
from repro.serve.workload import make_queries

# memory_stats()["bytes_limit"] that one TPU v5e (v5 lite) chip reports.
V5E_BYTES_LIMIT = 16_909_336_064
REGIMES = ("small", "medium", "large", "mixed")
# The kernel's two fetch strategies, as launch geometries (tile, fetch, bs).
FETCHES = {"resident": (8, "resident", 128), "dma": (8, "dma", 128)}


@pytest.fixture
def limit(monkeypatch):
    """Set the memory limit the planner reads from the device."""

    def set_limit(value):
        monkeypatch.setattr(build_mod, "device_bytes_limit", lambda: value)

    return set_limit


def _table_free(x, fetch="dma"):
    """A table-free hybrid over ``x``, built through the plan pipeline."""
    plan = build_mod.plan_for(
        "hybrid", x.shape[0], use_kernels=True, kernel_config=FETCHES[fetch]
    )
    assert plan.meta["long_path"] == "kernel"
    return plan, build_mod.execute(plan, jnp.asarray(x))


def _ties(rng, n):
    return rng.integers(0, 8, n).astype(np.float32)  # frequent ties


def _check(s, x, l, r):
    idx, val = hybrid.query(s, l, r)
    gold = ref.rmq_ref(x, l, r)
    np.testing.assert_array_equal(np.asarray(idx), gold)
    np.testing.assert_array_equal(np.asarray(val), x[gold])


# --- the planner's rule ------------------------------------------------------


@pytest.mark.parametrize(
    "log2n,long_path", [(20, "table"), (26, "table"), (27, "kernel"), (30, "kernel")]
)
def test_v5e_limit_decides_the_long_path(limit, log2n, long_path):
    limit(V5E_BYTES_LIMIT)
    n = 1 << log2n
    plan = build_mod.plan_for("hybrid", n, use_kernels=True, kernel_config=None)
    assert plan.meta["long_path"] == long_path
    thr = plan.meta["threshold"]
    assert thr == (n if long_path == "kernel" else hybrid.regime_boundary(n))


def test_plan_for_serving_on_a_v5e_drops_the_table_at_2_30(limit, monkeypatch):
    """The served entry point, as the benchmark calls it on a chip (kernels
    in use because the backend is a TPU); only the plan is made."""
    limit(V5E_BYTES_LIMIT)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan = registry.plan_for_serving("hybrid", 1 << 30, threshold=None, kernel_config=None)
    assert plan.meta["use_kernels"] and plan.meta["long_path"] == "kernel"
    assert plan.meta["threshold"] == 1 << 30
    plan = registry.plan_for_serving("hybrid", 1 << 26, threshold=None, kernel_config=None)
    assert plan.meta["long_path"] == "table" and plan.meta["threshold"] == 8192


@pytest.mark.parametrize("log2n", [26, 30])
def test_no_reported_limit_keeps_the_table(limit, log2n):
    limit(None)
    plan = build_mod.plan_for("hybrid", 1 << log2n, use_kernels=True, kernel_config=None)
    assert plan.meta["long_path"] == "table"


def test_cpu_reports_no_limit():
    assert build_mod.device_bytes_limit() is None


def test_without_kernels_the_table_stays(limit):
    """The online hybrid's pure-jnp build keeps its table whatever the limit."""
    limit(V5E_BYTES_LIMIT)
    plan = build_mod.plan_for("hybrid", 1 << 30, use_kernels=False)
    assert plan.meta["long_path"] == "table"


@pytest.mark.parametrize("packed", ["packed32", "packed64", "auto"])
def test_packed_layout_that_does_not_fit_raises(limit, packed):
    limit(V5E_BYTES_LIMIT)
    with pytest.raises(ValueError, match="does not fit"):
        build_mod.plan_for("hybrid", 1 << 30, use_kernels=True, packed=packed)


def test_packed_layout_that_fits_keeps_its_table(limit):
    limit(V5E_BYTES_LIMIT)
    plan = build_mod.plan_for("hybrid", 1 << 20, use_kernels=True, packed="packed32")
    assert plan.meta["long_path"] == "table"


def test_table_bytes_count_padded_rows():
    """27 rows pad to 32 at 2^26: 8 GiB of table, and the rest on top."""
    n = 1 << 26
    total = build_mod.hybrid_table_bytes(n)
    assert 32 * n * 4 < total < 32 * n * 4 + (2 << 30)
    assert build_mod.hybrid_table_bytes(1 << 30) > 128 << 30


def test_warmup_bounds_one_probe_without_table(limit):
    limit(1)
    n = 1000
    plan = build_mod.plan_for("hybrid", n, use_kernels=True)
    assert plan.meta["long_path"] == "kernel"
    probes = build_mod.warmup_bounds(plan)(4)
    assert len(probes) == 1
    l, r = probes[0]
    assert (l == 0).all() and (r == n - 1).all()


# --- the table-free hybrid against the oracle ---------------------------------


@pytest.mark.parametrize("fetch", sorted(FETCHES))
@pytest.mark.parametrize("regime", REGIMES)
def test_table_free_matches_oracle_in_every_regime(limit, rng, fetch, regime):
    limit(1)
    n = 3000  # not a multiple of 128
    x = _ties(rng, n)
    _, s = _table_free(x, fetch)
    assert s.st is None and s.long_fn is None and s.threshold == n
    l, r = make_queries(rng, n, 100, regime)  # 100: a padded launch
    _check(s, x, l, r)


@pytest.mark.parametrize("fetch", sorted(FETCHES))
@pytest.mark.parametrize("n", [128, 1000, 4096 + 77])
def test_table_free_edges(limit, rng, fetch, n):
    """l = r, the whole array, a range inside one block, adjacent blocks,
    a range ending in the last (partial) block."""
    limit(1)
    x = _ties(rng, n)
    _, s = _table_free(x, fetch)
    last = n - 1
    l = [0, last, 0, 5, 3, 100, 127, 0, last // 2]
    r = [0, last, last, 5, 60, min(200, last), min(128, last), min(255, last), last]
    _check(s, x, np.asarray(l), np.asarray(r))


def test_table_free_every_pair_at_tiny_n(limit):
    """All (l, r) over a tie-heavy array of 300: every block layout."""
    limit(1)
    n = 300
    x = np.random.default_rng(3).integers(0, 3, n).astype(np.float32)
    _, s = _table_free(x)
    l, r = np.triu_indices(n)
    _check(s, x, l, r)


def test_table_free_equals_table_hybrid(limit, rng):
    """The same array and batch with and without the raw table."""
    n = 5000
    x = _ties(rng, n)
    limit(None)
    with_table = build_mod.build("hybrid", jnp.asarray(x), use_kernels=True)
    assert with_table.st is not None
    limit(1)
    _, without = _table_free(x)
    l, r = make_queries(rng, n, 128, "mixed")
    a = hybrid.query(with_table, l, r)
    b = hybrid.query(without, l, r)
    for u, v in zip(a, b):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


def test_table_free_dispatch_never_partitions(limit, rng):
    """One uniform launch per batch; the long ranges are reported as the
    kernel's, and no routing split is."""
    limit(1)
    n = 4096
    x = _ties(rng, n)
    _, s = _table_free(x)
    l, r = make_queries(rng, n, 64, "mixed")
    calls, splits, kernel_long = [], [], []
    short_fn = s.short_fn
    s = s._replace(short_fn=lambda a, b: calls.append(a.shape) or short_fn(a, b))
    with hybrid.record_splits(lambda a, b: splits.append((a, b)), kernel_long.append):
        idx, val = hybrid.query(s, l, r)
    assert calls == [(64,)] and splits == []
    assert kernel_long == [int(((r - l + 1) > hybrid.regime_boundary(n)).sum())]
    assert isinstance(idx, jax.Array)  # a uniform launch's device arrays


# --- served through RMQServer ---------------------------------------------------


def test_table_free_served_counts_kernel_long(limit, rng):
    limit(1)
    n = 2000
    x = _ties(rng, n)
    plan, s = _table_free(x)
    srv = RMQServer(
        lambda l, r: hybrid.query(s, l, r),
        ServeConfig(max_batch=64, n=n, deadline_s=0.0),
        warmup_bounds=build_mod.warmup_bounds(plan),
    )
    srv.warmup([64])  # each request below is one launch padded to 64
    reqs = [make_queries(rng, n, 37, regime) for regime in REGIMES]
    with srv:
        res = [srv.submit(l, r).result(timeout=120) for l, r in reqs]
    for (l, r), out in zip(reqs, res):
        gold = ref.rmq_ref(x, l, r)
        np.testing.assert_array_equal(out.idx, gold)
        np.testing.assert_array_equal(out.val, x[gold])
    counters = {(name, tuple(sorted(c.labels.items()))): c.value for name, c in srv.metrics.counters()}
    boundary = hybrid.regime_boundary(n)
    want = sum(int(((r - l + 1) > boundary).sum()) for l, r in reqs)
    assert want > 0
    assert counters[("serve_kernel_long_queries_total", ())] == want
    assert counters[("serve_regime_queries_total", (("regime", "long"),))] == 0
    assert counters[("serve_regime_queries_total", (("regime", "short"),))] == 0
    assert srv.stats().regime_splits == ()


def test_table_hybrid_counts_no_kernel_long(rng):
    """With a table the long ranges are routed, and counted as routed."""
    n = 2000
    x = _ties(rng, n)
    s = build_mod.build("hybrid", jnp.asarray(x), use_kernels=False)
    l, r = make_queries(rng, n, 50, "large")
    with RMQServer(lambda a, b: hybrid.query(s, a, b), ServeConfig(max_batch=64, n=n)) as srv:
        srv.submit(l, r).result(timeout=120)
    counters = {(name, tuple(sorted(c.labels.items()))): c.value for name, c in srv.metrics.counters()}
    assert counters[("serve_kernel_long_queries_total", ())] == 0
    assert counters[("serve_regime_queries_total", (("regime", "long"),))] > 0

"""Compile the served kernels and builds for a described TPU v5e (no chip needed).

Interpret mode cannot see what the TPU compiler refuses: blocks off the
(8, 128) tiling, one-lane reads at a dynamic offset, DMAs of rows wider than
one lane tile, programs that overflow the chip's memory. Each test here
lowers a kernel or build at the size it serves, compiles it for a described
``v5e:2x2`` topology (one chip, or the 2x2 mesh), and checks the compiled
program: kernel programs hold the Mosaic kernel (``tpu_custom_call``), and
the builds that set the served sizes fit a chip's HBM by
``compiled.memory_analysis()``, which reports per-device bytes. Nothing runs.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and the test workers import every file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import distributed, packing, sparse_table
from repro.kernels import ops
from repro.kernels.block_min import block_min
from repro.kernels.fused_query import fused_query, fused_query_packed
from repro.kernels.lane_query import lane_partials
from repro.kernels.rmq_query import rmq_partials

V5E_HBM_BYTES = 16 << 30
BATCH = 1024
KERNEL_CALL = "tpu_custom_call"
# The sizes chip_smoke.py serves: the hybrid on one chip, sharded_hybrid's
# shard_structure mode on the 2x2 mesh.
N_ONE_CHIP = 1 << 26
N_FOUR_CHIPS = 1 << 27


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back without the chip:
    # keep these out of any persistent cache. And compile as the service
    # runs: 32-bit (a packed64 test in the same process may have turned on
    # x64, under which Mosaic refuses these kernels).
    prev = jax.config.jax_enable_compilation_cache, jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev[0])
    jax.config.update("jax_enable_x64", prev[1])
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


@pytest.fixture(scope="module")
def four_chips(v5e):
    assert len(v5e.devices) == 4
    return Mesh(np.asarray(v5e.devices), ("shard",))


def _compile(fn, *shapes, kernel=True):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert (KERNEL_CALL in compiled.as_text()) == kernel
    return compiled


def _held(m) -> int:
    """Bytes a program holds at once on one device: inputs, outputs, temps."""
    return (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
        - m.alias_size_in_bytes
    )


def _tables(sharding, nb):
    k = max(1, (nb - 1).bit_length() + 1)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return s, k, s((BATCH,), jnp.int32)


@pytest.mark.parametrize("fetch,nb", [("resident", 1 << 13), ("dma", 1 << 19)])
def test_fused_query_compiles(one_chip, fetch, nb):
    s, k, q = _tables(one_chip, nb)
    _compile(
        lambda xb, bv, bg, st, l, r: fused_query(
            xb, bv, bg, st, l, r, fetch=fetch, interpret=False, materialize_interior=True
        ),
        s((nb, 128), jnp.float32), s((nb,), jnp.float32), s((nb,), jnp.int32),
        s((k, nb), jnp.int32), q, q,
    )


@pytest.mark.parametrize("layout", ["packed32", "quantized"])
def test_fused_query_packed_compiles(one_chip, layout):
    nb = 1 << 13
    s, k, q = _tables(one_chip, nb)
    bits = packing.idx_bits_for(nb * 128)
    if layout == "packed32":
        spec = packing.PackSpec("packed32", "int32", bits, 31 - bits)
        _compile(
            lambda xb, stw, l, r: fused_query_packed(
                xb, stw, l, r, spec=spec, interpret=False
            ),
            s((nb, 128), jnp.int32), s((k, nb), jnp.int32), q, q,
        )
    else:
        spec = packing.PackSpec("quantized", "float32", bits, 31 - bits, qscale=1e-3)
        _compile(
            lambda xb, stw, bv, l, r: fused_query_packed(
                xb, stw, l, r, spec=spec, bmin_val=bv, interpret=False
            ),
            s((nb, 128), jnp.float32), s((k, nb), jnp.int32), s((nb,), jnp.float32), q, q,
        )


def test_block_min_compiles(one_chip):
    s, _, _ = _tables(one_chip, 1 << 19)
    _compile(lambda x: block_min(x, interpret=False), s((1 << 19, 128), jnp.float32))


def test_partials_kernels_compile(one_chip):
    nb = 1 << 13
    s, _, q = _tables(one_chip, nb)
    _compile(
        lambda x, a, b, c, d, e: rmq_partials(x, a, b, c, d, e, interpret=False),
        s((nb, 128), jnp.float32), q, q, q, q, q,
    )
    v, i = s((nb, 128), jnp.float32), s((nb, 128), jnp.int32)
    _compile(
        lambda xs, sv, si, pv, pi, a, b, c, d: lane_partials(
            xs, sv, si, pv, pi, a, b, c, d, interpret=False
        ),
        v, v, i, v, i, q, q, q, q,
    )


def test_hybrid_short_path_fits_v5e(one_chip):
    """The hybrid engine's short path at n = 2^26 (nb = 2^19, dma fetch) —
    the query the served path launches — compiles and fits one chip."""
    x = jax.ShapeDtypeStruct((N_ONE_CHIP,), jnp.float32, sharding=one_chip)
    built = jax.eval_shape(lambda x: ops.build(x, 128, interpret=True), x)
    built = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), built
    )
    q = jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=one_chip)
    compiled = _compile(lambda s, l, r: ops.query(s, l, r, interpret=False), built, q, q)
    assert _held(compiled.memory_analysis()) < V5E_HBM_BYTES


def test_hybrid_build_fits_v5e(one_chip):
    """The hybrid build at n = 2^26. Its doubling table over x has 27 rows,
    padded to 32 in HBM (8 GiB); it is built beside the kernel structures
    (``ops.build``) and must fit with them. Rows are written into the table
    in place, so the build's temporaries stay a small share of the table."""
    x = jax.ShapeDtypeStruct((N_ONE_CHIP,), jnp.float32, sharding=one_chip)
    blocked = _compile(lambda x: ops.build(x, 128, interpret=False), x).memory_analysis()
    st = _compile(sparse_table.build, x, kernel=False).memory_analysis()
    assert st.output_size_in_bytes >= 8 << 30
    assert st.temp_size_in_bytes < st.output_size_in_bytes // 4
    assert _held(st) + blocked.output_size_in_bytes < V5E_HBM_BYTES


def test_sharded_hybrid_build_and_query_fit_four_v5e(four_chips):
    """sharded_hybrid's shard_structure mode at n = 2^27 on the 2x2 mesh.
    Its (idx, val) doubling table is 28 rows (32 padded) x 2^27 columns,
    32 GiB in all, 8 GiB per chip: more than one chip holds. The halo build
    writes rows in place, and the table, the blocked structure and the long
    query's temporaries fit each chip."""
    n, axes = N_FOUR_CHIPS, ("shard",)
    col = lambda dt: jax.ShapeDtypeStruct((n,), dt, sharding=NamedSharding(four_chips, P(axes)))
    halo = _compile(
        lambda i, v: distributed.st_halo_doubling(i, v, four_chips, axes),
        col(jnp.int32), col(jnp.float32), kernel=False,
    ).memory_analysis()
    assert halo.output_size_in_bytes >= 8 << 30
    assert halo.temp_size_in_bytes < halo.output_size_in_bytes // 4
    blocked = _compile(
        lambda x: distributed.build_sharded(x, four_chips, axes, 128),
        col(jnp.float32), kernel=False,
    ).memory_analysis()

    k = distributed.st_levels(n)
    table = NamedSharding(four_chips, P(None, axes))
    st = distributed.ShardedSparseTable(
        idx=jax.ShapeDtypeStruct((k, n), jnp.int32, sharding=table),
        val=jax.ShapeDtypeStruct((k, n), jnp.float32, sharding=table),
    )
    q = jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=NamedSharding(four_chips, P()))
    query = _compile(
        distributed.make_st_query_fn(four_chips, axes), st, q, q, kernel=False
    ).memory_analysis()
    held = _held(halo) + blocked.output_size_in_bytes + query.temp_size_in_bytes
    assert held < V5E_HBM_BYTES

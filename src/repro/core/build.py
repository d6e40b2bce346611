"""Staged BuildPlan pipeline: the one path every engine build lowers through.

The paper's preprocessing step (building the blocked / sparse-table structure
rays are cast against) is the scalability bottleneck the serving layer
inherits, so construction is a first-class pipeline rather than a pile of
per-engine build functions. A ``BuildPlan`` is an ordered list of named
stages over a shared build-state dict:

    shard_layout   host-side: shard geometry (``ShardLayout``) + padding
    local_build    per-shard structures, no communication
    halo_exchange  collectives only (the distributed doubling recurrence)
    finalize       assemble the engine state (+ jitted query closures)

Single-host engines carry the degenerate layout (one shard) and skip the
halo stage; mesh engines get real sharding and — for the column-sharded
doubling table — a build whose per-device memory is bounded by the shard,
never the full (K, n) table (``distributed.st_local_level0`` /
``st_halo_doubling``).

``plan_for(engine, n, ...)`` resolves everything static at plan time (shard
geometry, the routing threshold including cache/calibration policy, the
distribution mode), so a plan is inspectable metadata: the serving layer
derives warmup query regimes from it (``warmup_bounds``) and benchmarks
observe per-stage allocations (``execute(..., observer=...)``).

``registry.EngineSpec`` lowers both its ``build`` and its serving build
through ``build()`` / ``plan_for()`` + ``execute()``; ``hybrid.build``,
``sharded_hybrid.build`` and ``distributed.build_sharded_st`` are thin
wrappers over the same planners.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as obs_trace

from . import block_rmq, calib_cache, distributed, lane_rmq, lca, packing, sparse_table

__all__ = [
    "BuildPlan",
    "BuildStage",
    "STAGE_NAMES",
    "ShardLayout",
    "build",
    "default_mesh",
    "device_bytes_limit",
    "execute",
    "execute_update",
    "hybrid_table_bytes",
    "plan_for",
    "planner_names",
    "run_stages",
    "update_plan",
    "warmup_bounds",
]

# Canonical stage order. The first four are the build pipeline; the last two
# are the online-update pipeline (``repro.update``): ``apply_deltas`` patches
# structures incrementally from a coalesced DeltaBatch, ``publish`` installs
# the patched state as the next MVCC version.
STAGE_NAMES = (
    "shard_layout",
    "local_build",
    "halo_exchange",
    "finalize",
    "apply_deltas",
    "publish",
)


class ShardLayout(NamedTuple):
    """Static shard geometry, resolved at plan time from ``n`` alone."""

    n: int  # logical array length (pre-padding)
    n_pad: int  # padded length (shard-divisible)
    num_shards: int  # flattened structure-shard count (1 on a single host)
    shard_len: int  # columns per structure shard (n_pad on a single host)


class BuildStage(NamedTuple):
    """One named pipeline stage: ``fn`` advances the build-state dict."""

    name: str  # one of STAGE_NAMES
    fn: Callable[[dict], dict]


class BuildPlan(NamedTuple):
    """A fully-resolved build: static layout + metadata + executable stages."""

    engine: str
    layout: ShardLayout
    stages: Tuple[BuildStage, ...]
    meta: Dict[str, Any]  # resolved threshold / mode / block_size / mesh ...


def default_mesh():
    """The all-devices 1-D mesh: (mesh, axis_names) — the one definition of
    "no mesh was passed", shared by the registry and the serve CLI."""
    from repro.launch.mesh import make_mesh

    return make_mesh((len(jax.devices()),), ("shard",)), ("shard",)


def _mesh_or_default(mesh, axis_names):
    if mesh is None:
        return default_mesh()
    return mesh, tuple(axis_names if axis_names is not None else mesh.axis_names)


def _resolve_threshold(
    threshold,
    n: int,
    block_size: int,
    *,
    n_devices: Optional[int] = None,
    cache_path=None,
    calibrate_kw: Optional[dict] = None,
    key_mode: Optional[str] = None,
    key_mesh_shape=None,
    layout: Optional[str] = None,
) -> int:
    """The routing-threshold policy, shared by both hybrid planners.

    ``None`` -> deterministic sqrt(n) (never touches machine state);
    ``"cached"`` -> persistent cache with the sqrt(n) fallback, never
    measuring; ``"calibrated"`` -> measure via ``hybrid.calibrate`` on a
    miss and persist (``calibrate_kw`` carries the mesh for sharded-aware
    measurement); an int pins it.

    Sharded planners pass ``key_mode``/``key_mesh_shape`` (cache key v2) so
    every (mode, mesh factoring) owns its own cached threshold; single-host
    planners omit them and keep reading their v1 entries. ``layout`` (cache
    key v3) scopes the measurement to a packed word layout — the crossover
    moves when both tiers read packed planes.
    """
    from . import hybrid  # deferred: hybrid lowers its build through here

    if threshold is None:
        return hybrid.regime_boundary(n)
    if isinstance(threshold, (int, np.integer)):
        return int(threshold)
    if threshold == "cached":
        key = calib_cache.cache_key(
            n,
            block_size,
            n_devices=n_devices,
            mode=key_mode,
            mesh_shape=key_mesh_shape,
            layout=layout,
        )
        hit = calib_cache.load(key, path=cache_path)
        if hit is not None:
            return hit
        return hybrid.regime_boundary(n)
    if threshold == "calibrated":
        return calib_cache.get_threshold(
            n,
            block_size,
            n_devices=n_devices,
            mode=key_mode,
            mesh_shape=key_mesh_shape,
            path=cache_path,
            layout=layout,
            **(calibrate_kw or {}),
        )
    raise ValueError(
        f"threshold must be an int, None, 'cached' or 'calibrated'; got {threshold!r}"
    )


def _resolve_kernel_config(kernel_config, n: int, block_size: int | None = None):
    """The megakernel launch-geometry policy (mirrors ``_resolve_threshold``).

    ``None`` -> the deterministic default config (never touches machine
    state); ``"cached"`` -> the persistent cache, default fallback, never
    measuring; ``"tuned"`` -> the cache, sweeping via ``tuning.autotune``
    only on a miss; a ``tuning.KernelConfig`` (or compatible tuple) pins it.
    ``block_size`` pins that knob when the caller's structure already
    committed to one.
    """
    from repro.kernels import tuning  # deferred: keep core importable alone

    if kernel_config is None or isinstance(kernel_config, str):
        return tuning.get_config(n, policy=kernel_config, block_size=block_size)
    return tuning.KernelConfig(*kernel_config)


def _check_kernel_block_size(block_size: int) -> None:
    """Refuse, when the plan is made, a block size the kernels cannot launch
    with on this backend (compiled launches DMA 128-lane rows), rather than
    after the whole build, at the first warm-up launch."""
    from repro.kernels import tiling  # deferred: keep core importable alone

    tiling.resolve_interpret(None, row=block_size)


def _norm_packed(packed) -> Optional[str]:
    """Normalise the ``packed=`` build kwarg to a layout request or ``None``.

    ``None``/``False`` -> unpacked structures (the historical default);
    ``True`` -> ``"auto"``; otherwise one of ``packing.LAYOUTS`` or
    ``"auto"``. The request is resolved to a concrete ``PackSpec`` only at
    execute time (``packing.spec_for``) — the winning layout depends on the
    data's key range, which a plan (static, pre-``x``) cannot see.
    """
    if packed is None or packed is False:
        return None
    if packed is True:
        return "auto"
    packed = str(packed)
    if packed == "unpacked":
        return None
    if packed != "auto" and packed not in packing.PACKED_LAYOUTS:
        raise ValueError(
            f"packed must be one of {('auto',) + packing.PACKED_LAYOUTS}, "
            f"a bool, or None; got {packed!r}"
        )
    return packed


def device_bytes_limit() -> Optional[int]:
    """The memory the first device holds (``memory_stats()["bytes_limit"]``),
    or None where the backend reports none (the CPU)."""
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return None if limit is None else int(limit)


# Widest value the kernels serve (float32, int32): the plan sees n, not x.
_VALUE_BYTES = 4


def hybrid_table_bytes(n: int, *, word_bytes: int = 4, block_size: int = 128) -> int:
    """Device bytes a hybrid build holds at its peak when it builds the
    doubling table over the raw array, ``x`` included.

    The table is ``sparse_table.levels(n)`` rows of ``n`` words, its rows
    padded to a multiple of 8 as a TPU lays them out (27 -> 32 rows at
    n = 2^26: 8 GiB). Beside it: ``x`` and its padded block copy, the three
    planes of the kernel's table over block minima and the minima
    themselves, and the table build's transient, three length-``n`` rows
    (its copy of ``x`` and two rows of temporaries: v5e compile).
    """
    nb = -(-n // block_size)
    nbp = -(-nb // 128) * 128
    pad8 = lambda rows: -(-rows // 8) * 8  # noqa: E731
    table = pad8(sparse_table.levels(n)) * n * word_bytes
    blocks = 2 * nb * block_size * _VALUE_BYTES + 2 * nb * 4
    block_tables = 3 * pad8(sparse_table.levels(nb)) * nbp * 4
    return table + 3 * n * 4 + blocks + block_tables


# --- pipeline execution -----------------------------------------------------


def run_stages(plan: BuildPlan, state: dict, *, observer: Optional[Callable] = None):
    """Advance ``state`` through ``plan``'s stages; return ``state["result"]``.

    The one stage sequencer behind both pipelines (build and online update).
    ``observer(stage_name, state)`` fires after each stage — the seam the
    build-memory benchmark, the no-full-table allocation probes, and the
    update-throughput breakdown hook. When the process-global tracer is
    enabled, each stage additionally lands as a span (named after the stage,
    ``engine`` attr from the plan) under whatever span is ambient — build
    stages under the CLI's build span, update stages under the server's
    ``update`` span (DESIGN.md §14).
    """
    tr = obs_trace.get_tracer()
    if not tr.enabled:
        for stage in plan.stages:
            state = stage.fn(state)
            if observer is not None:
                observer(stage.name, state)
        return state["result"]
    for stage in plan.stages:
        with tr.span(stage.name, attrs={"engine": plan.engine}):
            state = stage.fn(state)
        if observer is not None:
            observer(stage.name, state)
    return state["result"]


def execute(plan: BuildPlan, x, *, observer: Optional[Callable] = None):
    """Run ``plan``'s build stages over ``x``; return the finalize result."""
    x = jnp.asarray(x)
    if x.ndim != 1 or x.shape[0] != plan.layout.n:
        raise ValueError(
            f"plan for n={plan.layout.n} executed on array of shape {x.shape}"
        )
    return run_stages(plan, {"x": x}, observer=observer)


# --- online-update pipeline --------------------------------------------------


def update_plan(
    engine: str,
    layout: ShardLayout,
    apply_fn: Callable[[dict], dict],
    publish_fn: Callable[[dict], dict],
    meta: Optional[Dict[str, Any]] = None,
) -> BuildPlan:
    """The two-stage online-update plan: ``apply_deltas`` -> ``publish``.

    ``apply_fn`` consumes ``state["deltas"]`` (a coalesced
    ``repro.update.DeltaBatch``) and writes ``state["patched"]`` (the next
    engine state, copy-on-write over the previous version's leaves);
    ``publish_fn`` installs it as the next MVCC version and writes
    ``state["result"]`` (an ``UpdateResult``). ``repro.update.OnlineEngine``
    constructs these plans; they run through the same ``run_stages``
    sequencer (and observer seam) as builds.
    """
    return BuildPlan(
        engine,
        layout,
        (
            BuildStage("apply_deltas", apply_fn),
            BuildStage("publish", publish_fn),
        ),
        dict(meta or {}),
    )


def execute_update(plan: BuildPlan, deltas, *, observer: Optional[Callable] = None):
    """Run an update plan over a coalesced ``DeltaBatch``."""
    return run_stages(plan, {"deltas": deltas}, observer=observer)


_PLANNERS: Dict[str, Callable] = {}


def _planner(name: str):
    def deco(fn):
        _PLANNERS[name] = fn
        return fn

    return deco


def planner_names() -> Tuple[str, ...]:
    return tuple(sorted(_PLANNERS))


def plan_for(engine: str, n: int, *, mesh=None, axis_names=None, **kwargs) -> BuildPlan:
    """Resolve the staged BuildPlan for ``engine`` over a length-``n`` array."""
    try:
        planner = _PLANNERS[engine]
    except KeyError:
        raise ValueError(
            f"no build planner for engine {engine!r}; have {planner_names()}"
        ) from None
    return planner(int(n), mesh=mesh, axis_names=axis_names, **kwargs)


def build(engine: str, x, *, mesh=None, axis_names=None, observer=None, **kwargs):
    """The single build entry point: ``plan_for`` + ``execute`` in one call."""
    x = jnp.asarray(x)
    plan = plan_for(engine, x.shape[0], mesh=mesh, axis_names=axis_names, **kwargs)
    return execute(plan, x, observer=observer)


def warmup_bounds(plan: BuildPlan) -> Callable[[int], list]:
    """Plan-derived warmup batches: ``(size) -> [(l, r), ...]`` int32 arrays.

    One batch per query regime the built engine can dispatch to: threshold
    engines get a longest-still-short probe and (when any length routes
    long) a full-range probe, so every constituent path compiles before the
    first client; single-path engines get the two extremes.
    """
    n = plan.layout.n
    thr = plan.meta.get("threshold")

    def bounds(size: int) -> list:
        zeros = np.zeros(size, np.int32)
        if thr is None:  # single-path engine: the two extremes
            out = [(zeros, zeros)]
            if n > 1:
                out.append((zeros, np.full(size, n - 1, np.int32)))
            return out
        out = []
        if thr >= 1:  # longest range that still routes short
            out.append((zeros, np.full(size, min(thr, n) - 1, np.int32)))
        if n > thr:  # full range routes long
            out.append((zeros, np.full(size, n - 1, np.int32)))
        return out

    return bounds


# --- single-host planners ---------------------------------------------------


def _single_host_plan(engine, n, build_fn, *, with_x=False, meta=None) -> BuildPlan:
    layout = ShardLayout(n=n, n_pad=n, num_shards=1, shard_len=n)

    def local(state):
        state["built"] = build_fn(state["x"])
        return state

    def fin(state):
        state["result"] = (state["built"], state["x"]) if with_x else state["built"]
        return state

    return BuildPlan(
        engine,
        layout,
        (
            BuildStage("shard_layout", lambda state: state),
            BuildStage("local_build", local),
            BuildStage("finalize", fin),
        ),
        dict(meta or {}),
    )


@_planner("sparse_table")
def _plan_sparse_table(n, *, mesh=None, axis_names=None, packed=None):
    layout = _norm_packed(packed)
    if layout is None:
        return _single_host_plan("sparse_table", n, sparse_table.build, with_x=True)
    # Packed state is ``((PackedSparseTable, PackSpec), x)`` — the registry
    # query wrapper dispatches on the tuple shape.
    return _single_host_plan(
        "sparse_table",
        n,
        lambda x: sparse_table.build_packed(x, layout=layout),
        with_x=True,
        meta={"packed": layout},
    )


@_planner("block")
def _plan_block(n, *, mesh=None, axis_names=None, block_size=128, packed=None):
    layout = _norm_packed(packed)
    if layout is None:
        return _single_host_plan(
            "block",
            n,
            lambda x: block_rmq.build(x, block_size),
            meta={"block_size": block_size},
        )
    return _single_host_plan(
        "block",
        n,
        lambda x: block_rmq.build_packed(x, block_size, layout=layout),
        meta={"block_size": block_size, "packed": layout},
    )


@_planner("lane")
def _plan_lane(n, *, mesh=None, axis_names=None):
    return _single_host_plan("lane", n, lane_rmq.build)


@_planner("lca")
def _plan_lca(n, *, mesh=None, axis_names=None):
    return _single_host_plan("lca", n, lca.build, with_x=True)


@_planner("exhaustive")
def _plan_exhaustive(n, *, mesh=None, axis_names=None):
    return _single_host_plan("exhaustive", n, lambda x: x, with_x=True)


@_planner("fused")
def _plan_fused(
    n, *, mesh=None, axis_names=None, block_size=None, kernel_config=None, packed=None
):
    layout = _norm_packed(packed)
    cfg = _resolve_kernel_config(kernel_config, n, block_size)
    # A tuned config may carry its own block size; an explicit block_size
    # pins the sweep, so the two can never disagree. A tuned layout rides
    # along the same way: the config's own layout field wins unless the
    # caller pins one via ``packed=``.
    bs = block_size if block_size is not None else cfg.block_size
    _check_kernel_block_size(bs)
    if layout is None and cfg.layout != "unpacked":
        layout = cfg.layout
    if layout == "packed64":
        raise ValueError(
            "packed64 words are int64 — outside the TPU kernel vocabulary; "
            "use the XLA engines (sparse_table/block/hybrid with packed=) "
            "or packed32/quantized for the fused kernels"
        )

    def build_fn(x):
        from repro import kernels

        if layout is None:
            return kernels.ops.build(x, bs)
        return kernels.ops.build_packed(x, bs, layout=layout)

    def fin(state):
        state["result"] = (state["built"], cfg)
        return state

    plan = _single_host_plan(
        "fused",
        n,
        build_fn,
        meta={"block_size": bs, "kernel_config": cfg, "packed": layout},
    )
    stages = tuple(
        BuildStage("finalize", fin) if s.name == "finalize" else s for s in plan.stages
    )
    return plan._replace(stages=stages)


@_planner("hybrid")
def _plan_hybrid(
    n,
    *,
    mesh=None,
    axis_names=None,
    block_size=128,
    threshold=None,
    use_kernels=None,
    kernel_config=None,
    packed=None,
):
    pack_layout = _norm_packed(packed)
    if use_kernels is None:
        use_kernels = jax.default_backend() == "tpu"
    if use_kernels:
        _check_kernel_block_size(block_size)
    # The one place that decides whether the doubling table over the raw
    # array is built. Where the device reports its memory and the table does
    # not fit beside the rest of the build, the kernel answers every range
    # (its interior lookups use the table over block minima); the online
    # hybrid (``use_kernels=False``) keeps its table.
    long_path = "table"
    limit = device_bytes_limit()
    if limit is not None:
        word = 8 if pack_layout == "packed64" else 4
        if hybrid_table_bytes(n, word_bytes=word, block_size=block_size) > limit:
            if pack_layout is not None:
                raise ValueError(
                    f"packed hybrid at n={n}: its {pack_layout} doubling table over "
                    f"the raw array does not fit the device's {limit} bytes"
                )
            if use_kernels:
                long_path = "kernel"
    if long_path == "kernel":
        thr = n
    else:
        thr = _resolve_threshold(
            threshold,
            n,
            block_size,
            calibrate_kw={"use_kernels": use_kernels},
            layout=pack_layout,
        )
    # The megakernel's launch geometry, swept within this build's block size
    # (the hybrid's structures are committed to it). Resolved only when the
    # short path actually runs the kernels.
    cfg = (
        _resolve_kernel_config(kernel_config, n, block_size) if use_kernels else None
    )
    layout = ShardLayout(n=n, n_pad=n, num_shards=1, shard_len=n)

    def local(state):
        x = state["x"]
        if pack_layout is not None:
            # One spec for both tiers: blocked and doubling structures pack
            # against the same (key bias, idx width), so cross-tier merges in
            # ``dispatch_by_length`` compare words from one total order.
            spec = packing.spec_for(x, n, pack_layout)
            state["spec"] = spec
            if use_kernels and spec.layout in ("packed32", "quantized"):
                from repro import kernels

                state["blocked"], _ = kernels.ops.build_packed(
                    x, block_size, spec=spec
                )
            else:
                # packed64 (int64 words) lives outside the TPU kernel
                # vocabulary; XLA packed structures serve it.
                state["blocked"], _ = block_rmq.build_packed(x, block_size, spec=spec)
            state["st"], _ = sparse_table.build_packed(x, spec=spec)
            return state
        if use_kernels:
            from repro import kernels

            state["blocked"] = kernels.ops.build(x, block_size)
        else:
            state["blocked"] = block_rmq.build(x, block_size)
        state["st"] = sparse_table.build(x) if long_path == "table" else None
        return state

    def fin(state):
        from . import hybrid

        x, blocked, table = state["x"], state["blocked"], state["st"]
        spec = state.get("spec")
        if spec is not None:
            if use_kernels and spec.layout in ("packed32", "quantized"):
                from repro import kernels

                short_fn = lambda l, r: kernels.ops.query_packed(
                    blocked, spec, l, r, config=cfg
                )
            else:
                short_fn = lambda l, r: block_rmq.query_packed(blocked, spec, l, r)
            long_fn = lambda l, r: sparse_table.query_packed(table, spec, l, r)
        else:
            if use_kernels:
                from repro import kernels

                # The kernel call is jitted inside, with the structure as
                # arguments; the tuned launch geometry rides as a static.
                short_fn = functools.partial(kernels.ops.query, blocked, config=cfg)
            else:
                short_fn = functools.partial(hybrid.block_query, blocked)
            long_fn = None if table is None else functools.partial(hybrid.long_query, table, x)

        state["result"] = hybrid.HybridRMQ(
            blocked=blocked,
            st=table,
            x=x,
            threshold=thr,
            use_kernels=bool(use_kernels),
            short_fn=short_fn,
            long_fn=long_fn,
        )
        return state

    return BuildPlan(
        "hybrid",
        layout,
        (
            BuildStage("shard_layout", lambda state: state),
            BuildStage("local_build", local),
            BuildStage("finalize", fin),
        ),
        {
            "block_size": block_size,
            "threshold": thr,
            "use_kernels": bool(use_kernels),
            "kernel_config": cfg,
            "packed": pack_layout,
            "long_path": long_path,
        },
    )


# --- mesh planners ----------------------------------------------------------


def _st_layout(n: int, num: int) -> ShardLayout:
    n_pad = -(-max(n, 1) // num) * num
    return ShardLayout(n=n, n_pad=n_pad, num_shards=num, shard_len=n_pad // num)


def _sharded_st_stages(mesh, axis_names, layout, *, key: str = "st"):
    """The distributed doubling-table build as (layout, local, halo) stage fns.

    Shared by the standalone ``sharded_st`` plan and the sharded-hybrid
    plans; writes ``{key}`` (a ``ShardedSparseTable``) into the build state.
    """

    def lay(state):
        x = state["x"]
        # Pad columns with +inf values; queries never index past n-1 and
        # every window [c, c + 2^k) they touch lies inside [l, r], so pads
        # never win.
        state[f"{key}_xp"] = jnp.pad(
            x, (0, layout.n_pad - layout.n), constant_values=block_rmq.maxval(x.dtype)
        )
        return state

    def local(state):
        idx0, val0 = distributed.st_local_level0(state[f"{key}_xp"], mesh, axis_names)
        state[f"{key}_level0"] = (idx0, val0)
        return state

    def halo(state):
        idx0, val0 = state.pop(f"{key}_level0")
        idx, val = distributed.st_halo_doubling(idx0, val0, mesh, axis_names)
        state[key] = distributed.ShardedSparseTable(idx=idx, val=val)
        del state[f"{key}_xp"]
        return state

    return lay, local, halo


@_planner("sharded_st")
def _plan_sharded_st(n, *, mesh=None, axis_names=None):
    mesh, axis_names = _mesh_or_default(mesh, axis_names)
    layout = _st_layout(n, distributed.num_shards(mesh, axis_names))
    lay, local, halo = _sharded_st_stages(mesh, axis_names, layout)

    def fin(state):
        state["result"] = state["st"]
        return state

    return BuildPlan(
        "sharded_st",
        layout,
        (
            BuildStage("shard_layout", lay),
            BuildStage("local_build", local),
            BuildStage("halo_exchange", halo),
            BuildStage("finalize", fin),
        ),
        {"mesh": mesh, "axis_names": axis_names},
    )


@_planner("distributed")
def _plan_distributed(n, *, mesh=None, axis_names=None, block_size=1024, packed=None):
    pack_layout = _norm_packed(packed)
    if pack_layout == "quantized":
        raise ValueError(
            "quantized packing is single-host only: its exact-fallback gather "
            "needs the raw blocks resident, which the sharded merge does not "
            "ship; use packed32/packed64/auto for mesh engines"
        )
    mesh, axis_names = _mesh_or_default(mesh, axis_names)
    num = distributed.num_shards(mesh, axis_names)
    chunk = num * block_size
    n_pad = -(-max(n, 1) // chunk) * chunk
    layout = ShardLayout(n=n, n_pad=n_pad, num_shards=num, shard_len=n_pad // num)

    def local(state):
        if pack_layout is not None:
            # auto resolves to packed32/packed64 only, never quantized.
            spec = packing.spec_for(state["x"], n, pack_layout)
            state["spec"] = spec
            state["blocked"] = distributed.build_sharded_packed(
                state["x"], mesh, axis_names, block_size, spec
            )
        else:
            state["blocked"] = distributed.build_sharded(
                state["x"], mesh, axis_names, block_size
            )
        return state

    def fin(state):
        if "spec" in state:
            qfn = distributed.make_packed_query_fn(mesh, axis_names, state["spec"])
        else:
            qfn = distributed.make_query_fn(mesh, axis_names)
        state["result"] = (state["blocked"], qfn)
        return state

    return BuildPlan(
        "distributed",
        layout,
        (
            BuildStage("shard_layout", lambda state: state),
            BuildStage("local_build", local),
            BuildStage("finalize", fin),
        ),
        {
            "block_size": block_size,
            "mesh": mesh,
            "axis_names": axis_names,
            "packed": pack_layout,
        },
    )


def _mode_axes(mode: str, axis_names: Tuple[str, ...]):
    """(structure axes, batch axes) per distribution mode.

    ``shard_2d`` puts the structure on the first axis and the batch on the
    rest; on a 1-axis mesh it degrades to ``shard_structure``.
    """
    if mode == "shard_structure":
        return axis_names, ()
    if mode == "shard_batch":
        return (), axis_names
    return axis_names[:1], axis_names[1:]  # shard_2d


@_planner("sharded_hybrid")
def _plan_sharded_hybrid(
    n,
    *,
    mesh=None,
    axis_names=None,
    block_size=128,
    threshold=None,
    mode="shard_structure",
    cache_path=None,
    packed=None,
):
    from . import sharded_hybrid

    if mode not in sharded_hybrid.MODES:
        raise ValueError(f"unknown mode {mode!r}; have {sharded_hybrid.MODES}")
    pack_layout = _norm_packed(packed)
    if pack_layout == "quantized":
        raise ValueError(
            "quantized packing is single-host only: its exact-fallback gather "
            "needs the raw blocks resident, which the sharded merge does not "
            "ship; use packed32/packed64/auto for mesh engines"
        )
    mesh, axis_names = _mesh_or_default(mesh, axis_names)
    num = distributed.num_shards(mesh, axis_names)
    struct_axes, batch_axes = _mode_axes(mode, axis_names)
    thr = _resolve_threshold(
        threshold,
        n,
        block_size,
        n_devices=num,
        cache_path=cache_path,
        # Sharded-aware measurement: calibrate times the sharded constituents
        # on this very mesh, so the cached value reflects collective costs.
        calibrate_kw={"use_kernels": False, "mesh": mesh, "axis_names": axis_names},
        # Cache key v2: the measurement varies per (mode, mesh factoring).
        key_mode=mode,
        key_mesh_shape=tuple(mesh.shape[a] for a in mesh.axis_names),
        layout=pack_layout,
    )
    num_struct = distributed.num_shards(mesh, struct_axes) if struct_axes else 1
    layout = _st_layout(n, num_struct)

    stages = []
    if struct_axes:
        lay, st_local, st_halo = _sharded_st_stages(mesh, struct_axes, layout)

        if pack_layout is not None:

            def local(state):
                x = state["x"]
                # One spec for both tiers (same key bias / idx width), so
                # the packed halo recurrence and the blocked merge share a
                # total order. Words carry GLOBAL indices — merges need no
                # per-shard offsetting and ship ONE plane per level.
                spec = packing.spec_for(x, n, pack_layout)
                state["spec"] = spec
                state["blocked"] = distributed.build_sharded_packed(
                    x, mesh, struct_axes, block_size, spec
                )
                state["st_w0"] = distributed.pack_global(x, spec, layout.n_pad)
                return state

            def halo(state):
                spec = state["spec"]
                words = distributed.st_halo_doubling_packed(
                    state.pop("st_w0"), mesh, struct_axes, spec
                )
                state["st"] = sparse_table.PackedSparseTable(words=words)
                return state

            stages.append(BuildStage("shard_layout", lambda state: state))
            stages.append(BuildStage("local_build", local))
            stages.append(BuildStage("halo_exchange", halo))
        else:

            def local(state):
                state["blocked"] = distributed.build_sharded(
                    state["x"], mesh, struct_axes, block_size
                )
                return st_local(state)

            stages.append(BuildStage("shard_layout", lay))
            stages.append(BuildStage("local_build", local))
            stages.append(BuildStage("halo_exchange", st_halo))
    else:  # shard_batch: replicated structures, no halo stage

        if pack_layout is not None:

            def local(state):
                x = state["x"]
                spec = packing.spec_for(x, n, pack_layout)
                state["spec"] = spec
                state["blocked"] = distributed.build_replicated_packed(
                    x, mesh, block_size, spec
                )
                state["st"] = distributed.build_replicated_st_packed(x, mesh, spec)
                return state

        else:

            def local(state):
                state["blocked"] = distributed.build_replicated(
                    state["x"], mesh, block_size
                )
                state["st"] = distributed.build_replicated_st(state["x"], mesh)
                return state

        stages.append(BuildStage("shard_layout", lambda state: state))
        stages.append(BuildStage("local_build", local))

    def _query_fns(spec):
        """Query closures, resolved at finalize time: the packed variants
        close over the data-dependent ``PackSpec`` a plan cannot know."""
        if spec is not None:
            if struct_axes:
                return (
                    distributed.make_packed_query_fn(
                        mesh, struct_axes, spec, batch_axes=batch_axes or None
                    ),
                    distributed.make_packed_st_query_fn(
                        mesh, struct_axes, spec, batch_axes=batch_axes or None
                    ),
                )
            return (
                distributed.make_packed_query_fn(
                    mesh, axis_names, spec, batch_sharded=True
                ),
                distributed.make_packed_st_query_fn(
                    mesh, axis_names, spec, batch_sharded=True
                ),
            )
        if struct_axes:
            return (
                distributed.make_query_fn(
                    mesh, struct_axes, batch_axes=batch_axes or None
                ),
                distributed.make_st_query_fn(
                    mesh, struct_axes, batch_axes=batch_axes or None
                ),
            )
        return (
            distributed.make_query_fn(mesh, axis_names, batch_sharded=True),
            distributed.make_st_query_fn(mesh, axis_names, batch_sharded=True),
        )

    def fin(state):
        x = state["x"]
        short_fn, long_fn = _query_fns(state.get("spec"))
        state["result"] = sharded_hybrid.ShardedHybridRMQ(
            blocked=state["blocked"],
            st=state["st"],
            n=int(n),
            threshold=int(thr),
            mode=mode,
            n_shards=int(num),
            dtype=np.dtype(x.dtype),
            short_fn=short_fn,
            long_fn=long_fn,
        )
        return state

    stages.append(BuildStage("finalize", fin))
    return BuildPlan(
        "sharded_hybrid",
        layout,
        tuple(stages),
        {
            "block_size": block_size,
            "threshold": int(thr),
            "mode": mode,
            "mesh": mesh,
            "axis_names": axis_names,
            "struct_axes": struct_axes,
            "batch_axes": batch_axes,
            "packed": pack_layout,
        },
    )

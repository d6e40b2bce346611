"""Range-adaptive hybrid RMQ dispatcher (the paper's crossover, exploited).

RTXRMQ §6 (and GPU-RMQ independently) report a regime-dependent winner: the
blocked/RT-style structure is fastest for *small* query ranges, while the
O(1) table-lookup family (LCA / sparse table) overtakes it at medium/large
ranges. This engine exploits that crossover instead of living on one side of
it: a batch is partitioned by range length against a threshold, short ranges
go to the blocked path (pure-jnp ``block_rmq`` on CPU, the fused Pallas
megakernel ``kernels.ops`` on TPU), long ranges go to the pure sparse-table
path, and the two result sets are scattered back into the original batch
order. Results are bit-identical to ``block_rmq.query`` — every constituent
engine implements exact leftmost-tie semantics.

``calibrate`` measures both constituent engines at a few range lengths and
returns the measured crossover threshold; ``build`` takes it (or a default)
as a static attribute. The partition runs host-side (numpy) — query batches
arrive from the host in serving anyway, and a data-dependent partition under
``jit`` would force padded two-sided execution, which is exactly the waste
this engine removes. See DESIGN.md §5.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as obs_trace

from . import block_rmq, sparse_table
from .block_rmq import BlockRMQ

__all__ = [
    "HybridRMQ",
    "build",
    "query",
    "calibrate",
    "dispatch_by_length",
    "record_splits",
    "DEFAULT_THRESHOLD_FRAC",
    "regime_boundary",
]

# Fallback threshold when no calibration is run: the paper's small/medium
# boundary sits near n**0.5 for the sizes it sweeps; ranges shorter than
# sqrt(n) touch only a couple of blocks and favor the blocked path.
DEFAULT_THRESHOLD_FRAC = 0.5  # threshold = n ** DEFAULT_THRESHOLD_FRAC

_INT32_MAX = np.iinfo(np.int32).max


def regime_boundary(n: int) -> int:
    """The deterministic sqrt(n) threshold: ranges up to it are short."""
    return max(1, int(round(n**DEFAULT_THRESHOLD_FRAC)))


class HybridRMQ(NamedTuple):
    """Both constituent structures, routing threshold, jitted path closures.

    ``st`` and ``long_fn`` are None when the build planner found no room for
    the doubling table over the raw array (``core.build``: the table is
    ~32 words per element): the fused kernel then answers every range, the
    long ones through its interior lookups on the table over block minima,
    and ``threshold`` is ``n``.
    """

    blocked: BlockRMQ
    st: Optional[sparse_table.SparseTable]  # doubling table over the raw array
    x: jax.Array  # raw values (answers value lookups for the long path)
    threshold: int  # range lengths <= threshold go to the blocked path
    use_kernels: bool  # short path: fused Pallas megakernel vs pure jnp
    short_fn: object  # jitted (l, r) -> (idx, val), structure closed over
    long_fn: object  # jitted (l, r) -> (idx, val); None without ``st``


def build(
    x: jax.Array,
    block_size: int = 128,
    *,
    threshold: int | str | None = None,
    use_kernels: bool | None = None,
    kernel_config=None,
    packed=None,
) -> HybridRMQ:
    """Build both constituent engines (via the staged ``core.build`` plan).

    ``threshold=None`` -> deterministic sqrt(n) default (never touches
    machine state); ``"cached"`` -> the persistent JSON cache
    (``calib_cache``) with the sqrt(n) fallback, never measuring;
    ``"calibrated"`` -> the cache, measuring via ``calibrate`` only on a
    miss, so repeated builds of the same configuration never re-measure.
    ``kernel_config`` is the megakernel launch-geometry policy for the
    kernelized short path (None | "cached" | "tuned" | a
    ``kernels.tuning.KernelConfig``), same cache lifecycle as thresholds.
    ``packed`` opts both tiers into fused (value, index) words
    (``core.packing``): None/False -> unpacked, True/"auto" -> measured
    best fit, or an explicit layout name.
    """
    from . import build as build_mod  # deferred: build.py hosts the planner

    return build_mod.build(
        "hybrid",
        x,
        block_size=block_size,
        threshold=threshold,
        use_kernels=use_kernels,
        kernel_config=kernel_config,
        packed=packed,
    )


def _long_query(table: sparse_table.SparseTable, x, l, r):
    idx = sparse_table.query(table, l, r)
    return idx, x[idx]


# The pure-jnp paths, jitted once at module level and bound to a structure
# with functools.partial: the structure is an argument, never a closed-over
# constant (which would copy a multi-GiB table into every lowered program),
# and a same-shape structure (a published update) is a jit-cache hit.
long_query = jax.jit(_long_query)
block_query = jax.jit(block_rmq.query)


# Per-thread sink for regime-split observations: the serving layer wraps each
# engine launch in ``record_splits`` so its stats can report how dispatch
# partitioned every coalesced batch without coupling the engines to the server.
_split_sink = threading.local()


@contextlib.contextmanager
def record_splits(cb, kernel_long=None):
    """Route this thread's ``dispatch_by_length`` splits to ``cb(n_short,
    n_long)``, and, from a dispatch with no long path, the number of long
    ranges its short path answered to ``kernel_long(n)``."""
    prev = getattr(_split_sink, "cb", None), getattr(_split_sink, "kernel_long", None)
    _split_sink.cb, _split_sink.kernel_long = cb, kernel_long
    try:
        yield
    finally:
        _split_sink.cb, _split_sink.kernel_long = prev


def _pad_pow2(lm: np.ndarray, rm: np.ndarray):
    """``(l, r, k)``: a sub-batch of ``k`` queries padded with (0, 0) to a
    power of two, so the jit cache stays bounded (log2(B) shapes per path)
    however batch sizes and splits vary."""
    k = lm.size
    kp = 1 << (k - 1).bit_length() if k > 1 else 1
    if kp != k:
        lp = np.zeros(kp, np.int64)
        rp = np.zeros(kp, np.int64)
        lp[:k] = lm
        rp[:k] = rm
        lm, rm = lp, rp
    return lm, rm, k


def dispatch_by_length(l, r, threshold: int, short_fn, long_fn, out_dtype):
    """Range-adaptive dispatch core, shared by ``hybrid`` and ``sharded_hybrid``.

    Host-side partition of the batch by range length against ``threshold``,
    per-regime launches through ``short_fn`` / ``long_fn`` (each
    ``(l_jnp, r_jnp) -> (idx, val)``), ordered exact-leftmost scatter-back.
    Empty batches return empty ``(idx, val)`` without launching anything.
    With ``long_fn=None`` nothing is partitioned: ``short_fn`` answers the
    whole batch in one launch, and the ranges longer than ``threshold`` are
    reported as the long ones it answered (``record_splits``).

    Bounds must be integer arrays inside the int32 index range: every
    constituent engine computes int32 indices, so an out-of-range bound
    would wrap silently instead of failing loudly — checked here, the one
    query path both hybrids share.

    Answers are returned where they already are: a uniform batch's as the
    launch's device arrays (the caller copies them once), a mixed batch's
    as host ``np.ndarray``s (int32 and ``out_dtype``), since the merge
    scatters both halves into batch order on the host and sending the
    result back to the device would only be copied out again.

    Each phase is a span under the caller's ambient one (the server's
    ``launch``): ``prepare`` (checks, casts, partition, padding), then per
    sub-batch ``h2d`` (its bounds to the device) and ``enqueue`` (its
    asynchronous launch), and on a mixed batch per sub-batch ``wait`` (on
    its launch) and ``merge`` (its answers to the host, scattered into
    batch order).
    """
    tr = obs_trace.get_tracer()
    with tr.span("prepare"):
        l = np.asarray(l)
        r = np.asarray(r)
        if not (np.issubdtype(l.dtype, np.integer) and np.issubdtype(r.dtype, np.integer)):
            raise TypeError(f"query bounds must be integer arrays, got {l.dtype} / {r.dtype}")
        l = l.astype(np.int64)
        r = r.astype(np.int64)
        if l.size == 0:  # nothing to do: no phantom padded query, no launch
            return jnp.zeros(0, jnp.int32), jnp.zeros(0, out_dtype)
        if int(l.min()) < 0 or int(r.max()) > _INT32_MAX:
            raise ValueError(
                f"query bounds [{int(l.min())}, {int(r.max())}] outside the engines' "
                "int32 index range"
            )
        short = (r - l + 1) <= threshold
        n_short = int(short.sum())
        if long_fn is None:  # no long path: nothing to route
            cb = getattr(_split_sink, "kernel_long", None)
            if cb is not None:
                cb(int(l.size - n_short))
            n_short = short.size
        else:
            cb = getattr(_split_sink, "cb", None)
            if cb is not None:
                cb(n_short, int(l.size - n_short))
        # Uniform batches skip the partition/scatter round-trip entirely.
        if n_short == short.size or n_short == 0:
            subs = [(short_fn if n_short else long_fn, None, *_pad_pow2(l, r))]
        else:
            subs = [
                (fn, mask, *_pad_pow2(l[mask], r[mask]))
                for mask, fn in ((short, short_fn), (~short, long_fn))
            ]
    # Each sub-batch launches as soon as its bounds are on the device, so
    # the first computes while the second's bounds travel. A mixed batch's
    # answers are copied to the host as soon as their launch ends, not when
    # the merge gets to them.
    outs = []
    for fn, mask, lp, rp, _ in subs:
        with tr.span("h2d"):
            lj, rj = jnp.asarray(lp), jnp.asarray(rp)
        with tr.span("enqueue"):
            out = fn(lj, rj)
            if mask is not None:
                for a in out:
                    if isinstance(a, jax.Array):
                        a.copy_to_host_async()
            outs.append(out)
    if len(subs) == 1:
        (qi, qv), k = outs[0], subs[0][4]
        return qi[:k], qv[:k]

    # Mixed batch: each sub-batch's answers come back and are scattered into
    # batch order as soon as they are ready, the first while the second runs.
    idx = np.empty(l.shape, np.int32)
    val = np.empty(l.shape, np.dtype(out_dtype))
    for (_, mask, _, _, k), (qi, qv) in zip(subs, outs):
        with tr.span("wait"):
            jax.block_until_ready((qi, qv))
        with tr.span("merge"):
            idx[mask] = np.asarray(qi)[:k]
            val[mask] = np.asarray(qv)[:k]
    return idx, val


def query(s: HybridRMQ, l, r) -> Tuple[jax.Array | np.ndarray, jax.Array | np.ndarray]:
    """Range-adaptive batched RMQ. Returns (leftmost argmin idx int32, value).

    Host-side partition by range length, per-engine sub-batches, ordered
    scatter-back. Bit-identical to ``block_rmq.query`` on the same batch.
    A uniform batch's answers are device arrays, a mixed batch's host
    ``np.ndarray``s (``dispatch_by_length``). Without a long path the
    kernel answers the whole batch; the sqrt(n) regime boundary then only
    counts the long ranges among it.
    """
    if s.long_fn is None:
        thr = regime_boundary(s.x.shape[0])
    else:
        thr = s.threshold
    return dispatch_by_length(l, r, thr, s.short_fn, s.long_fn, s.x.dtype)


def _measure(kind: str, fn, lj, rj, repeats: int) -> float:
    """Median wall seconds of one jitted path (post-warmup).

    ``kind`` names the path ("short" / "long") purely so tests can swap this
    out for a deterministic fake and pin calibrate's control flow.
    """
    del kind
    fn(lj, rj)  # warmup / compile
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(lj, rj))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def calibrate(
    n: int,
    batch: int = 4096,
    *,
    block_size: int = 128,
    use_kernels: bool | None = None,
    seed: int = 0,
    repeats: int = 3,
    mesh=None,
    axis_names=None,
    mode: str = "shard_structure",
    layout: str | None = None,
) -> int:
    """Time both constituent paths across range lengths; return the crossover.

    Sweeps log-spaced range lengths, measures the per-call median of each
    path on a ``batch``-sized query load, and returns the largest swept
    length at which the short (blocked) path still wins — i.e. the value to
    pass as ``threshold`` given the ``len <= threshold -> short`` routing.
    Degenerate measurements stay honest: ``n`` when the short path wins
    everywhere, ``0`` (route everything long) when the long path wins even
    at length 1.

    With ``mesh`` (+ optional ``axis_names``/``mode``) the *sharded*
    constituents are measured — the sharded blocked path and the
    column-sharded doubling table in the given distribution mode — so the
    threshold reflects collective costs on that mesh, not single-host
    proxies. The cache key already carries ``ndev``; this makes the
    measurement match it.

    ``layout`` (cache key v3) measures the *packed* constituents instead —
    the crossover moves when both tiers read fused (value, index) word
    planes. packed32's key-range precondition is data-dependent, so that
    measurement runs over a narrow-range int32 proxy array (the layout it
    times is the layout served); the other layouts keep the float proxy.
    """
    rng = np.random.default_rng(seed)
    if layout == "packed32":
        # A proxy whose key span always fits 31 - idx_bits value bits.
        x = jnp.asarray(rng.integers(-1000, 1000, size=n).astype(np.int32))
    else:
        x = jnp.asarray(rng.random(n, dtype=np.float32))
    if mesh is None:
        s = build(x, block_size, use_kernels=use_kernels, packed=layout)
        short_fn, long_fn = s.short_fn, s.long_fn  # both already jit-wrapped
    else:
        # Deferred import: sharded_hybrid builds on this module's dispatcher.
        from . import sharded_hybrid

        sh = sharded_hybrid.build(
            x, mesh, axis_names, block_size, threshold=0, mode=mode, packed=layout
        )
        short_fn = lambda l, r: sh.short_fn(sh.blocked, l, r)
        long_fn = lambda l, r: sh.long_fn(sh.st, l, r)

    lengths = np.unique(
        np.geomspace(1, n, num=8).astype(np.int64).clip(1, n)
    )
    crossover = None
    prev_length = 0
    for length in lengths:
        lo = rng.integers(0, max(n - length + 1, 1), batch)
        lj = jnp.asarray(lo)
        rj = jnp.asarray(np.minimum(lo + length - 1, n - 1))

        if _measure("long", long_fn, lj, rj, repeats) < _measure(
            "short", short_fn, lj, rj, repeats
        ):
            # The long path wins at `length`; routing is `len <= threshold ->
            # short`, so the threshold is the last length where short won.
            crossover = int(prev_length)
            break
        prev_length = int(length)
    if crossover is None:
        crossover = prev_length  # short path won at every swept length (= n)
    return crossover  # 0 => route everything long (long won even at len 1)

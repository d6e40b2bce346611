"""Persistent autotuner for the fused megakernel's launch geometry.

The megakernel has three static knobs — ``tile`` (queries per grid step),
``fetch`` (table strategy: VMEM-resident vs per-query DMA windows, see
``fused_query.py``), and ``block_size`` — and the right setting is a property
of (problem size, batch, machine), not of the code. This module sweeps the
config product, times each candidate with the same measurement seam
``hybrid.calibrate`` uses (``hybrid._measure``, monkeypatchable in tests),
and persists winners in the calibration JSON cache (``core.calib_cache``)
under a ``kernel/`` key namespace:

    kernel/n=65536/batch=4096/backend=tpu/ndev=8
        -> {"tile": 8, "fetch": "dma", "block_size": 128}

so serving and benchmarks load tuned configs with zero re-timing. Policy
resolution (``get_config``):

* ``None``      — the deterministic default config. Never touches the cache
  or any machine state: same answer on every host, before and after any
  cache write.
* ``"cached"``  — read-only cache lookup, default fallback on miss. Never
  measures.
* ``"tuned"``   — cache lookup; sweeps + persists on a miss, so repeated
  builds of one configuration time the product exactly once per machine.

The exemplar is the TVM/AttentionEngine autotuner shape (config product ->
timed best -> cached); the cache lifecycle (atomic writes, version staleness,
corrupt-file tolerance) is inherited from ``calib_cache``.
"""

from __future__ import annotations

import itertools
from typing import NamedTuple

__all__ = [
    "DEFAULT_TILE",
    "DEFAULT_TUNE_BATCH",
    "FETCH_STRATEGIES",
    "KernelConfig",
    "RESIDENT_NB_CEILING",
    "autotune",
    "candidate_configs",
    "config_from_entry",
    "default_config",
    "get_config",
    "resolve_fetch",
    "sweep",
    "tuning_key",
]

# Queries answered per grid step by the tiled query kernels. 8 packs a full
# sublane; the autotuner below replaces this guess per (n, batch, machine).
DEFAULT_TILE = 8

# Table fetch strategies fused_query implements (module docstring there).
FETCH_STRATEGIES = ("resident", "dma")

# Above this many blocks the resident strategy's VMEM-resident bmin planes
# and its one-hot hop over all nb lanes per query stop paying; "auto"
# switches to the bounded-VMEM dma strategy. See DESIGN.md §12.
RESIDENT_NB_CEILING = 1 << 13

# Swept values. Small on purpose: each candidate costs a build + timed
# queries, and the product is per (n, batch, backend, ndev) cache entry.
# Compiled kernels need a tile of whole sublanes (8) and a block of exactly
# one 128-lane row (``tiling.resolve_interpret``).
TUNE_TILES = (8, 16, 32)
TUNE_BLOCK_SIZES = (128,)
DEFAULT_TUNE_BATCH = 4096

# The packed-structure layout axis (DESIGN.md §13). ``candidate_configs``
# sweeps only "unpacked" unless the caller opts the axis in (pass
# ``layouts=TUNE_LAYOUTS`` or an explicit subset) — the packed kernels carry
# their own feasibility rules (packed64 words are int64, outside the TPU
# kernel vocabulary; packed32 needs the data's key range to fit; the
# quantized fallback hop needs its resident plane, so no dma strategy) and
# ``sweep`` skips candidates the sweep data cannot express.
TUNE_LAYOUTS = ("unpacked", "packed32", "quantized", "packed64")


class KernelConfig(NamedTuple):
    """Static launch geometry for the fused megakernel.

    ``layout`` (config v3) names the packed-structure layout the geometry
    was tuned for — "unpacked" is the historical default, so pre-layout
    configs (and positional 3-tuples) keep constructing unchanged.
    """

    tile: int = DEFAULT_TILE
    fetch: str = "auto"  # "resident" | "dma" | "auto" (resolve by nb)
    block_size: int = 128
    layout: str = "unpacked"  # "unpacked" | "packed32" | "quantized" | "packed64"


def resolve_fetch(fetch: str, nb: int) -> str:
    """Concrete fetch strategy for ``nb`` blocks ("auto" -> by the ceiling)."""
    if fetch == "auto":
        return "dma" if nb > RESIDENT_NB_CEILING else "resident"
    if fetch not in FETCH_STRATEGIES:
        raise ValueError(f"unknown fetch strategy {fetch!r} (want {FETCH_STRATEGIES})")
    return fetch


def default_config(block_size: int = 128) -> KernelConfig:
    """The untuned config: machine-independent, deterministic."""
    return KernelConfig(tile=DEFAULT_TILE, fetch="auto", block_size=block_size)


def candidate_configs(n: int, block_size: int | None = None, *, layouts=None):
    """The swept config product for an ``n``-element array.

    ``block_size`` pins that knob (hybrid builds tune within their block
    size; fused builds sweep it). Resident candidates past the nb ceiling
    are excluded — they are exactly the configs the ceiling exists to avoid.
    The default config's resolution is always a member, so the tuned winner
    can never be slower than the default on the sweep's own measurements.

    ``layouts`` opts the packed-structure axis in (e.g. ``TUNE_LAYOUTS``);
    the default sweeps only "unpacked". Statically-infeasible members are
    excluded here: packed64 words are int64 (outside the TPU kernel
    vocabulary — packed64 serves through the XLA packed engines instead),
    the quantized fallback hop keeps a resident plane, so it has no
    bounded-VMEM dma strategy, and packed32 has one kernel that fetches
    only DMA windows, so it is swept once per tile, as "dma". packed32's
    *data*-dependent feasibility
    (does the key range fit?) is settled by ``sweep`` per array.
    """
    sizes = (block_size,) if block_size is not None else TUNE_BLOCK_SIZES
    if layouts is None:
        layouts = ("unpacked",)
    out = []
    for bs, fetch, tile, lay in itertools.product(
        sizes, FETCH_STRATEGIES, TUNE_TILES, layouts
    ):
        if fetch == "resident" and -(-n // bs) > RESIDENT_NB_CEILING:
            continue
        if lay == "packed64":
            continue  # int64 words: no kernel path
        if lay == "quantized" and fetch == "dma":
            continue  # fallback hop needs the resident exact-minima plane
        if lay == "packed32" and fetch == "resident":
            continue  # one kernel, DMA windows only: timed once, as "dma"
        out.append(KernelConfig(tile=tile, fetch=fetch, block_size=bs, layout=lay))
    for bs in sizes:  # the resolved default, if the product missed it
        d = KernelConfig(DEFAULT_TILE, resolve_fetch("auto", -(-n // bs)), bs)
        if d not in out:
            out.append(d)
    return out


def tuning_key(
    n: int,
    batch: int = DEFAULT_TUNE_BATCH,
    *,
    backend: str | None = None,
    n_devices: int | None = None,
    layout: str | None = None,
) -> str:
    """Cache key for a tuned config: ``kernel/`` namespace + (n, batch,
    backend, ndev) — disjoint from the threshold keys in the same file.

    ``layout`` (key v3) scopes a tuning slot to one packed layout; the
    default appends nothing, so migrated v2 entries keep matching. A sweep
    run *across* layouts stores under the default slot — the winning
    config's own ``layout`` field records what won.
    """
    import jax

    if backend is None:
        backend = jax.default_backend()
    if n_devices is None:
        n_devices = len(jax.devices())
    key = f"kernel/n={n}/batch={batch}/backend={backend}/ndev={n_devices}"
    if layout is not None and layout != "unpacked":
        key += f"/layout={layout}"
    return key


def config_from_entry(entry) -> KernelConfig | None:
    """KernelConfig from a cached JSON entry; None if malformed (treated as
    a miss — a cache must never turn into a crash)."""
    if not isinstance(entry, dict):
        return None
    try:
        cfg = KernelConfig(
            tile=int(entry["tile"]),
            fetch=str(entry["fetch"]),
            block_size=int(entry["block_size"]),
            # Pre-layout entries (and migrated v2 files) mean unpacked.
            layout=str(entry.get("layout", "unpacked")),
        )
    except (KeyError, TypeError, ValueError):
        return None
    if cfg.fetch not in FETCH_STRATEGIES + ("auto",):
        return None
    if cfg.tile < 1 or cfg.block_size % 128 != 0:
        return None
    if cfg.layout not in TUNE_LAYOUTS:
        return None
    return cfg


def sweep(
    n: int,
    batch: int = DEFAULT_TUNE_BATCH,
    *,
    block_size: int | None = None,
    candidates=None,
    seed: int = 0,
    repeats: int = 3,
    interpret: bool | None = None,
):
    """Time every candidate config. Returns ``[(KernelConfig, seconds)]``.

    One mixed-length query batch (seeded, so the sweep is reproducible) is
    timed through the fused megakernel per candidate, via the exact
    measurement seam ``hybrid.calibrate`` uses (``hybrid._measure`` — tests
    monkeypatch it to make sweeps deterministic and to assert a warm cache
    performs zero of them). Builds are shared across the candidates of a
    (block size, layout). Packed candidates the sweep data cannot encode
    (a packed32 key range that does not fit) are skipped, not errored —
    the winner must come from configs this machine can actually run.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core import hybrid

    from . import ops

    if candidates is None:
        candidates = candidate_configs(n, block_size)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.random(n, dtype=np.float32))
    a = rng.integers(0, n, batch)
    b = rng.integers(0, n, batch)
    lj = jnp.asarray(np.minimum(a, b))
    rj = jnp.asarray(np.maximum(a, b))

    results = []
    built = {}
    for cfg in candidates:
        bkey = (cfg.block_size, cfg.layout)
        if bkey not in built:
            if cfg.layout == "unpacked":
                built[bkey] = (
                    ops.build(x, cfg.block_size, interpret=interpret),
                    None,
                )
            else:
                try:
                    built[bkey] = ops.build_packed(
                        x, cfg.block_size, layout=cfg.layout, interpret=interpret
                    )
                except ValueError:
                    built[bkey] = None  # data can't express this layout
        if built[bkey] is None:
            continue
        s, spec = built[bkey]

        if cfg.layout == "unpacked":

            def fn(l, r, s=s, cfg=cfg):
                return ops.query(s, l, r, config=cfg, interpret=interpret)

        else:

            def fn(l, r, s=s, spec=spec, cfg=cfg):
                return ops.query_packed(s, spec, l, r, config=cfg, interpret=interpret)

        kind = f"kernel/tile={cfg.tile}/fetch={cfg.fetch}/bs={cfg.block_size}"
        if cfg.layout != "unpacked":  # unpacked kinds stay v2-identical
            kind += f"/layout={cfg.layout}"
        results.append((cfg, hybrid._measure(kind, fn, lj, rj, repeats)))
    return results


def autotune(
    n: int,
    batch: int = DEFAULT_TUNE_BATCH,
    *,
    block_size: int | None = None,
    candidates=None,
    seed: int = 0,
    repeats: int = 3,
    interpret: bool | None = None,
) -> KernelConfig:
    """Sweep the config product and return the fastest candidate.

    Ties break toward the earliest candidate in the (deterministic) product
    order, so a fake-measure test pins the winner exactly.
    """
    results = sweep(
        n,
        batch,
        block_size=block_size,
        candidates=candidates,
        seed=seed,
        repeats=repeats,
        interpret=interpret,
    )
    best_cfg, _ = min(results, key=lambda cv: cv[1])
    return best_cfg


def get_config(
    n: int,
    batch: int = DEFAULT_TUNE_BATCH,
    *,
    policy: str | None = None,
    block_size: int | None = None,
    backend: str | None = None,
    n_devices: int | None = None,
    path=None,
    **tune_kw,
) -> KernelConfig:
    """Resolve the kernel config for an (n, batch) point under ``policy``.

    See the module docstring for the three policies. ``block_size`` pins the
    sweep (and the default's block size) when the caller's structure is
    already committed to one.
    """
    if policy is None:
        return default_config(block_size if block_size is not None else 128)
    if policy not in ("cached", "tuned"):
        raise ValueError(f"unknown kernel-config policy {policy!r}")

    from repro.core import calib_cache

    key = tuning_key(n, batch, backend=backend, n_devices=n_devices)
    cfg = config_from_entry(calib_cache.load_entry(key, path))
    if cfg is not None:
        return cfg
    if policy == "cached":
        return default_config(block_size if block_size is not None else 128)
    cfg = autotune(n, batch, block_size=block_size, **tune_kw)
    calib_cache.store_entry(key, dict(cfg._asdict()), path)
    return cfg

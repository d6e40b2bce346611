"""Online engines: incremental mutation + MVCC versions per registry engine.

``make_online(name, x)`` wraps an ``updatable`` registry engine in an
``OnlineEngine``: the initial state is built through the engine's staged
BuildPlan, and every subsequent mutation lowers through the two online
stages (``core.build.update_plan``: ``apply_deltas`` -> ``publish``) instead
of a rebuild. Queries pin a version from the MVCC store and never block on
mutation; ``apply`` is serialized (one updater at a time), so version ids
are the consistency order.

Per-engine patch strategy:

* ``sparse_table`` / ``block128`` / ``block256`` / ``hybrid`` — host numpy
  mirrors (``repro.update.patch``): windowed per-level doubling repair and
  O(bs) block-min repair, then the patched leaves are published as fresh
  device arrays (copy-on-write at the leaf level). Hybrid versions share
  module-level jitted query closures so a publish never retraces.
* ``distributed`` / ``sharded_hybrid`` (structure-sharded modes) — the SPMD
  patch kernels (``distributed.patch_sharded`` / ``patch_sharded_st``):
  updates scatter on the owning devices, doubling levels re-run masked to
  the affected windows with the ``_flat_shift`` halo transport across shard
  boundaries. Appends that fit the padded capacity are patches (pad columns
  become real); growing past capacity falls back to a structural rebuild
  through the engine's BuildPlan (reported via ``UpdateResult.patched``).
* ``sharded_hybrid`` (``shard_batch``) — host mirrors patched once, then
  re-replicated (each device holds the full structure by construction).

Every patched state is bit-identical to a from-scratch rebuild of the
mutated array — the acceptance criterion tests/test_update.py asserts
leaf-for-leaf.
"""

from __future__ import annotations

import functools
import json
import threading
import time
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import block_rmq, distributed, packing, registry, sparse_table
from repro.core import build as build_mod
from repro.obs import trace as obs_trace
from repro.core.block_rmq import BlockRMQ
from repro.core.hybrid import HybridRMQ, block_query, long_query
from repro.core.sparse_table import SparseTable

from .deltas import DeltaBatch, DeltaLog, shard_batches
from .patch import BlockMirror, PackedBlockMirror, PackedSTMirror, STMirror
from .patch import packed_fit_check
from .versions import Version, VersionStore

__all__ = [
    "EnginePoisoned",
    "OnlineEngine",
    "UpdateResult",
    "make_online",
    "online_names",
]


class EnginePoisoned(RuntimeError):
    """The engine fail-stopped after a mid-patch apply failure.

    Carries what recovery needs: ``cause`` is the original exception and
    ``seq`` the failing update's journal sequence number (``None`` when the
    engine runs unjournaled). Queries keep serving published versions; a
    successful checkpoint+journal restore (``fault.durable``) replaces the
    poisoned engine with a consistent one — the aborted seq is skipped on
    replay, so the restored state is the last published version.
    """

    def __init__(self, name: str, seq, cause: BaseException):
        at = f" applying journaled update seq {seq}" if seq is not None else ""
        super().__init__(
            f"online engine {name!r} is fail-stopped after an apply error{at}: "
            f"{cause!r}; restore from checkpoint+journal or rebuild (queries "
            f"still serve published versions)"
        )
        self.engine = name
        self.seq = seq
        self.cause = cause


class UpdateResult(NamedTuple):
    """What one applied update batch did."""

    version: int  # the published version id
    n: int  # logical array length after the batch
    patched: bool  # True = incremental patch; False = structural rebuild
    n_writes: int  # coalesced in-place writes
    n_appended: int  # appended elements
    seconds: float  # apply wall time (patch + publish material)
    touched_shards: int = 1  # structure shards owning >= 1 changed position
    # Host->device bytes this publish uploaded (single-host engines with the
    # windowed-COW publish; 0 = untracked — mesh engines scatter replicated
    # (pos, val) arrays inside shard_map, already O(batch) upload).
    publish_bytes: int = 0


def _block_state(m: BlockMirror) -> BlockRMQ:
    bmin = jnp.asarray(m.bmin_val)
    return BlockRMQ(
        x_blocks=jnp.asarray(m.x_blocks),
        bmin_val=bmin,
        bmin_gidx=jnp.asarray(m.bmin_gidx),
        st=SparseTable(idx=jnp.asarray(m.st_idx), x=bmin),
    )


# --- windowed copy-on-write publish ------------------------------------------
#
# A publish installs fresh device leaves for the next MVCC version. Uploading
# every host mirror in full costs ~O(n log n) host->device bytes for an
# O(log n)-window point patch (the ROADMAP carried-forward); instead each
# leaf keeps its current device array and a publish splices only the patched
# windows into it with one fused jit of chained dynamic_update_slice ops.
# The previous array is NOT donated — it belongs to a published version that
# pinned queries may still hold — so XLA materializes the copy device-side:
# COW is preserved while only the window bytes cross the host->device
# boundary. Window lengths are padded to powers of two (the padding uploads
# unchanged-but-correct mirror content) so the jit cache stays bounded at
# ~log2(n) shapes per leaf.


def _put(host, like):
    """Upload ``host`` beside ``like``: onto its device when ``like`` is
    committed to one (a fleet replica's array), else the default device."""
    if isinstance(like, jax.Array) and like.committed:
        return jax.device_put(host, like.sharding)
    return jnp.asarray(host)


def _cow_splice(dev, wins, starts):
    for w, st in zip(wins, starts):
        dev = jax.lax.dynamic_update_slice(dev, w, st)
    return dev


_cow_splice_jit = jax.jit(_cow_splice)


def _padded_span(a: int, b: int, m: int) -> Tuple[int, int]:
    """Inclusive [a, b] -> (start, pow2 length), shifted left to fit in m."""
    ln = b - a + 1
    p = 1 << (ln - 1).bit_length()
    if p >= m:
        return 0, m
    return min(a, m - p), p


class _CowLeaf:
    """One device-resident structure leaf published copy-on-write.

    ``full(host)`` re-uploads the mirror (shape changed); ``splice`` /
    ``splice_rows`` upload only the padded patch windows and splice them
    into the previous device array. Either way the uploaded byte count
    accumulates into the shared ``counter`` (an UpdateResult.publish_bytes
    source) and ``dev`` is the leaf for the next version.
    """

    __slots__ = ("dev", "_counter")

    def __init__(self, dev, counter):
        self.dev = dev
        self._counter = counter

    def full(self, host):
        self.dev = _put(host, self.dev)
        self._counter["bytes"] += int(self.dev.nbytes)
        return self.dev

    def splice(self, host, spans):
        """``spans``: (row, a, b) windows — row=None for a 1-D leaf."""
        if not spans:
            return self.dev
        m = int(host.shape[-1])
        wins, starts = [], []
        for row, a, b in spans:
            s, p = _padded_span(a, b, m)
            if row is None:
                w = jnp.asarray(host[s : s + p])
                starts.append((np.int32(s),))
            else:
                w = jnp.asarray(host[row : row + 1, s : s + p])
                starts.append((np.int32(row), np.int32(s)))
            wins.append(w)
            self._counter["bytes"] += int(w.nbytes)
        self.dev = _cow_splice_jit(self.dev, tuple(wins), tuple(starts))
        return self.dev

    def splice_rows(self, host, runs):
        """``runs``: inclusive (a, b) row ranges of a 2-D leaf (full width)."""
        if not runs:
            return self.dev
        nrows = int(host.shape[0])
        wins, starts = [], []
        for a, b in runs:
            s, p = _padded_span(a, b, nrows)
            w = jnp.asarray(host[s : s + p])
            wins.append(w)
            starts.append((np.int32(s), np.int32(0)))
            self._counter["bytes"] += int(w.nbytes)
        self.dev = _cow_splice_jit(self.dev, tuple(wins), tuple(starts))
        return self.dev


class _BlockLeaves:
    """The four device leaves of a ``BlockRMQ``, published copy-on-write."""

    def __init__(self, m: BlockMirror, counter, state: Optional[BlockRMQ] = None, like=None):
        if state is None:  # restore: seed from the mirror (no argmin rebuild)
            bv = _put(m.bmin_val, like)
            state = BlockRMQ(
                x_blocks=_put(m.x_blocks, like),
                bmin_val=bv,
                bmin_gidx=_put(m.bmin_gidx, like),
                st=SparseTable(idx=_put(m.st_idx, like), x=bv),
            )
        self.xb = _CowLeaf(state.x_blocks, counter)
        self.bv = _CowLeaf(state.bmin_val, counter)
        self.bg = _CowLeaf(state.bmin_gidx, counter)
        self.bst = _CowLeaf(state.st.idx, counter)

    def state(self) -> BlockRMQ:
        return BlockRMQ(
            x_blocks=self.xb.dev,
            bmin_val=self.bv.dev,
            bmin_gidx=self.bg.dev,
            st=SparseTable(idx=self.bst.dev, x=self.bv.dev),
        )

    def publish(self, m: BlockMirror) -> BlockRMQ:
        """Refresh the leaves from the just-patched mirror, windowed."""
        if m.last_block_runs is None:  # block count grew: shapes changed
            self.xb.full(m.x_blocks)
            self.bv.full(m.bmin_val)
            self.bg.full(m.bmin_gidx)
            self.bst.full(m.st_idx)
        else:
            runs1d = [(None, a, b) for a, b in m.last_block_runs]
            self.xb.splice_rows(m.x_blocks, m.last_block_runs)
            self.bv.splice(m.bmin_val, runs1d)
            self.bg.splice(m.bmin_gidx, runs1d)
            self.bst.splice(m.st_idx, m.last_st_windows)
        return self.state()


class _Impl(NamedTuple):
    """One engine's online hooks: the resolved plan, the initial state,
    ``patch(batch, prev_state) -> (next_state, was_incremental)``, plus the
    crash-safety hooks — ``snapshot() -> {name: np.ndarray}`` (the host-side
    structure leaves a checkpoint persists; a factory given ``snap=...``
    reconstructs the same state without re-running the argmin build) and
    ``array() -> np.ndarray`` (a host copy of the current logical array:
    published on every version for the degraded fallback + oracle checks,
    and the rebuild source for mesh-resident engines)."""

    plan: build_mod.BuildPlan
    state0: object
    patch: Callable
    snapshot: Optional[Callable] = None
    array: Optional[Callable] = None
    # () -> int: host->device bytes the last patch's publish uploaded (the
    # windowed-COW engines); None = untracked (UpdateResult reports 0).
    publish_bytes: Optional[Callable] = None


# --- single-host implementations --------------------------------------------
#
# The single-host engines restore *instantly*: their host mirrors ARE the
# built structures, so a checkpoint persists the mirror leaves and a restore
# re-seats them without recomputing a single argmin. The mesh engines (below)
# snapshot only the logical array and restore by re-running their BuildPlan —
# bit-identical by the patched==rebuilt invariant this subsystem asserts.


def _sparse_table_impl(x, mesh, axis_names, kw, snap=None) -> _Impl:
    plan = build_mod.plan_for("sparse_table", x.shape[0])
    pub = {"bytes": 0}
    if snap is None:
        state0 = build_mod.execute(plan, x)
        mirror = STMirror.from_state(state0[0])
        idx_leaf = _CowLeaf(state0[0].idx, pub)
        x_leaf = _CowLeaf(state0[1], pub)
    else:
        mirror = STMirror(snap["st_idx"], snap["x"])
        idx_leaf = _CowLeaf(_put(mirror.idx, x), pub)
        x_leaf = _CowLeaf(_put(mirror.x, x), pub)
        state0 = (SparseTable(idx=idx_leaf.dev, x=x_leaf.dev), x_leaf.dev)

    def patch(batch: DeltaBatch, prev):
        pub["bytes"] = 0
        mirror.patch(batch)
        if mirror.last_idx_windows is None:  # grew: leaf shapes changed
            xj = x_leaf.full(mirror.x)
            ij = idx_leaf.full(mirror.idx)
        else:
            xj = x_leaf.splice(
                mirror.x, [(None, a, b) for a, b in mirror.last_x_windows]
            )
            ij = idx_leaf.splice(mirror.idx, mirror.last_idx_windows)
        return (SparseTable(idx=ij, x=xj), xj), True

    return _Impl(
        plan,
        state0,
        patch,
        snapshot=lambda: {"x": mirror.x.copy(), "st_idx": mirror.idx.copy()},
        array=lambda: mirror.x.copy(),
        publish_bytes=lambda: pub["bytes"],
    )


def _block_impl(block_size: int):
    def factory(x, mesh, axis_names, kw, snap=None) -> _Impl:
        bs = kw.get("block_size", block_size)
        plan = build_mod.plan_for("block", x.shape[0], block_size=bs)
        pub = {"bytes": 0}
        if snap is None:
            state0 = build_mod.execute(plan, x)
            mirror = BlockMirror.from_state(state0, x.shape[0])
            leaves = _BlockLeaves(mirror, pub, state=state0)
        else:
            mirror = BlockMirror(
                snap["x_blocks"],
                snap["bmin_val"],
                snap["bmin_gidx"],
                snap["st_idx"],
                snap["x"].shape[0],
            )
            leaves = _BlockLeaves(mirror, pub, like=x)
            state0 = leaves.state()

        def patch(batch: DeltaBatch, prev):
            pub["bytes"] = 0
            mirror.patch(batch)
            return leaves.publish(mirror), True

        return _Impl(
            plan,
            state0,
            patch,
            snapshot=lambda: {
                "x": mirror.x_blocks.reshape(-1)[: mirror.n].copy(),
                "x_blocks": mirror.x_blocks.copy(),
                "bmin_val": mirror.bmin_val.copy(),
                "bmin_gidx": mirror.bmin_gidx.copy(),
                "st_idx": mirror.st_idx.copy(),
            },
            array=lambda: mirror.x_blocks.reshape(-1)[: mirror.n].copy(),
            publish_bytes=lambda: pub["bytes"],
        )

    return factory


def _hybrid_impl(x, mesh, axis_names, kw, snap=None) -> _Impl:
    if build_mod._norm_packed(kw.get("packed")) is not None:
        return _packed_hybrid_impl(x, mesh, axis_names, kw, snap=snap)
    # The online hybrid pins the pure-jnp short path: the Pallas megakernel's
    # packed buffers are not patched in place yet (kernel-side COW is a
    # ROADMAP follow-up), and the CPU baseline never uses them anyway.
    plan = build_mod.plan_for(
        "hybrid",
        x.shape[0],
        block_size=kw.get("block_size", 128),
        threshold=kw.get("threshold"),
        use_kernels=False,
    )

    pub = {"bytes": 0}

    def _assemble(blocked: BlockRMQ, table: SparseTable, xj, threshold) -> HybridRMQ:
        return HybridRMQ(
            blocked=blocked,
            st=table,
            x=xj,
            threshold=threshold,
            use_kernels=False,
            short_fn=functools.partial(block_query, blocked),
            long_fn=functools.partial(long_query, table, xj),
        )

    if snap is None:
        state0 = build_mod.execute(plan, x)
        blocked_m = BlockMirror.from_state(state0.blocked, x.shape[0])
        st_m = STMirror.from_state(state0.st)
        leaves = _BlockLeaves(blocked_m, pub, state=state0.blocked)
        ti_leaf = _CowLeaf(state0.st.idx, pub)
        x_leaf = _CowLeaf(state0.st.x, pub)
    else:
        blocked_m = BlockMirror(
            snap["b_x_blocks"],
            snap["b_bmin_val"],
            snap["b_bmin_gidx"],
            snap["b_st_idx"],
            snap["x"].shape[0],
        )
        st_m = STMirror(snap["st_idx"], snap["x"])
        leaves = _BlockLeaves(blocked_m, pub, like=x)
        ti_leaf = _CowLeaf(_put(st_m.idx, x), pub)
        x_leaf = _CowLeaf(_put(st_m.x, x), pub)
        # The snapshot was taken under the plan's resolved threshold (the
        # restore kwargs pin it), so routing is identical to the live engine.
        state0 = _assemble(
            leaves.state(),
            SparseTable(idx=ti_leaf.dev, x=x_leaf.dev),
            x_leaf.dev,
            plan.meta["threshold"],
        )

    def patch(batch: DeltaBatch, prev: HybridRMQ):
        pub["bytes"] = 0
        blocked_m.patch(batch)
        st_m.patch(batch)
        blocked = leaves.publish(blocked_m)
        if st_m.last_idx_windows is None:  # grew: full-array leaves changed shape
            xj = x_leaf.full(st_m.x)
            ti = ti_leaf.full(st_m.idx)
        else:
            xj = x_leaf.splice(
                st_m.x, [(None, a, b) for a, b in st_m.last_x_windows]
            )
            ti = ti_leaf.splice(st_m.idx, st_m.last_idx_windows)
        return _assemble(blocked, SparseTable(idx=ti, x=xj), xj, prev.threshold), True

    return _Impl(
        plan,
        state0,
        patch,
        snapshot=lambda: {
            "x": st_m.x.copy(),
            "st_idx": st_m.idx.copy(),
            "b_x_blocks": blocked_m.x_blocks.copy(),
            "b_bmin_val": blocked_m.bmin_val.copy(),
            "b_bmin_gidx": blocked_m.bmin_gidx.copy(),
            "b_st_idx": blocked_m.st_idx.copy(),
        },
        array=lambda: st_m.x.copy(),
        publish_bytes=lambda: pub["bytes"],
    )


# --- packed single-host hybrid -----------------------------------------------


def _spec_blob(spec) -> np.ndarray:
    """The ``PackSpec`` as a uint8 JSON blob (checkpoints persist arrays only).

    The concrete spec must survive a checkpoint: an overflow-triggered
    rebuild re-biases the key range, after which ``spec_for`` over the
    restored array would derive a *different* (equally valid) spec — and a
    restore must be bit-identical to the live engine, not merely conformant.
    """
    return np.frombuffer(json.dumps(spec.to_meta()).encode(), np.uint8)


def _spec_from_blob(blob: np.ndarray):
    spec = packing.PackSpec.from_meta(json.loads(np.asarray(blob, np.uint8).tobytes()))
    if spec.layout == "packed64":
        packing.ensure_x64()  # spec_for normally flips this; restores skip it
    return spec


def _packed_hybrid_impl(x, mesh, axis_names, kw, snap=None) -> _Impl:
    """Online packed hybrid: packed mirrors + windowed word-plane publish.

    The packed mirrors (``update.patch``) delegate the exact windowed repair
    to the raw mirrors and repack words over only the recomputed windows, so
    a publish uploads the same O(windows) volume as the unpacked engine —
    but each window is one fused word plane instead of parallel idx/val
    leaves. A batch the build-time spec cannot encode (a packed32 value
    outside the key range, appends past the index field) raises
    ``OverflowError`` BEFORE any mirror mutates and falls back to a
    structural rebuild under a fresh spec; packed64 always fits, so its
    appends stay incremental.
    """
    layout_req = build_mod._norm_packed(kw.get("packed", "auto")) or "auto"
    plan = build_mod.plan_for(
        "hybrid",
        x.shape[0],
        block_size=kw.get("block_size", 128),
        threshold=kw.get("threshold"),
        use_kernels=False,
        packed=layout_req,
    )
    bs = plan.meta["block_size"]
    pub = {"bytes": 0}

    def _assemble(blocked, table, xj, threshold, spec) -> HybridRMQ:
        # query_packed jits internally with the spec static, so binding a
        # fresh same-shape structure on publish is a jit-cache hit.
        return HybridRMQ(
            blocked=blocked,
            st=table,
            x=xj,
            threshold=threshold,
            use_kernels=False,
            short_fn=lambda l, r: block_rmq.query_packed(blocked, spec, l, r),
            long_fn=lambda l, r: sparse_table.query_packed(table, spec, l, r),
        )

    def _seed(state, spec, x_host):
        """Mirrors + COW leaves over a freshly built packed state."""
        blocked_m = PackedBlockMirror.from_state(state.blocked, spec, x_host.shape[0])
        st_m = PackedSTMirror.from_state(state.st, x_host, spec)
        leaves = {
            "blocks": _CowLeaf(state.blocked.blocks, pub),
            "stw": _CowLeaf(state.blocked.stw, pub),
            "words": _CowLeaf(state.st.words, pub),
            "x": _CowLeaf(state.x, pub),
        }
        return blocked_m, st_m, leaves

    if snap is None:
        state0 = build_mod.execute(plan, x)
        # Deterministic from the data — identical to the spec the plan's
        # local stage derived (and discarded with the build state dict).
        spec = packing.spec_for(x, x.shape[0], plan.meta["packed"])
        blocked_m, st_m, leaves = _seed(state0, spec, np.asarray(x))
    else:
        spec = _spec_from_blob(snap["spec"])
        blocked_m = PackedBlockMirror(
            snap["b_blocks"], snap["b_stw"], spec, snap["x"].shape[0]
        )
        st_m = PackedSTMirror(snap["st_words"], snap["x"], spec)
        leaves = {
            "blocks": _CowLeaf(_put(snap["b_blocks"], x), pub),
            "stw": _CowLeaf(_put(snap["b_stw"], x), pub),
            "words": _CowLeaf(_put(snap["st_words"], x), pub),
            "x": _CowLeaf(_put(snap["x"], x), pub),
        }
        state0 = _assemble(
            block_rmq.PackedBlockRMQ(
                blocks=leaves["blocks"].dev, stw=leaves["stw"].dev
            ),
            sparse_table.PackedSparseTable(
                words=leaves["words"].dev,
                x=leaves["x"].dev if spec.layout == "quantized" else None,
            ),
            leaves["x"].dev,
            plan.meta["threshold"],
            spec,
        )

    def patch(batch: DeltaBatch, prev: HybridRMQ):
        nonlocal spec, blocked_m, st_m, leaves
        pub["bytes"] = 0
        vals = np.concatenate([batch.val, batch.tail.astype(batch.val.dtype)])
        try:
            packed_fit_check(spec, vals, batch.n_new)
        except OverflowError:
            # The build-time spec cannot encode this batch: structural
            # rebuild under a fresh spec (threshold pinned, deterministic).
            xj = jnp.asarray(batch.apply_numpy(st_m.x))
            p2 = build_mod.plan_for(
                "hybrid",
                batch.n_new,
                block_size=bs,
                threshold=int(prev.threshold),
                use_kernels=False,
                packed=layout_req,
            )
            state = build_mod.execute(p2, xj)
            spec = packing.spec_for(xj, batch.n_new, p2.meta["packed"])
            blocked_m, st_m, leaves = _seed(state, spec, np.asarray(xj))
            return state, False
        blocked_m.patch(batch)
        st_m.patch(batch)
        b_host = (
            blocked_m.block_words
            if blocked_m.block_words is not None  # quantized keeps raw blocks
            else blocked_m.inner.x_blocks
        )
        if blocked_m.last_block_runs is None:  # block count grew
            bw = leaves["blocks"].full(b_host)
            sw = leaves["stw"].full(blocked_m.stw_words)
        else:
            bw = leaves["blocks"].splice_rows(b_host, blocked_m.last_block_runs)
            sw = leaves["stw"].splice(blocked_m.stw_words, blocked_m.last_st_windows)
        if st_m.last_word_windows is None:  # grew: full-plane shapes changed
            wj = leaves["words"].full(st_m.words)
            xj = leaves["x"].full(st_m.x)
        else:
            wj = leaves["words"].splice(st_m.words, st_m.last_word_windows)
            xj = leaves["x"].splice(
                st_m.x, [(None, a, b) for a, b in st_m.last_x_windows]
            )
        blocked = block_rmq.PackedBlockRMQ(blocks=bw, stw=sw)
        table = sparse_table.PackedSparseTable(
            words=wj, x=xj if spec.layout == "quantized" else None
        )
        return _assemble(blocked, table, xj, prev.threshold, spec), True

    def snapshot():
        b_host = (
            blocked_m.block_words
            if blocked_m.block_words is not None
            else blocked_m.inner.x_blocks
        )
        return {
            "x": st_m.x.copy(),
            "st_words": st_m.words.copy(),
            "b_blocks": b_host.copy(),
            "b_stw": blocked_m.stw_words.copy(),
            "spec": _spec_blob(spec),
        }

    return _Impl(
        plan,
        state0,
        patch,
        snapshot=snapshot,
        array=lambda: st_m.x.copy(),
        publish_bytes=lambda: pub["bytes"],
    )


# --- mesh implementations ----------------------------------------------------


def _distributed_impl(x, mesh, axis_names, kw, snap=None) -> _Impl:
    # Mesh-resident structures: the snapshot is the logical array only, and a
    # restore re-executes the BuildPlan over it (bit-identical to the live
    # patched state by the patched==rebuilt invariant). ``snap`` therefore
    # needs no special casing here — ``from_snapshot`` hands the saved array
    # in as ``x`` and the normal build path is the restore path.
    plan = build_mod.plan_for(
        "distributed",
        x.shape[0],
        mesh=mesh,
        axis_names=axis_names,
        block_size=kw.get("block_size", 128),
    )
    state0 = build_mod.execute(plan, x)
    mesh, axes = plan.meta["mesh"], plan.meta["axis_names"]
    bs = plan.meta["block_size"]
    x_host = np.asarray(x)  # full-array mirror: the rebuild-fallback source

    def patch(batch: DeltaBatch, prev):
        nonlocal x_host
        x_host = batch.apply_numpy(x_host)
        s, qfn = prev
        capacity = s.x_blocks.shape[0] * s.x_blocks.shape[1]
        if batch.n_new > capacity:  # grew past the padded shard capacity
            p2 = build_mod.plan_for(
                "distributed", batch.n_new, mesh=mesh, axis_names=axes, block_size=bs
            )
            return build_mod.execute(p2, jnp.asarray(x_host)), False
        pos = batch.touched()
        val = np.concatenate([batch.val, batch.tail.astype(batch.val.dtype)])
        return (distributed.patch_sharded(s, pos, val, mesh, axes), qfn), True

    return _Impl(
        plan,
        state0,
        patch,
        snapshot=lambda: {"x": x_host.copy()},
        array=lambda: x_host.copy(),
    )


def _sharded_hybrid_impl(x, mesh, axis_names, kw, snap=None) -> _Impl:
    if build_mod._norm_packed(kw.get("packed")) is not None:
        return _packed_sharded_hybrid_impl(x, mesh, axis_names, kw, snap=snap)
    # Like ``_distributed_impl``: snapshot = the logical array, restore =
    # re-run the BuildPlan (with the threshold pinned via the restore
    # kwargs), bit-identical by the patched==rebuilt invariant.
    plan = build_mod.plan_for(
        "sharded_hybrid",
        x.shape[0],
        mesh=mesh,
        axis_names=axis_names,
        block_size=kw.get("block_size", 128),
        threshold=kw.get("threshold"),
        mode=kw.get("mode", "shard_structure"),
    )
    state0 = build_mod.execute(plan, x)
    mesh = plan.meta["mesh"]
    struct_axes = plan.meta["struct_axes"]
    mode, bs = plan.meta["mode"], plan.meta["block_size"]
    x_host = np.asarray(x)
    snapshot = lambda: {"x": x_host.copy()}
    array = lambda: x_host.copy()

    if not struct_axes:  # shard_batch: replicated structures, host mirrors
        blocked_m = BlockMirror.from_state(state0.blocked, x.shape[0])
        st_m = STMirror.from_state(state0.st)
        repl = NamedSharding(mesh, P())

        def patch(batch: DeltaBatch, prev):
            nonlocal x_host
            x_host = batch.apply_numpy(x_host)
            blocked_m.patch(batch)
            st_m.patch(batch)
            table = SparseTable(idx=jnp.asarray(st_m.idx), x=jnp.asarray(st_m.x))
            return (
                prev._replace(
                    blocked=jax.device_put(_block_state(blocked_m), repl),
                    st=jax.device_put(table, repl),
                    n=batch.n_new,
                ),
                True,
            )

        return _Impl(plan, state0, patch, snapshot=snapshot, array=array)

    def patch(batch: DeltaBatch, prev):
        nonlocal x_host
        x_host = batch.apply_numpy(x_host)
        cap_blocked = prev.blocked.x_blocks.shape[0] * prev.blocked.x_blocks.shape[1]
        cap_st = prev.st.idx.shape[1]
        if batch.n_new > min(cap_blocked, cap_st):
            # Structural rebuild (capacity exceeded); the routing threshold
            # stays pinned so the rebuild is as deterministic as the patch.
            p2 = build_mod.plan_for(
                "sharded_hybrid",
                batch.n_new,
                mesh=mesh,
                axis_names=plan.meta["axis_names"],
                block_size=bs,
                threshold=int(prev.threshold),
                mode=mode,
            )
            return build_mod.execute(p2, jnp.asarray(x_host)), False
        pos = batch.touched()
        val = np.concatenate([batch.val, batch.tail.astype(batch.val.dtype)])
        return (
            prev._replace(
                blocked=distributed.patch_sharded(
                    prev.blocked, pos, val, mesh, struct_axes
                ),
                st=distributed.patch_sharded_st(prev.st, pos, val, mesh, struct_axes),
                n=batch.n_new,
            ),
            True,
        )

    return _Impl(plan, state0, patch, snapshot=snapshot, array=array)


def _packed_sharded_hybrid_impl(x, mesh, axis_names, kw, snap=None) -> _Impl:
    """Online packed sharded hybrid: single-plane SPMD patches.

    Structure-sharded modes patch through ``distributed.patch_sharded_packed``
    / ``patch_sharded_st_packed`` — one word plane rides the halo transport
    per doubling level, half the unpacked patch's traffic. ``shard_batch``
    patches host packed mirrors and re-replicates. A batch the spec cannot
    encode (packed32 key range, appends past the index field) raises
    host-side BEFORE any device state mutates and falls back to a structural
    rebuild under a fresh spec. Snapshot = the logical array (the mesh
    convention): restore re-runs the BuildPlan, which re-derives the spec
    deterministically from the restored array.
    """
    layout_req = build_mod._norm_packed(kw.get("packed", "auto")) or "auto"
    plan = build_mod.plan_for(
        "sharded_hybrid",
        x.shape[0],
        mesh=mesh,
        axis_names=axis_names,
        block_size=kw.get("block_size", 128),
        threshold=kw.get("threshold"),
        mode=kw.get("mode", "shard_structure"),
        packed=layout_req,
    )
    state0 = build_mod.execute(plan, x)
    mesh = plan.meta["mesh"]
    struct_axes = plan.meta["struct_axes"]
    mode, bs = plan.meta["mode"], plan.meta["block_size"]
    x_host = np.asarray(x)
    spec = packing.spec_for(x, x.shape[0], plan.meta["packed"])
    snapshot = lambda: {"x": x_host.copy()}
    array = lambda: x_host.copy()

    def _rebuild(n_new, threshold):
        nonlocal spec
        xj = jnp.asarray(x_host)
        p2 = build_mod.plan_for(
            "sharded_hybrid",
            n_new,
            mesh=mesh,
            axis_names=plan.meta["axis_names"],
            block_size=bs,
            threshold=threshold,
            mode=mode,
            packed=layout_req,
        )
        state = build_mod.execute(p2, xj)
        spec = packing.spec_for(xj, n_new, p2.meta["packed"])
        return state

    if not struct_axes:  # shard_batch: replicated structures, packed mirrors
        blocked_m = PackedBlockMirror.from_state(state0.blocked, spec, x.shape[0])
        st_m = PackedSTMirror.from_state(state0.st, x_host, spec)
        repl = NamedSharding(mesh, P())

        def patch(batch: DeltaBatch, prev):
            nonlocal x_host, blocked_m, st_m
            vals = np.concatenate([batch.val, batch.tail.astype(batch.val.dtype)])
            try:
                packed_fit_check(spec, vals, batch.n_new)
            except OverflowError:
                x_host = batch.apply_numpy(x_host)
                state = _rebuild(batch.n_new, int(prev.threshold))
                blocked_m = PackedBlockMirror.from_state(
                    state.blocked, spec, batch.n_new
                )
                st_m = PackedSTMirror.from_state(state.st, x_host, spec)
                return state, False
            x_host = batch.apply_numpy(x_host)
            blocked_m.patch(batch)
            st_m.patch(batch)
            # Mesh packing is never quantized, so both word planes exist.
            blocked = block_rmq.PackedBlockRMQ(
                blocks=jnp.asarray(blocked_m.block_words),
                stw=jnp.asarray(blocked_m.stw_words),
            )
            table = sparse_table.PackedSparseTable(words=jnp.asarray(st_m.words))
            return (
                prev._replace(
                    blocked=jax.device_put(blocked, repl),
                    st=jax.device_put(table, repl),
                    n=batch.n_new,
                ),
                True,
            )

        return _Impl(plan, state0, patch, snapshot=snapshot, array=array)

    def patch(batch: DeltaBatch, prev):
        nonlocal x_host
        vals = np.concatenate([batch.val, batch.tail.astype(batch.val.dtype)])
        x_host = batch.apply_numpy(x_host)
        cap_blocked = prev.blocked.blocks.shape[0] * prev.blocked.blocks.shape[1]
        cap_st = prev.st.words.shape[1]
        if batch.n_new > min(cap_blocked, cap_st):
            return _rebuild(batch.n_new, int(prev.threshold)), False
        try:
            # Appends inside the padded capacity can still outgrow the
            # spec's index field — checked host-side before any scatter.
            packed_fit_check(spec, vals, batch.n_new)
        except OverflowError:
            return _rebuild(batch.n_new, int(prev.threshold)), False
        pos = batch.touched()
        return (
            prev._replace(
                blocked=distributed.patch_sharded_packed(
                    prev.blocked, pos, vals, mesh, struct_axes, spec
                ),
                st=distributed.patch_sharded_st_packed(
                    prev.st, pos, vals, mesh, struct_axes, spec
                ),
                n=batch.n_new,
            ),
            True,
        )

    return _Impl(plan, state0, patch, snapshot=snapshot, array=array)


_FACTORIES: Dict[str, Callable] = {
    "sparse_table": _sparse_table_impl,
    "block128": _block_impl(128),
    "block256": _block_impl(256),
    "hybrid": _hybrid_impl,
    "distributed": _distributed_impl,
    "sharded_hybrid": _sharded_hybrid_impl,
    "packed_hybrid": _packed_hybrid_impl,
    "packed_sharded_hybrid": _packed_sharded_hybrid_impl,
}


def online_names() -> Tuple[str, ...]:
    """Engines with an online patch implementation (= registry ``updatable``)."""
    return tuple(sorted(_FACTORIES))


class OnlineEngine:
    """One updatable engine under MVCC: pinned-version queries + delta apply.

    ``apply`` lowers through the ``apply_deltas`` -> ``publish`` stages of
    ``core.build.update_plan`` (observable like any BuildPlan); queries go
    through ``pin()``/``release()`` so in-flight work keeps its snapshot
    while updates publish. Thread-safe: ``apply`` is serialized, pins are
    refcounted.
    """

    def __init__(
        self,
        name: str,
        x,
        *,
        mesh=None,
        axis_names=None,
        _snapshot=None,  # checkpoint leaves: restore path (see from_snapshot)
        _first_vid: int = 0,  # version-id continuity across a restore
        **build_kw,
    ):
        spec = registry.get(name)
        if not spec.updatable:
            raise ValueError(
                f"engine {name!r} is not updatable; have {registry.updatable_names()}"
            )
        x = jnp.asarray(x)
        if x.ndim != 1:
            raise ValueError(f"need a 1-D array, got shape {x.shape}")
        self.name = name
        self.spec = spec
        impl = _FACTORIES[name](x, mesh, axis_names, build_kw, snap=_snapshot)
        self.plan = impl.plan
        self._dtype = np.dtype(x.dtype)
        # Pin the plan-resolved knobs: a snapshot restored with these kwargs
        # re-plans to the exact same layout/threshold/mode deterministically.
        self._build_kw = dict(build_kw)
        for key in ("block_size", "threshold", "mode", "packed"):
            val = self.plan.meta.get(key)
            if val is not None:
                self._build_kw[key] = int(val) if isinstance(val, (int, np.integer)) else val
        self.store = VersionStore(first_vid=_first_vid)
        self._apply_lock = threading.Lock()
        self._failed: Optional[BaseException] = None
        self._failed_seq: Optional[int] = None
        self.store.publish(impl.state0, x.shape[0], x_host=impl.array())
        # The store owns version 0 now; keeping state0 on the impl would pin
        # its arrays for the engine's whole lifetime.
        self._impl = impl._replace(state0=None)
        self._uplan = build_mod.update_plan(
            name, self.plan.layout, self._stage_apply, self._stage_publish,
            meta=self.plan.meta,
        )

    # -- versions -------------------------------------------------------------

    @property
    def n(self) -> int:
        return self.store.current.n

    @property
    def current_vid(self) -> int:
        return self.store.current_vid

    @property
    def dtype(self) -> np.dtype:
        """Value dtype (what ``DeltaLog.coalesce`` must target)."""
        return self._dtype

    @property
    def poisoned(self) -> bool:
        """True once a mid-patch failure fail-stopped the applier."""
        return self._failed is not None

    def pin(self) -> Version:
        return self.store.pin()

    def release(self, vid: int) -> None:
        self.store.release(vid)

    def query(self, state, l, r):
        """The registry conformance query against one pinned version's state."""
        return self.spec.query(state, l, r)

    # -- checkpointing --------------------------------------------------------

    def snapshot(self):
        """``(arrays, meta)`` capturing the current version durably.

        ``arrays`` holds host copies of the structure leaves (single-host
        engines) or the logical array (mesh engines — restore rebuilds
        through the BuildPlan, bit-identical by the patched==rebuilt
        invariant); ``meta`` is the JSON-serializable identity
        (engine/vid/n/dtype + plan-resolved build kwargs). Taken under the
        apply lock so a snapshot never interleaves with a half-applied
        patch; refuses on a poisoned engine (the mirrors may have diverged
        from the published chain — exactly what a snapshot must never
        persist).
        """
        with self._apply_lock:
            if self._failed is not None:
                raise EnginePoisoned(self.name, self._failed_seq, self._failed)
            arrays = dict(self._impl.snapshot())
            meta = {
                "engine": self.name,
                "vid": int(self.store.current_vid),
                "n": int(self.n),
                "dtype": str(self._dtype),
                "build_kw": dict(self._build_kw),
            }
            return arrays, meta

    @classmethod
    def from_snapshot(cls, arrays, meta, *, mesh=None, axis_names=None, device=None):
        """Reconstruct an engine from ``snapshot()`` output.

        Version ids continue from the snapshot's vid (the restored initial
        publish IS that version). Meshes are not serializable — the caller
        supplies the current process's mesh for mesh engines, or the
        ``device`` a single-device engine is restored onto.
        """
        x = np.ascontiguousarray(arrays["x"])
        x = jax.device_put(x, device) if device is not None else jnp.asarray(x)
        return cls(
            meta["engine"],
            x,
            mesh=mesh,
            axis_names=axis_names,
            _snapshot=arrays,
            _first_vid=int(meta["vid"]),
            **meta.get("build_kw", {}),
        )

    # -- mutation -------------------------------------------------------------

    def _stage_apply(self, state: dict) -> dict:
        batch: DeltaBatch = state["deltas"]
        new_state, patched = self._impl.patch(batch, self.store.current.state)
        for leaf in jax.tree_util.tree_leaves(new_state):
            if isinstance(leaf, jax.Array):
                leaf.block_until_ready()
        state["patched"] = new_state
        state["incremental"] = patched
        return state

    def _stage_publish(self, state: dict) -> dict:
        batch: DeltaBatch = state["deltas"]
        vid = self.store.publish(
            state.pop("patched"), batch.n_new, x_host=self._impl.array()
        )
        layout = self.plan.layout
        state["result"] = UpdateResult(
            version=vid,
            n=batch.n_new,
            patched=state["incremental"],
            n_writes=int(batch.idx.size),
            n_appended=int(batch.tail.size),
            seconds=0.0,
            touched_shards=(
                len(shard_batches(batch, layout.num_shards, layout.shard_len))
                if layout.num_shards > 1
                else 1
            ),
            publish_bytes=(
                int(self._impl.publish_bytes())
                if self._impl.publish_bytes is not None
                else 0
            ),
        )
        return state

    def _check_batch(self, batch: DeltaBatch) -> None:
        """Reject malformed batches BEFORE any mirror mutation: patching is
        in-place on shared host mirrors, so a mid-patch failure cannot be
        rolled back (it fail-stops the engine instead — see ``apply``)."""
        if batch.n_old != self.n:
            raise ValueError(
                f"update batch coalesced for n={batch.n_old}, engine is at "
                f"n={self.n} (coalesce against the current length)"
            )
        if batch.idx.size:
            if batch.idx.min() < 0 or batch.idx.max() >= batch.n_old:
                raise ValueError(
                    f"write positions [{batch.idx.min()}, {batch.idx.max()}] "
                    f"outside [0, {batch.n_old})"
                )
            if batch.idx.size != batch.val.size:
                raise ValueError("idx/val length mismatch")
        if batch.n_new != batch.n_old + batch.tail.size:
            raise ValueError(f"inconsistent batch lengths: {batch}")

    def apply(
        self,
        deltas,
        *,
        observer: Optional[Callable] = None,
        seq: Optional[int] = None,
    ) -> UpdateResult:
        """Apply one update batch; returns the published ``UpdateResult``.

        ``deltas`` is a ``DeltaLog`` (coalesced here against the current
        length) or an already-coalesced ``DeltaBatch`` (validated before any
        mutation). Serialized: updates publish in apply order. Queries
        against pinned versions proceed concurrently throughout. ``seq`` is
        the batch's journal sequence number when the caller journals
        (``fault.durable``) — recorded on failure so the poison error names
        the exact lost update.

        Failure semantics are **fail-stop**: malformed batches are rejected
        up front with the engine untouched, but an exception raised mid-patch
        (device OOM, a bug) may leave the host mirrors inconsistent with the
        published chain — the engine marks itself failed and every later
        ``apply`` raises ``EnginePoisoned`` (carrying the original exception
        and failing seq), rather than silently publishing a diverged version.
        Queries keep serving the already-published versions; a journal-replay
        restore yields a clean replacement engine.
        """
        with self._apply_lock:
            if self._failed is not None:
                raise EnginePoisoned(
                    self.name, self._failed_seq, self._failed
                ) from self._failed
            tr = obs_trace.get_tracer()
            if isinstance(deltas, DeltaLog):
                with tr.span("coalesce", attrs={"engine": self.name} if tr.enabled else None):
                    batch = deltas.coalesce(self.n, dtype=self._dtype)
                    if tr.enabled:
                        obs_trace.set_attr("n_writes", int(batch.idx.size))
                        obs_trace.set_attr("n_appended", int(batch.tail.size))
            else:
                batch = deltas
            self._check_batch(batch)
            t0 = time.perf_counter()
            try:
                res = build_mod.execute_update(self._uplan, batch, observer=observer)
            except BaseException as e:
                self._failed = e
                self._failed_seq = seq
                raise
            return res._replace(seconds=time.perf_counter() - t0)


def make_online(
    name: str, x, *, mesh=None, axis_names=None, **build_kw
) -> OnlineEngine:
    """Build engine ``name`` as an ``OnlineEngine`` over ``x``."""
    return OnlineEngine(name, x, mesh=mesh, axis_names=axis_names, **build_kw)

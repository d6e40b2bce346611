"""RMQ serving launcher — thin CLI over the serve subsystem + engine registry.

Two modes:

* ``--mode oneshot`` (default): the benchmark-parity driver — build once,
  dispatch pre-formed query batches synchronously, verify a sample against
  the numpy oracle.
* ``--mode async``: concurrent simulated clients submit variable-size
  requests through ``repro.serve.RMQServer`` (open-loop Poisson arrivals);
  the deadline micro-batcher coalesces them into power-of-two padded engine
  launches, scatters per-request results back, and EVERY request is
  verified bit-identical against the oracle. Prints p50/p99 latency,
  sustained throughput, and the microbatch/coalescing profile. With
  ``--mutate K`` (engines declaring ``updatable``), a mutator thread
  interleaves K update batches (point writes, range fills, appends) through
  ``submit_update`` while the clients run: the engine is built as a
  ``repro.update.OnlineEngine``, each request is answered against its
  pinned MVCC version, and verification replays the delta stream on the
  host so every request is checked against the oracle **of its version**.
  ``--adaptive-deadline`` lets the batcher move its coalescing deadline
  with load (trajectory reported in the stats line).

Engine choices and flag validation derive from the registry's capability
metadata (``core.registry.EngineSpec``) — no hard-coded engine name lists:
``--qshard`` needs an engine with a ``"shard_batch"`` mode (``--qshard 2d``
needs ``"shard_2d"``: a 2D structure x batch mesh), ``--calibrate`` needs a
``"threshold"`` build kwarg, ``--block-size`` needs a ``"block_size"`` build
kwarg. Builds lower through the staged BuildPlan pipeline
(``registry.plan_for_serving`` + ``core.build.execute``); in async mode the
plan's resolved threshold drives per-regime engine warmup.

  PYTHONPATH=src python -m repro.launch.serve --n 1048576 --batch 4096 \
      --batches 8 --dist small --engine sharded_hybrid
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.serve --mode async --engine sharded_hybrid \
      --n 65536 --dist medium --clients 4 --requests 32 --qshard 2d
"""

from __future__ import annotations

import argparse
import contextlib
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import update as update_mod
from repro.core import build as build_mod
from repro.core import ref, registry
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import factor_2d, make_mesh, set_mesh
from repro.obs import Tracer, default_registry, set_tracer, verify_request_chains
from repro.serve import RMQServer, ServeConfig, ServerOverloaded
from repro.serve.workload import DISTS, make_queries, run_poisson_clients

__all__ = ["main"]

# --qshard values -> sharded_hybrid distribution modes.
_QSHARD_MODES = {"batch": "shard_batch", "2d": "shard_2d"}


def _parser() -> argparse.ArgumentParser:
    engines = registry.serveable_names()
    ap = argparse.ArgumentParser(
        description="Serve batched RMQs through any registry engine.",
        epilog="engines: "
        + "; ".join(f"{n} — {registry.get(n).doc}" for n in engines),
    )
    ap.add_argument("--mode", choices=["oneshot", "async"], default="oneshot")
    ap.add_argument("--n", type=int, default=1 << 20)
    ap.add_argument("--dist", choices=list(DISTS), default="small")
    ap.add_argument("--engine", choices=engines, default="sharded_hybrid")
    ap.add_argument(
        "--block-size",
        type=int,
        default=None,
        help="engine block size (engines declaring a 'block_size' build kwarg; "
        "default: the engine's own)",
    )
    ap.add_argument(
        "--packed",
        nargs="?",
        const="auto",
        choices=["auto", "packed32", "packed64", "quantized"],
        default=None,
        help="serve fused (value, index) word structures (core.packing): bare "
        "--packed (= 'auto') picks the tightest layout the data fits, or name "
        "one explicitly (engines declaring a 'packed' build kwarg)",
    )
    ap.add_argument(
        "--qshard",
        nargs="?",
        const="batch",
        choices=sorted(_QSHARD_MODES),
        default=None,
        help="shard the query batch: bare --qshard (= 'batch') replicates the "
        "structure and shards queries over all devices; '--qshard 2d' shards "
        "the structure over one mesh axis and the batch over the other "
        "(engines declaring the matching mode)",
    )
    ap.add_argument(
        "--calibrate",
        action="store_true",
        help="routing threshold from the calibration cache, measuring once per "
        "configuration (engines declaring a 'threshold' build kwarg)",
    )
    ap.add_argument(
        "--tune",
        action="store_true",
        help="megakernel launch geometry (tile, fetch, block size) from the "
        "autotune cache, sweeping once per configuration (engines declaring "
        "a 'kernel_config' build kwarg; without --tune, cached winners are "
        "still loaded read-only)",
    )
    one = ap.add_argument_group("oneshot")
    one.add_argument("--batch", type=int, default=4096, help="queries per batch")
    one.add_argument("--batches", type=int, default=8, help="batches to serve")
    one.add_argument("--verify", type=int, default=64, help="oracle sample size")
    asy = ap.add_argument_group("async")
    asy.add_argument("--clients", type=int, default=4, help="concurrent simulated clients")
    asy.add_argument("--requests", type=int, default=32, help="requests per client")
    asy.add_argument("--req-batch", type=int, default=16, help="queries per request")
    asy.add_argument(
        "--rate",
        type=float,
        default=200.0,
        help="per-client offered load, Poisson requests/s (0 = no pacing)",
    )
    asy.add_argument("--deadline-ms", type=float, default=2.0, help="micro-batch deadline")
    asy.add_argument("--max-batch", type=int, default=4096, help="queries per engine launch")
    asy.add_argument("--workers", type=int, default=1, help="engine-pool threads")
    asy.add_argument("--max-pending", type=int, default=4096, help="admission-control bound")
    asy.add_argument(
        "--replicas",
        type=int,
        default=1,
        help="replica fleet size: >1 serves through serve.fleet's regime-"
        "routing front door (updatable engines; mesh engines carve one "
        "device group per replica)",
    )
    asy.add_argument(
        "--max-lag",
        type=int,
        default=1,
        help="fleet rollout barrier: max version spread between replicas",
    )
    asy.add_argument(
        "--mutate",
        type=int,
        default=0,
        metavar="K",
        help="interleave K update batches (point/range writes + appends) "
        "while serving (engines declaring 'updatable'); every request is "
        "verified against the oracle of its pinned version",
    )
    asy.add_argument(
        "--mutate-rate",
        type=float,
        default=50.0,
        help="mutator offered load, update batches/s",
    )
    asy.add_argument(
        "--adaptive-deadline",
        action="store_true",
        help="let the batcher shrink its deadline under load and grow it when idle",
    )
    asy.add_argument(
        "--restore",
        default=None,
        metavar="DIR",
        help="durability root (with --mutate): restore the engine from DIR's "
        "latest checkpoint + journal suffix if one exists, else create it "
        "there; every update is WAL-journaled before it applies",
    )
    ap.add_argument(
        "--chaos",
        type=int,
        default=None,
        metavar="SEED",
        help="run the seeded chaos soak instead of serving: crash workers, "
        "fail patches and checkpoints mid-stream, then crash-restore and "
        "verify nothing was lost (engines declaring 'updatable')",
    )
    obs = ap.add_argument_group("observability")
    obs.add_argument(
        "--trace",
        default=None,
        metavar="OUT.json",
        help="record request/update/build lifecycle spans and export a "
        "Chrome-trace JSON here (open at https://ui.perfetto.dev); async "
        "modes additionally self-verify that every served request has a "
        "complete admission->flush->launch->scatter->resolve span chain",
    )
    obs.add_argument(
        "--metrics-interval",
        type=float,
        default=None,
        metavar="S",
        help="dump the metrics registry as one JSON line every S seconds "
        "(plus a final dump at shutdown)",
    )
    return ap


def _validate(ap: argparse.ArgumentParser, args, spec: registry.EngineSpec) -> None:
    """Flag validation straight off the EngineSpec capability metadata."""
    if args.qshard is not None and _QSHARD_MODES[args.qshard] not in spec.modes:
        ap.error(
            f"--qshard {args.qshard} requires an engine with a "
            f"'{_QSHARD_MODES[args.qshard]}' mode; "
            f"{args.engine} declares modes {spec.modes or '()'}"
        )
    if args.calibrate and "threshold" not in spec.build_kwargs:
        ap.error(
            f"--calibrate requires an engine with a 'threshold' build kwarg; "
            f"{args.engine} declares {sorted(spec.build_kwargs) or '()'}"
        )
    if args.block_size is not None and "block_size" not in spec.build_kwargs:
        ap.error(
            f"--block-size requires an engine with a 'block_size' build kwarg; "
            f"{args.engine} declares {sorted(spec.build_kwargs) or '()'}"
        )
    if args.tune and "kernel_config" not in spec.build_kwargs:
        ap.error(
            f"--tune requires an engine with a 'kernel_config' build kwarg; "
            f"{args.engine} declares {sorted(spec.build_kwargs) or '()'}"
        )
    if args.packed is not None and "packed" not in spec.build_kwargs:
        ap.error(
            f"--packed requires an engine with a 'packed' build kwarg; "
            f"{args.engine} declares {sorted(spec.build_kwargs) or '()'}"
        )
    if args.packed == "quantized" and spec.needs_mesh:
        ap.error(
            "--packed quantized is single-host only (its exact fallback needs "
            f"the raw blocks resident); {args.engine} is a mesh engine"
        )
    if args.mutate:
        if args.mode != "async":
            ap.error("--mutate requires --mode async")
        if not spec.updatable:
            ap.error(
                f"--mutate requires an updatable engine; "
                f"{args.engine} is not (have {registry.updatable_names()})"
            )
    if args.replicas > 1:
        if args.mode != "async":
            ap.error("--replicas > 1 requires --mode async")
        if not spec.updatable:
            ap.error(
                f"--replicas > 1 requires an updatable engine; "
                f"{args.engine} is not (have {registry.updatable_names()})"
            )
        if args.chaos is not None:
            ap.error("--chaos runs a single-engine soak; drop --replicas")
    if args.chaos is not None and not spec.updatable:
        ap.error(
            f"--chaos requires an updatable engine; "
            f"{args.engine} is not (have {registry.updatable_names()})"
        )
    if args.restore is not None and not args.mutate and args.chaos is None:
        ap.error("--restore requires --mutate (durable online serving) or --chaos")


def _build_kwargs(args, spec: registry.EngineSpec) -> dict:
    kw = {}
    if args.block_size is not None:
        kw["block_size"] = args.block_size
    if "threshold" in spec.build_kwargs:
        kw["threshold"] = "calibrated" if args.calibrate else "cached"
    if "kernel_config" in spec.build_kwargs:
        kw["kernel_config"] = "tuned" if args.tune else "cached"
    if args.packed is not None:
        kw["packed"] = args.packed
    if args.qshard is not None:
        kw["mode"] = _QSHARD_MODES[args.qshard]
    return kw


def _serve_mesh(args, spec: registry.EngineSpec):
    """(mesh, axis_names) for the engine — 2D (structure x batch) on demand.

    ``--qshard 2d`` factors the device count into the squarest (struct,
    qbatch) grid; everything else gets the default all-devices 1-D mesh.
    """
    if not spec.needs_mesh:
        return None, None
    ndev = len(jax.devices())
    if args.qshard == "2d" and ndev > 1:
        return make_mesh(factor_2d(ndev), ("struct", "qbatch")), ("struct", "qbatch")
    return registry.default_mesh()


def _block_on_state(state) -> None:
    for leaf in jax.tree_util.tree_leaves(state):
        if isinstance(leaf, jax.Array):
            leaf.block_until_ready()


def _run_oneshot(args, spec, state, x, rng) -> bool:
    total_q = 0
    last = None
    t0 = time.perf_counter()
    for _ in range(args.batches):
        l, r = make_queries(rng, args.n, args.batch, args.dist)
        idx, val = spec.query(state, l, r)
        last = (l, r, idx, val)
        total_q += args.batch
    jax.block_until_ready(last[2])
    t_serve = time.perf_counter() - t0

    l, r, idx, val = last
    k = min(args.verify, args.batch)
    gold = ref.rmq_ref(x, l[:k], r[:k])
    ok = (np.asarray(idx[:k]) == gold).all()
    mode = f" qshard={args.qshard}" if args.qshard else ""
    print(
        f"[{args.engine}{mode}] served {total_q} RMQs over n={args.n} "
        f"({args.dist} ranges) on {len(jax.devices())} device(s): "
        f"serve {t_serve*1e3:.1f} ms ({t_serve/total_q*1e9:.1f} ns/RMQ), "
        f"verify[{k}] {'OK' if ok else 'MISMATCH'}"
    )
    return bool(ok)


def _run_async(args, spec, state, x, plan, online=None):
    cfg = ServeConfig(
        deadline_s=args.deadline_ms * 1e-3,
        max_batch=args.max_batch,
        max_pending=args.max_pending,
        workers=args.workers,
        n=args.n,
        adaptive_deadline=args.adaptive_deadline,
    )
    wb = build_mod.warmup_bounds(plan)
    # The process-wide registry so WAL/restore counters from a durable engine
    # land in the same snapshot; launch spans carry the resolved plan attrs.
    okw = dict(metrics=default_registry(), trace_attrs=_span_attrs(args.engine, plan))
    if online is not None:
        srv = RMQServer(online=online, config=cfg, warmup_bounds=wb, **okw)
    else:
        qfn = lambda l, r: spec.query(state, l, r)
        srv = RMQServer(qfn, cfg, warmup_bounds=wb, **okw)
    srv.warmup()  # compile every padded launch shape (per plan regime)
    # The oracle of the version serving starts from — a restored engine
    # continues its original timeline, so this need not be 0.
    base_vid = online.current_vid if online is not None else 0

    upd_futs = []

    def mutator():
        # Open-loop Poisson mutator: point writes every batch, a range fill
        # every 3rd, an append every 4th; overload rejections are dropped.
        mrng = np.random.default_rng(77)
        for i in range(args.mutate):
            if args.mutate_rate > 0:
                time.sleep(mrng.exponential(1.0 / args.mutate_rate))
            cur_n = online.n
            log = update_mod.DeltaLog()
            for _ in range(3):
                log.point(int(mrng.integers(0, cur_n)), float(mrng.random()))
            if i % 3 == 1 and cur_n > 2:
                a = int(mrng.integers(0, cur_n - 1))
                log.fill(a, min(a + 63, cur_n - 1), float(mrng.random()))
            if i % 4 == 3:
                log.append(mrng.random(32, dtype=np.float32))
            try:
                upd_futs.append((log, srv.submit_update(log)))
            except ServerOverloaded:
                pass

    with _metrics_dump(args.metrics_interval, srv.metrics.snapshot), srv:
        t0 = time.perf_counter()
        mut = None
        if online is not None and args.mutate:
            mut = threading.Thread(target=mutator, name="mutator")
            mut.start()
        per_client = run_poisson_clients(
            args.clients,
            args.requests,
            args.rate,
            lambda rng, c: make_queries(rng, args.n, args.req_batch, args.dist),
            srv.submit,
            seed=10_000,
        )
        if mut is not None:
            mut.join()
        done = []
        dropped = 0
        for out in per_client:
            for (l, r), fut in out:
                if fut is None:
                    dropped += 1
                else:
                    done.append((l, r, fut.result(timeout=300)))
        wall = time.perf_counter() - t0  # serving only: verification is below
    st = srv.stats()

    # Replay the delta stream on the host: one oracle array per published
    # version (submission order == publish order: single updater thread).
    oracles = {base_vid: np.asarray(x)}
    patched = rebuilt = 0
    if upd_futs:
        xm = np.asarray(x).copy()
        for log, fut in upd_futs:
            res = fut.result(timeout=300)
            xm = log.coalesce(xm.shape[0], xm.dtype).apply_numpy(xm)
            oracles[res.version] = xm.copy()
            patched += res.patched
            rebuilt += not res.patched

    served = len(done)
    mismatches = 0
    for l, r, res in done:
        ox = oracles[res.version if res.version is not None else base_vid]
        gold = ref.rmq_ref(ox, l, r)
        if not (np.array_equal(res.idx, gold) and np.array_equal(res.val, ox[gold])):
            mismatches += 1

    mode = f" qshard={args.qshard}" if args.qshard else ""
    print(
        f"[async {args.engine}{mode}] {args.clients} clients x {args.requests} reqs "
        f"x {args.req_batch} RMQs ({args.dist} ranges, {args.rate:g} req/s/client, "
        f"deadline {args.deadline_ms:g} ms) on {len(jax.devices())} device(s), "
        f"{wall*1e3:.0f} ms wall"
    )
    print(f"  {st.summary()}")
    if upd_futs:
        print(
            f"  mutate: {len(upd_futs)} update batches applied "
            f"({patched} patched, {rebuilt} rebuilt), n {args.n} -> {online.n}, "
            f"{len(oracles)} oracle versions"
        )
    print(
        f"  verify: {served - mismatches}/{served} requests bit-identical to the "
        f"oracle of their pinned version; dropped {dropped}"
    )
    ok = mismatches == 0 and served > 0
    if args.mutate:
        ok = ok and len(upd_futs) > 0
    return ok, [st]


def _run_fleet(args, spec, x):
    """Serve through a replica fleet (serve.fleet): regime-routed front door,
    bounded-lag rollouts, per-version oracle verification — the multi-replica
    twin of ``_run_async``."""
    from repro.serve.fleet import FleetConfig, RMQFleet

    scfg = ServeConfig(
        deadline_s=args.deadline_ms * 1e-3,
        max_batch=args.max_batch,
        max_pending=args.max_pending,
        workers=args.workers,
        adaptive_deadline=args.adaptive_deadline,
        max_retries=4,
    )
    fcfg = FleetConfig(replicas=args.replicas, max_version_lag=args.max_lag, server=scfg)
    t0 = time.perf_counter()
    fleet = RMQFleet.build(
        args.engine,
        jnp.asarray(x),
        config=fcfg,
        durable_root=args.restore,
        **_build_kwargs(args, spec),
    )
    base_vid = fleet.head_vid
    fleet.warmup()
    print(
        f"[{args.engine} x{args.replicas}] fleet build+warmup "
        f"{(time.perf_counter() - t0)*1e3:.1f} ms (threshold {fleet.threshold}, "
        f"lag bound {fcfg.max_version_lag}, "
        f"affinities {list(fcfg.resolved_affinities())})"
    )
    for rep in fleet.replicas:
        devs = sorted(
            {str(d) for leaf in jax.tree_util.tree_leaves(rep.engine.store.current.state)
             if isinstance(leaf, jax.Array) for d in leaf.devices()}
        )
        print(f"  replica {rep.i}: state on {', '.join(devs)}")

    upd_futs = []
    sess = fleet.session()

    def mutator():
        # Same open-loop Poisson mutator as the single-server path, but each
        # batch rolls out fleet-wide through the session (read-your-writes).
        mrng = np.random.default_rng(77)
        for i in range(args.mutate):
            if args.mutate_rate > 0:
                time.sleep(mrng.exponential(1.0 / args.mutate_rate))
            cur_n = fleet.head_n
            log = update_mod.DeltaLog()
            for _ in range(3):
                log.point(int(mrng.integers(0, cur_n)), float(mrng.random()))
            if i % 3 == 1 and cur_n > 2:
                a = int(mrng.integers(0, cur_n - 1))
                log.fill(a, min(a + 63, cur_n - 1), float(mrng.random()))
            if i % 4 == 3:
                log.append(mrng.random(32, dtype=np.float32))
            try:
                upd_futs.append((log, fleet.submit_update(log, session=sess)))
            except ServerOverloaded:
                pass

    with _metrics_dump(args.metrics_interval, fleet.metrics), fleet:
        t0 = time.perf_counter()
        mut = None
        if args.mutate:
            mut = threading.Thread(target=mutator, name="mutator")
            mut.start()
        per_client = run_poisson_clients(
            args.clients,
            args.requests,
            args.rate,
            lambda rng, c: make_queries(rng, args.n, args.req_batch, args.dist),
            fleet.submit,
            seed=10_000,
        )
        if mut is not None:
            mut.join()
        done = []
        dropped = 0
        for out in per_client:
            for (l, r), fut in out:
                if fut is None:
                    dropped += 1
                else:
                    done.append((l, r, fut.result(timeout=300)))
        settled = fleet.wait_settled(timeout=300)
        wall = time.perf_counter() - t0
        st = fleet.stats()
        rep_stats = [rep.server.stats() for rep in fleet.replicas]

    # Per-version host oracles, exactly as _run_async: the fleet assigns vids
    # in submission order, so the replay below matches every replica.
    oracles = {base_vid: np.asarray(x)}
    patched = rebuilt = 0
    if upd_futs:
        xm = np.asarray(x).copy()
        for log, fut in upd_futs:
            res = fut.result(timeout=300)
            xm = log.coalesce(xm.shape[0], xm.dtype).apply_numpy(xm)
            oracles[res.version] = xm.copy()
            patched += res.patched
            rebuilt += not res.patched

    served = len(done)
    mismatches = 0
    for l, r, res in done:
        ox = oracles[res.version if res.version is not None else base_vid]
        gold = ref.rmq_ref(ox, l, r)
        if not (np.array_equal(res.idx, gold) and np.array_equal(res.val, ox[gold])):
            mismatches += 1

    print(
        f"[fleet {args.engine} x{args.replicas}] {args.clients} clients x "
        f"{args.requests} reqs x {args.req_batch} RMQs ({args.dist} ranges, "
        f"{args.rate:g} req/s/client) on {len(jax.devices())} device(s), "
        f"{wall*1e3:.0f} ms wall"
    )
    print(f"  {st.summary()}")
    if upd_futs:
        print(
            f"  mutate: {len(upd_futs)} rollouts ({patched} patched, {rebuilt} "
            f"rebuilt), n {args.n} -> {fleet.head_n}, settled={settled}, "
            f"session floor v{sess.last_vid}"
        )
    print(
        f"  verify: {served - mismatches}/{served} requests bit-identical to the "
        f"oracle of their pinned version; dropped {dropped}"
    )
    ok = mismatches == 0 and served > 0 and settled
    if args.mutate:
        ok = ok and len(upd_futs) > 0
    return ok, rep_stats


def _span_attrs(engine: str, plan) -> dict:
    """Static launch-span attrs derived from the resolved BuildPlan: the
    engine, packed layout, routing threshold, and kernel config every
    exported launch span should carry (DESIGN.md §14)."""
    attrs = {"engine": engine}
    meta = getattr(plan, "meta", None) or {}
    if meta.get("threshold") is not None:
        attrs["threshold"] = int(meta["threshold"])
    if meta.get("block_size") is not None:
        attrs["block_size"] = int(meta["block_size"])
    layout = meta.get("packed")
    attrs["layout"] = str(layout) if layout is not None else "unpacked"
    kcfg = meta.get("kernel_config")
    if kcfg is not None and hasattr(kcfg, "tile"):
        attrs["kernel_tile"] = int(kcfg.tile)
        attrs["fetch"] = str(kcfg.fetch)
        attrs["kernel_block_size"] = int(kcfg.block_size)
    return attrs


@contextlib.contextmanager
def _metrics_dump(interval, snapshot_fn):
    """Periodic one-line JSON dumps of ``snapshot_fn()`` every ``interval``
    seconds (daemon thread), plus a final dump on exit. No-op when
    ``interval`` is None."""
    if interval is None:
        yield
        return
    stop = threading.Event()

    def loop():
        while not stop.wait(interval):
            try:
                print("[metrics] " + json.dumps(snapshot_fn()))
            except Exception as e:  # a dump must never kill serving
                print(f"[metrics] dump failed: {e!r}")

    t = threading.Thread(target=loop, daemon=True, name="metrics-dump")
    t.start()
    try:
        yield
    finally:
        stop.set()
        t.join(interval + 1.0)
        print("[metrics] final " + json.dumps(snapshot_fn()))


def _export_trace(path: str, tracer, *, expect_requests: bool) -> bool:
    """Export the trace + self-verify request chains; False on a gap."""
    n = tracer.export(path)
    complete, problems = verify_request_chains(tracer.spans())
    extra = f", {tracer.dropped} spans dropped by ring buffer" if tracer.dropped else ""
    print(f"[trace] {n} spans -> {path} ({complete} complete request chains{extra})")
    ok = True
    if problems:
        for p in problems[:10]:
            print(f"[trace] INCOMPLETE: {p}")
        if len(problems) > 10:
            print(f"[trace] ... and {len(problems) - 10} more")
        ok = False
    if expect_requests and complete == 0:
        print("[trace] FAIL: no complete request chains recorded")
        ok = False
    return ok


def main(argv=None) -> list:
    """Run the CLI; returns the ``ServeStats`` of every server it ran (one
    per replica in a fleet, none in oneshot and chaos modes). Exits 1 when
    any check fails."""
    ap = _parser()
    args = ap.parse_args(argv)
    spec = registry.get(args.engine)
    _validate(ap, args, spec)
    enable_compile_cache()

    tracer = None
    if args.trace is not None:
        # Install globally BEFORE the build so build/update stage spans and
        # the serving layer all land in the same ring buffer.
        tracer = Tracer(enabled=True, capacity=1 << 17)
        set_tracer(tracer)
    try:
        ok, stats = _run_modes(ap, args, spec)
    finally:
        if tracer is not None:
            set_tracer(None)
    if tracer is not None:
        served_requests = args.chaos is None and args.mode == "async"
        ok = _export_trace(args.trace, tracer, expect_requests=served_requests) and ok
    if not ok:
        raise SystemExit(1)
    return stats


def _run_modes(ap, args, spec):
    """Build and serve per ``args``; returns ``(ok, [ServeStats, ...])``."""
    rng = np.random.default_rng(0)
    x = rng.random(args.n, dtype=np.float32)

    mesh, axes = _serve_mesh(args, spec)
    if args.chaos is not None:
        # Outside the mesh context on purpose: run_soak hands the mesh to the
        # engines explicitly (like `python -m repro.fault.chaos`), so its
        # pool workers' concurrent sharded launches see no ambient mesh.
        from repro.fault import chaos as chaos_mod

        report = chaos_mod.run_soak(
            engine=args.engine,
            n=args.n,
            seed=args.chaos,
            root=args.restore,
            workers=args.workers,
            mesh=mesh,
            axis_names=axes,
            log=print,
        )
        print(report.summary())
        return bool(report.ok), []
    if args.replicas > 1:
        # Outside any global mesh context: the fleet carves its own disjoint
        # per-replica device groups (serve.fleet.RMQFleet.build).
        return _run_fleet(args, spec, x)
    ctx = set_mesh(mesh) if mesh is not None else contextlib.nullcontext()
    with ctx:
        if args.mutate:
            # Online build: the OnlineEngine plans + builds v0 and owns the
            # MVCC store; the server pins versions per launch. With
            # --restore, the engine is durable: WAL-journaled updates rooted
            # at DIR, resumed from its checkpoint + journal when one exists.
            t0 = time.perf_counter()
            if args.restore is not None:
                from repro import checkpoint as ckpt_mod
                from repro.fault import DurableEngine

                ckpt_dir = f"{args.restore}/ckpt"
                if ckpt_mod.latest_step(ckpt_dir) is not None:
                    online = DurableEngine.restore(args.restore, mesh=mesh, axis_names=axes)
                    x = np.asarray(online.store.current.x_host)
                    args.n = online.n
                    print(
                        f"[{args.engine}] restored from {args.restore}: "
                        f"version {online.current_vid}, seq {online.seq}, "
                        f"n={online.n} ({online.replayed} journal records replayed)"
                    )
                else:
                    online = DurableEngine.create(
                        args.engine,
                        jnp.asarray(x),
                        args.restore,
                        mesh=mesh,
                        axis_names=axes,
                        **_build_kwargs(args, spec),
                    )
            else:
                online = update_mod.make_online(
                    args.engine,
                    jnp.asarray(x),
                    mesh=mesh,
                    axis_names=axes,
                    **_build_kwargs(args, spec),
                )
            plan = online.plan
            _block_on_state(online.store.current.state)
            print(
                f"[{args.engine}] online build {((time.perf_counter() - t0))*1e3:.1f} ms "
                f"(n={args.n}, {plan.layout.num_shards} structure shard(s) x "
                f"{plan.layout.shard_len} cols, version {online.current_vid})"
            )
            return _run_async(args, spec, None, x, plan, online=online)

        # The staged BuildPlan resolves everything static (shard layout,
        # threshold, mode) before touching the array; async warmup reads the
        # plan's regimes instead of guessing.
        plan = registry.plan_for_serving(
            args.engine, args.n, mesh, axes, **_build_kwargs(args, spec)
        )
        t0 = time.perf_counter()
        state = build_mod.execute(plan, jnp.asarray(x))
        _block_on_state(state)
        kcfg = plan.meta.get("kernel_config")
        kmsg = (
            f", kernel tile={kcfg.tile} fetch={kcfg.fetch} bs={kcfg.block_size}"
            if kcfg is not None
            else ""
        )
        print(
            f"[{args.engine}] build {((time.perf_counter() - t0))*1e3:.1f} ms "
            f"(n={args.n}, {plan.layout.num_shards} structure shard(s) x "
            f"{plan.layout.shard_len} cols{kmsg})"
        )

        if args.mode == "oneshot":
            return _run_oneshot(args, spec, state, x, rng), []
        return _run_async(args, spec, state, x, plan)


if __name__ == "__main__":
    main()

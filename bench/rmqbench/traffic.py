"""Traffic for the benchmark: the paper's range regimes and the client loops.

``make_queries`` is a copy of the RTXRMQ (arXiv 2306.03282) §6.4 query-range
regimes as the serving stack draws them, kept here so that a change to the
program cannot move the yardstick:

* ``large``: range length uniform in [1, n];
* ``medium``: length ~ LogNormal(log n^0.6, 0.3);
* ``small``: length ~ LogNormal(log n^0.3, 0.3);
* ``mixed``: each query from one of the three, chosen uniformly.

A traffic mix is a JSON file of parameters (``bench/mixes/<name>.json``):

    loop                "closed": each client sends its next request when
                        the last is answered (batched-RMQ callers wait for
                        their answers)
    clients             concurrent clients
    queries_per_request (l, r) pairs in one request
    regime              one of REGIMES
    pool_per_client     distinct requests drawn per client from the seed;
                        a client cycles through its pool, so every seed
                        offers the same sizes and every answer is checked

``request_pool`` draws the pools: the range lengths of each request are the
same for every seed, which orders and places them. ``closed_loop``
drives a ``submit(l, r) -> Future`` for a fixed window and records every
request issued in it.
"""

from __future__ import annotations

import math
import queue
import time
from typing import Callable, List, NamedTuple, Optional

import jax
import numpy as np

REGIMES = ("large", "medium", "small", "mixed")
LOOPS = ("closed",)
_INT32_MAX = np.iinfo(np.int32).max
_MIX_KEYS = {"loop", "clients", "queries_per_request", "regime", "pool_per_client"}


def range_lengths(rng, n: int, batch: int, regime: str) -> np.ndarray:
    """``batch`` int64 range lengths over an array of ``n`` in ``regime``."""
    if not 1 <= n <= _INT32_MAX:
        raise ValueError(f"n={n} outside the int32 index range")
    if regime == "mixed":
        pick = rng.integers(0, 3, batch)
        return np.choose(pick, [range_lengths(rng, n, batch, d) for d in REGIMES[:3]])
    if regime == "large":
        return rng.integers(1, n + 1, batch)
    if regime in ("medium", "small"):
        exp = 0.6 if regime == "medium" else 0.3
        length = np.exp(rng.normal(np.log(n**exp), 0.3, batch))
        return np.clip(length, 1, n).astype(np.int64)
    raise ValueError(f"unknown regime {regime!r}; have {REGIMES}")


def place(rng, n: int, length: np.ndarray):
    """int32 (l, r) of ranges of the given lengths at uniform positions."""
    l = rng.integers(0, np.maximum(n - length + 1, 1), length.size)
    r = np.minimum(l + length - 1, n - 1)
    return l.astype(np.int32), r.astype(np.int32)


def make_queries(rng, n: int, batch: int, regime: str):
    """``batch`` int32 (l, r) pairs over an array of ``n`` in ``regime``."""
    return place(rng, n, range_lengths(rng, n, batch, regime))


def check_mix(mix: dict) -> dict:
    """Validate a traffic mix's parameters; return it."""
    if set(mix) != _MIX_KEYS:
        raise ValueError(f"traffic mix keys {sorted(mix)}; want {sorted(_MIX_KEYS)}")
    if mix["loop"] not in LOOPS or mix["regime"] not in REGIMES:
        raise ValueError(f"traffic mix loop/regime not in {LOOPS}/{REGIMES}: {mix}")
    for k in ("clients", "queries_per_request", "pool_per_client"):
        if not (isinstance(mix[k], int) and mix[k] >= 1):
            raise ValueError(f"traffic mix {k} must be a positive int: {mix[k]!r}")
    return mix


def request_pool(seed: int, n: int, mix: dict) -> List[List[tuple]]:
    """Per client, ``pool_per_client`` (l, r) requests.

    Every seed gets the same set of range lengths in each request, drawn
    from the client and request index alone; the seed shuffles them and
    places them. Which path a query takes depends on its length alone (the
    hybrid routes by length), so every seed asks for the same work: in the
    ``large`` regime the few ranges under the threshold decide whether a
    request is split over both paths, and left to the seed they made the
    rate differ from seed to seed.
    """
    pool = []
    for c in range(mix["clients"]):
        per_client = []
        for k in range(mix["pool_per_client"]):
            length = range_lengths(np.random.default_rng([c, k]), n, mix["queries_per_request"], mix["regime"])
            rng = np.random.default_rng([seed, c, k])
            per_client.append(place(rng, n, rng.permutation(length)))
        pool.append(per_client)
    return pool


class Record(NamedTuple):
    """One request issued in the window."""

    client: int
    pool_idx: int
    t_submit: float
    t_done: float  # inf: refused, failed or never answered
    queries: int
    ok: bool  # an answer came back (correctness is judged later)


class Answers:
    """Every answer, checked against the first answer for its pool entry.

    The first answer per pool entry is kept whole; the reference judges it
    after the window. A later answer is compared with that first one on
    arrival and kept only where it differs, so memory stays bounded while
    every answer is still judged.
    """

    def __init__(self):
        self.first = {}  # (client, pool_idx) -> (idx, val)
        self.differing = []  # ((client, pool_idx), idx, val)

    def add(self, key, idx, val) -> None:
        ref = self.first.setdefault(key, (idx, val))
        if ref[0] is not idx and not (np.array_equal(ref[0], idx) and np.array_equal(ref[1], val)):
            self.differing.append((key, idx, val))


def closed_loop(
    submit: Callable,
    pool: List[List[tuple]],
    seconds: float,
    answers: Optional[Answers],
    *,
    wait_s: float = 60.0,
    overloaded: type = RuntimeError,
) -> tuple:
    """Each client keeps one request in flight and sends its next when the
    answer comes, until the window closes. One thread, the caller's, drives
    every client: the thread that resolves a request only stamps the clock
    and queues the client, so the load adds no threads of its own to the
    process. Returns ``(records, t0, t1)``: every request issued in
    ``[t0, t1)``, each waited for up to ``wait_s`` past the close."""
    done: "queue.SimpleQueue" = queue.SimpleQueue()  # (client, t_done)
    records: List[Record] = []
    inflight = {}  # client -> (pool_idx, t_submit, queries, future)
    sent = [0] * len(pool)

    def send(c: int) -> None:
        t = time.perf_counter()
        if t >= t1:
            return
        pi = sent[c] % len(pool[c])
        sent[c] += 1
        l, r = pool[c][pi]
        with jax.profiler.TraceAnnotation("bench.submit"):
            try:
                fut = submit(l, r)
            except overloaded:
                records.append(Record(c, pi, t, math.inf, l.size, False))
                done.put((c, None))  # refused: the client sends again
                return
        inflight[c] = (pi, t, l.size, fut)
        fut.add_done_callback(lambda _f, c=c: done.put((c, time.perf_counter())))

    t0 = time.perf_counter()
    t1 = t0 + seconds
    for c in range(len(pool)):
        send(c)
    while inflight or (not done.empty() and time.perf_counter() < t1):
        try:
            with jax.profiler.TraceAnnotation("bench.wait"):
                c, t_done = done.get(timeout=max(t1 + wait_s - time.perf_counter(), 0.0))
        except queue.Empty:
            break  # what is still in flight never came
        if t_done is not None:
            pi, t_sub, size, fut = inflight.pop(c)
            res = None if fut.exception() is not None else fut.result()
            if res is not None and answers is not None:
                answers.add((c, pi), res.idx, res.val)
            records.append(Record(c, pi, t_sub, t_done if res is not None else math.inf, size, res is not None))
        send(c)
    for c, (pi, t_sub, size, _) in inflight.items():
        records.append(Record(c, pi, t_sub, math.inf, size, False))
    return records, t0, t1

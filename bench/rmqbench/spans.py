"""The program's spans of the window, reduced for the per-layer readers.

``ctx.spans`` holds the ``repro.obs`` spans that started in the window:
``name``, ``span_id``, ``parent_id``, ``t0``/``t1`` on the host's
``perf_counter`` clock, ``attrs``. The server records the worker's launch
cycle per coalesced launch as a ``flush`` root whose children are
``launch`` (holding the dispatch's ``prepare``, ``h2d``, ``enqueue`` and,
on a mixed batch, ``wait`` and ``merge``), ``wait``, ``d2h``, ``scatter``
and ``finish``. A program that records no ``finish`` span predates that
cycle, and the readers built on it report nothing.

The same spans, opened on one thread, are mirrored into the profiler's
host planes as ``rmq.<name>`` events, on the device trace's clock.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from .trace import merge

CYCLE = ("launch", "wait", "d2h", "scatter", "finish")  # the worker's, per flush
DISPATCH = ("prepare", "h2d", "enqueue", "merge")  # the dispatch's host work, per launch


def named(ctx, *names) -> list:
    return [s for s in ctx.spans if s.name in names]


def seconds(spans) -> float:
    return sum(s.t1 - s.t0 for s in spans)


def records_cycle(ctx) -> bool:
    """True when the program spans the worker's whole launch cycle."""
    return any(s.name == "finish" for s in ctx.spans)


def records_hook(name: str) -> bool:
    """True when the program's enabled tracer records ``name`` spans
    (``gc``, ``compile``) from a process hook."""
    from repro.obs import trace

    return name in getattr(trace, "HOOK_SPANS", ())


def children(ctx, names) -> Dict[int, Dict[str, list]]:
    """parent span id -> {name: spans} of the window's spans in ``names``."""
    out: Dict[int, Dict[str, list]] = {}
    for s in ctx.spans:
        if s.name in names and s.parent_id is not None:
            out.setdefault(s.parent_id, {}).setdefault(s.name, []).append(s)
    return out


def host_events(trace, names) -> List[Tuple[float, float]]:
    """Union of the trace's host events ``rmq.<name>`` for ``names``, in ns."""
    wanted = {f"rmq.{n}" for n in names}
    out = []
    for plane, lines in trace.raw["planes"].items():
        if not plane.startswith("/host"):
            continue
        for evs in lines.values():
            out.extend((s, s + d) for name, s, d in evs if d > 0 and name in wanted)
    return merge(out)


def minus(a: List[Tuple[float, float]], b: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The parts of sorted disjoint intervals ``a`` outside those of ``b``."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append((lo, b[k][0]))
            lo = max(lo, b[k][1])
            k += 1
        if hi > lo:
            out.append((lo, hi))
    return out


def overlap(a: List[Tuple[float, float]], b: List[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total

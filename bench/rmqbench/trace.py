"""Reduce a profiler trace of the window to device busy time, op time and gaps.

``raw_from_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into a plain document, ``{"planes": {plane: {line: [[name, start_ns,
duration_ns], ...]}}}``, which is also the form of the recorded
traces the tests keep. ``Trace`` does the arithmetic on that document:

* the devices are the planes named ``/device:TPU:<i>``; their ``XLA Ops``
  line holds one event per operation that ran, named by its HLO text (a
  Pallas kernel's names ``custom_call_target="tpu_custom_call"``), their
  ``XLA Modules`` line one per program run (``jit_fused_query(<id>)``);
* busy time is the union of a device's op intervals inside the window, and
  the idle share is one minus busy over the window, averaged over devices;
* an idle gap is labelled with the host event that overlaps it most (the
  benchmark's own ``bench.*`` annotations only when nothing else does).

The window is placed on the trace's clock by the ``bench.anchor``
annotation, which the harness emits at a host-clock time it records.
"""

from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import Callable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANCHOR = "bench.anchor"


def raw_from_xplane(path) -> dict:
    """The xplane file as a plain document."""
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    planes = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                [e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events
            )
        planes[plane.name] = lines
    return {"planes": planes}


def load(prof_dir, p_anchor: float, t0: float, t1: float) -> "Trace":
    """The trace under ``prof_dir``, windowed to host-clock ``[t0, t1)``."""
    files = sorted(Path(prof_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {prof_dir}")
    raw = {"planes": {}}
    for f in files:
        raw["planes"].update(raw_from_xplane(f)["planes"])
    return Trace.from_anchor(raw, p_anchor, t0, t1)


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class Trace:
    def __init__(self, raw: dict, w0_ns: float, w1_ns: float):
        self.raw = raw
        self.w0, self.w1 = float(w0_ns), float(w1_ns)
        self.window_s = (self.w1 - self.w0) * 1e-9
        self.devices = sorted(
            (p for p in raw["planes"] if DEVICE_PLANE.match(p)),
            key=lambda p: int(p.rsplit(":", 1)[1]),
        )

    @classmethod
    def from_anchor(cls, raw: dict, p_anchor: float, t0: float, t1: float) -> "Trace":
        anchor = None
        for lines in raw["planes"].values():
            for evs in lines.values():
                for e in evs:
                    if e[0] == ANCHOR:
                        anchor = e[1]
        if anchor is None:
            raise ValueError(f"no {ANCHOR} event in the trace")
        return cls(raw, anchor + (t0 - p_anchor) * 1e9, anchor + (t1 - p_anchor) * 1e9)

    # -- events in the window --------------------------------------------------

    def events(self, device: str, line: str = OPS_LINE):
        """(name, start, end) of ``line`` on ``device``, clipped to the window."""
        out = []
        for name, s, d in self.raw["planes"][device].get(line, ()):
            a, b = max(s, self.w0), min(s + d, self.w1)
            if b > a:
                out.append((name, a, b))
        return out

    def modules_of(self, device: str) -> Callable[[float], str]:
        """``f(t) -> name`` of the program running on ``device`` at ``t``."""
        mods = sorted((s, e, name) for name, s, e in self.events(device, MODULES_LINE))
        starts = [m[0] for m in mods]

        def at(t: float) -> str:
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and mods[i][1] >= t:
                return mods[i][2]
            return ""

        return at

    def busy(self, device: str) -> List[Tuple[float, float]]:
        return merge([(a, b) for _, a, b in self.events(device)])

    # -- reductions -------------------------------------------------------------

    def busy_s(self) -> Optional[float]:
        """Seconds some op ran, mean over devices (None without devices)."""
        if not self.devices:
            return None
        tot = [sum(b - a for a, b in self.busy(d)) * 1e-9 for d in self.devices]
        return sum(tot) / len(tot)

    def idle_share(self) -> Optional[float]:
        busy = self.busy_s()
        if busy is None or self.window_s <= 0:
            return None
        return 1.0 - busy / self.window_s

    def op_seconds(self, match: Callable[[str, str], bool]) -> Optional[float]:
        """Seconds of the ops for which ``match(module, op)`` holds,
        summed per device and averaged over devices; None without devices."""
        if not self.devices:
            return None
        tot = []
        for d in self.devices:
            mod = self.modules_of(d)
            tot.append(sum(b - a for name, a, b in self.events(d) if match(mod(a), name)) * 1e-9)
        return sum(tot) / len(tot)

    def top_ops(self, k: int = 10) -> List[list]:
        """The ``k`` (module/op, seconds) that took most device time, mean over devices."""
        acc = {}
        for d in self.devices:
            mod = self.modules_of(d)
            for name, a, b in self.events(d):
                key = f"{_short(mod(a))}/{name.split(' = ', 1)[0]}"
                acc[key] = acc.get(key, 0.0) + (b - a) * 1e-9 / len(self.devices)
        return [[n, s] for n, s in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]

    def gaps(self, device: str) -> List[Tuple[float, float]]:
        """Idle intervals of ``device`` inside the window."""
        out, t = [], self.w0
        for a, b in self.busy(device):
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if self.w1 > t:
            out.append((t, self.w1))
        return out

    def idle_gaps(self, k: int = 10) -> List[list]:
        """The ``k`` longest idle gaps of the first device, each labelled with
        the host activity in it."""
        if not self.devices:
            return []
        host = []
        for plane, lines in self.raw["planes"].items():
            if DEVICE_PLANE.match(plane) or not plane.startswith("/host"):
                continue
            for line, evs in lines.items():
                for name, s, d in evs:
                    if d > 0:
                        host.append((s, s + d, name))
        gaps = sorted(self.gaps(self.devices[0]), key=lambda g: g[0] - g[1])[:k]
        return [[_label(g, host), (g[1] - g[0]) * 1e-9] for g in gaps]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def _short(module: str) -> str:
    """``jit_fused_query(123)`` -> ``jit_fused_query``; an op's name is its
    HLO text, of which ``top_ops`` keeps the part before `` = ``."""
    return module.split("(", 1)[0] if module else "?"


def _label(gap: Tuple[float, float], host) -> str:
    best, best_bench = (0.0, ""), (0.0, "")
    for s, e, name in host:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov <= 0:
            continue
        if name.startswith("bench."):
            best_bench = max(best_bench, (ov, name))
        else:
            best = max(best, (ov, name))
    return best[1] or best_bench[1] or "no host event"

"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device-op intervals by module and op name, the union of
them (busy time), the idle gaps, and the host annotations the harness
placed (``bench.round``, ``bench.phase.<name>``, ``bench.evaluate``).

A device op is an event on the ``XLA Ops`` line of a ``/device:`` plane;
its module is its ``hlo_module`` stat, else the ``XLA Modules`` event
that contains it. Where no device plane exists (the CPU backend), events
that carry an ``hlo_module`` stat stand in, so that the reduction can be
exercised without a chip; such numbers are never device numbers.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

ANNOTATION_PREFIX = "bench."

Interval = Tuple[int, int]


def find_xplane(folder: Path) -> Path:
    found = sorted(Path(folder).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {folder}")
    return found[-1]


def merge(intervals: List[Interval]) -> List[Interval]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def overlap(merged: List[Interval], s: int, e: int) -> int:
    """Length of [s, e) covered by the disjoint sorted ``merged``."""
    i = bisect.bisect_right(merged, (s, float("inf"))) - 1
    i = max(i, 0)
    total = 0
    while i < len(merged) and merged[i][0] < e:
        a, b = merged[i]
        total += max(0, min(b, e) - max(a, s))
        i += 1
    return total


@dataclass
class Op:
    start: int
    end: int
    name: str
    module: str
    device: str


@dataclass
class Reduced:
    ops: List[Op] = field(default_factory=list)
    annotations: List[Tuple[int, int, str]] = field(default_factory=list)
    devices: List[str] = field(default_factory=list)

    # -- intervals ----------------------------------------------------------
    def busy(self, device: Optional[str] = None) -> List[Interval]:
        return merge([(o.start, o.end) for o in self.ops
                      if device is None or o.device == device])

    def window(self) -> Interval:
        rounds = [(s, e) for s, e, n in self.annotations
                  if n == "bench.round"]
        if rounds:
            return min(s for s, _ in rounds), max(e for _, e in rounds)
        spans = [(o.start, o.end) for o in self.ops]
        return min(s for s, _ in spans), max(e for _, e in spans)

    def window_seconds(self) -> float:
        s, e = self.window()
        return (e - s) * 1e-9

    def busy_seconds(self) -> float:
        """Seconds in the window in which an op ran, averaged over devices."""
        s, e = self.window()
        devs = self.devices or [None]
        return sum(overlap(self.busy(d), s, e) for d in devs) * 1e-9 / len(devs)

    def module_ns(self, prefix: str) -> int:
        """Device time of the ops of modules whose name starts with
        ``prefix`` (their union per device, summed over devices)."""
        total = 0
        for d in self.devices or [None]:
            total += sum(e - s for s, e in merge(
                [(o.start, o.end) for o in self.ops
                 if o.module.startswith(prefix)
                 and (d is None or o.device == d)]))
        return total // max(1, len(self.devices))

    def annotated(self, prefix: str) -> List[Interval]:
        return [(s, e) for s, e, n in self.annotations if n.startswith(prefix)]

    def host_only_ns(self, prefix: str) -> int:
        """Time inside the annotations named ``prefix...`` in which the
        (first) device ran nothing."""
        busy = self.busy(self.devices[0] if self.devices else None)
        return sum((e - s) - overlap(busy, s, e)
                   for s, e in merge(self.annotated(prefix)))

    # -- breakdown ----------------------------------------------------------
    def label_at(self, t: int) -> str:
        """The innermost harness annotation around host time ``t``."""
        best, width = "outside annotations", None
        for s, e, n in self.annotations:
            if s <= t < e and (width is None or e - s < width):
                best, width = n, e - s
        return best

    def idle_gaps(self) -> List[Tuple[int, int]]:
        s, e = self.window()
        gaps, cursor = [], s
        for a, b in self.busy(self.devices[0] if self.devices else None):
            if b <= s or a >= e:
                continue
            if a > cursor:
                gaps.append((cursor, a))
            cursor = max(cursor, b)
        if cursor < e:
            gaps.append((cursor, e))
        return gaps

    def breakdown(self, top: int = 10) -> dict:
        by_op: Dict[str, int] = defaultdict(int)
        for o in self.ops:
            by_op[f"{o.module}/{o.name}"] += o.end - o.start
        n = max(1, len(self.devices))
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        by_label: Dict[str, int] = defaultdict(int)
        for a, b in self.idle_gaps():
            by_label[self.label_at((a + b) // 2)] += b - a
        gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v * 1e-9 / n] for k, v in ops],
                "idle_gaps": [[k, v * 1e-9] for k, v in gaps]}


def _stats(event) -> Dict[str, object]:
    try:
        return dict(event.stats)
    except Exception:   # a stat of a type the reader cannot convert
        return {}


def load(path: Path) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    red = Reduced()
    device_planes = [p for p in pd.planes if p.name.startswith("/device:")]
    for plane in device_planes:
        modules: List[Tuple[int, int, str]] = []
        ops = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules += [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                             e.name) for e in line.events]
            elif line.name == "XLA Ops":
                ops += list(line.events)
        if not ops:
            continue
        red.devices.append(plane.name)
        modules.sort()
        starts = [m[0] for m in modules]
        for e in ops:
            s = int(e.start_ns)
            mod = _stats(e).get("hlo_module")
            if mod is None:
                i = bisect.bisect_right(starts, s) - 1
                mod = modules[i][2] if i >= 0 and modules[i][1] > s else ""
            red.ops.append(Op(s, s + int(e.duration_ns), e.name, str(mod),
                              plane.name))
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                s, d = int(e.start_ns), int(e.duration_ns)
                if e.name.startswith(ANNOTATION_PREFIX):
                    red.annotations.append((s, s + d, e.name))
                elif not device_planes:
                    mod = _stats(e).get("hlo_module")
                    if mod is not None and d > 0:
                        red.ops.append(Op(s, s + d, e.name, str(mod),
                                          "host"))
    red.annotations.sort()
    if not device_planes and red.ops:
        red.devices = ["host"]
    return red

"""The program's own ``repro.obs`` spans, for the per-layer metrics.

Two readings of the same spans:

* the span records of the traced window (``ctx.spans``, host wall clock),
  for what a span's own duration or attrs say: :func:`per_round_ms`,
  :func:`attr_per_round`;
* the profiler's copy of each span, which a ``TraceRecorder`` mirrors as
  an annotation named like the span and carrying its ``span_id``: these
  lie on the device trace's clock, so they can be put against the device
  ops. :func:`events` reads them from the xplane that the traced window
  wrote under ``.bench_out/trace/<cell>/``; :func:`untraced_ns` and
  :func:`idle_by_span` split the device's idle time by them.

A program that mirrors no span gives no events, and the readers then
return ``None``.
"""

from __future__ import annotations

import sys
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

from harness import BENCH, OUT, load_module

trace = load_module(BENCH / "trace.py")

#: spans that only group others (and ``phase:*``, ``hcds:*_stage``): time
#: inside them and outside every work span is host work no span names
GROUPS = ("round", "consensus")


@dataclass
class Event:
    start: int          # ns, on the trace's clock
    end: int
    name: str
    span_id: int


def is_work(name: str) -> bool:
    return not (name in GROUPS or name.startswith("phase:")
                or (name.startswith("hcds:") and name.endswith("_stage")))


def load(path: Path) -> List[Event]:
    """The mirrored span events of one xplane: host events that carry a
    ``span_id`` stat."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                sid = trace._stats(e).get("span_id")
                if sid is not None:
                    s = int(e.start_ns)
                    out.append(Event(s, s + int(e.duration_ns), e.name,
                                     int(sid)))
    out.sort(key=lambda e: (e.start, e.span_id))
    return out


def events(ctx) -> List[Event]:
    """The mirrored span events of the cell's traced window."""
    try:
        path = trace.find_xplane(OUT / "trace" / ctx.cell.name)
    except FileNotFoundError:
        return []
    return load(path)


def busy(ctx) -> List[Tuple[int, int]]:
    """Device-busy intervals of the (first) device, as the other device
    metrics take them."""
    red = ctx.trace
    return red.busy(red.devices[0] if red.devices else None)


def _inside(evs: Iterable[Event], s: int, e: int) -> List[Event]:
    return [v for v in evs if v.start >= s and v.end <= e]


def untraced_ns(evs: List[Event], busy_iv: List[Tuple[int, int]]) -> int:
    """Device-idle time inside the ``round`` spans that no work span
    covers, summed over rounds."""
    total = 0
    for r in (v for v in evs if v.name == "round"):
        work = trace.merge([(v.start, v.end) for v in _inside(evs, r.start,
                                                              r.end)
                            if is_work(v.name)])
        cursor = r.start
        for a, b in work + [(r.end, r.end)]:
            if a > cursor:
                total += (a - cursor) - trace.overlap(busy_iv, cursor, a)
            cursor = max(cursor, b)
    return total


def _innermost(evs: List[Event], lo: int, hi: int
               ) -> List[Tuple[int, int, Optional[str]]]:
    """[lo, hi) cut at every span boundary, each piece with the innermost
    span around it (spans nest, as the recorder's stack opened them)."""
    pieces: List[Tuple[int, int, Optional[str]]] = []
    stack: List[Event] = []
    cursor = lo

    def upto(t: int) -> None:
        nonlocal cursor
        if t > cursor:
            pieces.append((cursor, t, stack[-1].name if stack else None))
            cursor = t

    for v in evs:
        while stack and stack[-1].end <= v.start:
            upto(stack[-1].end)
            stack.pop()
        upto(v.start)
        stack.append(v)
    while stack:
        upto(stack[-1].end)
        stack.pop()
    upto(hi)
    return pieces


def idle_by_span(evs: List[Event], busy_iv: List[Tuple[int, int]]
                 ) -> Dict[str, int]:
    """Device-idle ns inside the ``round`` spans, by the innermost span
    around it, summed over rounds."""
    out: Dict[str, int] = defaultdict(int)
    for r in (v for v in evs if v.name == "round"):
        for a, b, name in _innermost(_inside(evs, r.start, r.end),
                                     r.start, r.end):
            out[name or "round"] += (b - a) - trace.overlap(busy_iv, a, b)
    return dict(out)


# -- span records ------------------------------------------------------------

def per_round_ms(ctx, name: str, union: bool = False) -> Optional[float]:
    """Wall ms per round of the spans called ``name``; with ``union``,
    time inside any of them (nested calls counted once)."""
    spans = [s for s in ctx.spans if s.name == name]
    if not spans or ctx.rounds == 0:
        return None
    if union:
        seconds = sum(b - a for a, b in trace.merge(
            [(s.wall_start, s.wall_start + s.wall_dur) for s in spans]))
    else:
        seconds = sum(s.wall_dur for s in spans)
    return seconds / ctx.rounds * 1e3


def attr_per_round(ctx, attrs: Tuple[str, ...]) -> Optional[float]:
    """The sum per round of the named attrs over every span."""
    values = [s.attrs[a] for s in ctx.spans for a in attrs if a in s.attrs]
    if not values or ctx.rounds == 0:
        return None
    return sum(values) / ctx.rounds


def main(argv=None) -> int:
    """Print the device-idle split of a cell's last traced window by
    innermost program span, in ms per round, as one JSON object:

        python3 bench/program_spans.py <cell>
    """
    import argparse
    import json
    p = argparse.ArgumentParser(description=main.__doc__.splitlines()[0])
    p.add_argument("cell")
    args = p.parse_args(argv)
    path = trace.find_xplane(OUT / "trace" / args.cell)
    red = trace.load(path)
    evs = load(path)
    rounds = sum(e.name == "round" for e in evs)
    if rounds == 0:
        raise SystemExit(f"{path}: no mirrored program spans")
    busy_iv = red.busy(red.devices[0] if red.devices else None)
    split = idle_by_span(evs, busy_iv)
    per_round = {k: v / rounds / 1e6
                 for k, v in sorted(split.items(), key=lambda kv: -kv[1])}
    print(json.dumps({"rounds": rounds,
                      "idle_ms": sum(split.values()) / rounds / 1e6,
                      "untraced_ms": untraced_ns(evs, busy_iv) / rounds / 1e6,
                      "by_innermost_span_ms": per_round}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

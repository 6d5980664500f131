"""Device time of the ops under a ``jax.named_scope``, for the per-layer
metrics of one layer of the model (``wkv6``, ``rwkv.time_mix``,
``rwkv.channel_mix``).

A device op event of the trace names its HLO instruction (on a TPU the
event's name is the instruction's text, ``%fusion.73 = ...``; on the CPU
its ``hlo_op``), and its module. The profiler keeps each module's HLO in
the ``Hlo Proto`` stat of the ``/host:metadata`` plane, and there every
instruction carries its JAX name stack as ``metadata={op_name=...}``
(e.g. ``jit(round_fn)/while/body/transpose(jvp(rwkv.time_mix))/wkv6/
...``). An op is under scope ``s`` when a component of that stack is
``s``, or wraps it in transformations (``jvp(s)``, ``transpose(jvp(s))``).
A trace without the module's HLO, or a program without the scope, gives
no time, and the readers return ``None``.
"""

from __future__ import annotations

import re
import sys
from collections import defaultdict
from functools import lru_cache
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from harness import BENCH, OUT, load_module

trace = load_module(BENCH / "trace.py")
xspace = load_module(BENCH / "xspace.py")

_WRAPPERS = re.compile(r"^(?:[\w.-]+\()+(.*?)\)+$")
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%([^\s=]+) = .*?metadata=\{op_name="([^"]*)"')


def in_scope(path: str, scope: str) -> bool:
    """Whether ``scope`` is a component of the name stack ``path``."""
    for part in path.split("/"):
        while True:
            if part == scope:
                return True
            inner = _WRAPPERS.match(part)
            if inner is None:
                break
            part = inner.group(1)
    return False


def instruction(op_name: str) -> str:
    """The HLO instruction an op event runs: ``%name = ...`` on a TPU, the
    bare name elsewhere."""
    if op_name.startswith("%"):
        return op_name[1:].split(" ", 1)[0]
    return op_name


def _hlo_module(hlo_proto: bytes):
    """The ``HloModule`` in field 1 of a serialized ``HloProto``."""
    from google.protobuf.internal.decoder import _DecodeVarint
    from jax._src.lib import xla_client
    pos = 0
    while pos < len(hlo_proto):
        tag, pos = _DecodeVarint(hlo_proto, pos)
        if tag & 7 == 2:
            size, pos = _DecodeVarint(hlo_proto, pos)
            if tag >> 3 == 1:
                return xla_client._xla.HloModule.from_serialized_hlo_module_proto(
                    hlo_proto[pos:pos + size])
            pos += size
        elif tag & 7 == 0:
            _, pos = _DecodeVarint(hlo_proto, pos)
        else:
            pos += 8 if tag & 7 == 1 else 4
    return None


@lru_cache(maxsize=2)
def op_names(path: Path) -> Dict[str, Dict[str, str]]:
    """Module → instruction → JAX name stack, from the HLO the profiler
    kept. A module is keyed as the trace names it (``jit_f(5)``) and by
    its bare name."""
    from jax._src.lib import xla_client
    options = xla_client._xla.HloPrintOptions()
    options.print_metadata = True
    table: Dict[str, Dict[str, str]] = {}
    for events in xspace.event_metadata(path).values():
        for e in events:
            proto = e.stats.get("Hlo Proto")
            if not isinstance(proto, bytes):
                continue
            module = _hlo_module(proto)
            if module is None:
                continue
            names = {}
            for line in module.to_string(options).splitlines():
                m = _INSTRUCTION.match(line)
                if m:
                    names[m.group(1)] = m.group(2)
            table[e.name] = names
            table.setdefault(e.name.split("(", 1)[0], names)
    return table


def scope_intervals(ops, table: Dict[str, Dict[str, str]], scope: str
                    ) -> Dict[str, List[Tuple[int, int]]]:
    """Per device, the [start, end) ns of the ops under ``scope``."""
    out: Dict[str, List[Tuple[int, int]]] = defaultdict(list)
    for o in ops:
        names = table.get(o.module) or table.get(o.module.split("(", 1)[0])
        stack = names.get(instruction(o.name)) if names else None
        if stack is not None and in_scope(stack, scope):
            out[o.device].append((o.start, o.end))
    return dict(out)


def scope_ns(intervals: Dict[str, List[Tuple[int, int]]]) -> int:
    """Union of the intervals on each device, averaged over devices."""
    if not intervals:
        return 0
    total = sum(sum(e - s for s, e in trace.merge(iv))
                for iv in intervals.values())
    return total // len(intervals)


def per_round_ms(ctx, scope: str) -> Optional[float]:
    """Device ms per round of the ops under ``scope`` in the cell's traced
    window; None where no op carries it."""
    try:
        path = trace.find_xplane(OUT / "trace" / ctx.cell.name)
    except FileNotFoundError:
        return None
    ns = scope_ns(scope_intervals(ctx.trace.ops, op_names(path), scope))
    if ns == 0 or ctx.rounds == 0:
        return None
    return ns / ctx.rounds / 1e6


def main(argv=None) -> int:
    """Print the device ms per traced round under each named scope of a
    cell's last traced window:

        python3 bench/op_scopes.py <cell> [scope ...]
    """
    import argparse
    import json
    p = argparse.ArgumentParser(description=main.__doc__.splitlines()[0])
    p.add_argument("cell")
    p.add_argument("scopes", nargs="*",
                   default=["wkv6", "rwkv.time_mix", "rwkv.channel_mix"])
    args = p.parse_args(argv)
    path = trace.find_xplane(OUT / "trace" / args.cell)
    red = trace.load(path)
    rounds = max(1, sum(n == "bench.round" for _, _, n in red.annotations))
    table = op_names(path)
    print(json.dumps({
        "rounds": rounds,
        "modules_with_hlo": sorted(k for k in table if "(" in k),
        "ms_per_round": {s: scope_ns(scope_intervals(red.ops, table, s))
                         / rounds / 1e6 for s in args.scopes}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

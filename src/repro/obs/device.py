"""The host's edges with the device: where it blocks on a result, and how
many bytes a copy moves.

JAX dispatches asynchronously, so a span around a dispatch times the
launch only; the device's time shows up wherever the host first reads
the result. :func:`wait` makes that point a span of its own
(``device.wait``, attr ``on=``) while a recorder is enabled, and is a
no-op otherwise: untraced, the host blocks at that read all the same.
"""

from __future__ import annotations

from typing import Any

import jax

from repro.obs.recorder import get_recorder


def wait(on: str, tree: Any) -> None:
    """Block until every array of ``tree`` is ready, inside a
    ``device.wait`` span, only while tracing."""
    rec = get_recorder()
    if rec.enabled:
        with rec.span("device.wait", on=on):
            jax.block_until_ready(tree)


def device_nbytes(tree: Any) -> int:
    """Bytes of the device arrays in ``tree``: what pulling it to the host
    copies."""
    return sum(x.nbytes for x in jax.tree.leaves(tree)
               if isinstance(x, jax.Array))

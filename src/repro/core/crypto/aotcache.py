"""On-disk AOT kernel cache for the JAX limb backend.

The limb RLC kernel costs multi-second XLA compiles per pow2 lane bucket
— paid once per *process* without this module, i.e. every benchmark run,
every CI job, every consensus driver restart. Two cache layers move that
cost to once per *install*:

* **`jax.export` blobs** — the traced + lowered StableHLO of the kernel,
  serialized per (kernel version, jax version, device backend, ladder
  steps, lane bucket) under :func:`cache_root`. Deserializing skips
  tracing and lowering entirely (~milliseconds).
* **persistent XLA compilation cache** — owned by
  :mod:`repro.compile_cache`, so the backend-compile step that
  `exported.call` still performs on first use is a disk hit instead of a
  fresh ~10 s XLA run.

Cache root resolution: ``$REPRO_CRYPTO_KERNEL_CACHE`` if set, else
``crypto-kernels`` inside :func:`repro.compile_cache.cache_dir`. Entries
are invalidated structurally by their key — a jax upgrade, device change,
or kernel rework (bump :data:`KERNEL_VERSION`) lands in a fresh
subdirectory; stale ones are just dead files, safe to delete wholesale.

CLI (used by CI to persist the cache across workflow runs)::

    python -m repro.core.crypto.aotcache --warm  --lanes 2,16
    python -m repro.core.crypto.aotcache --smoke --lanes 16 --expect-hit
"""

from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Optional

ENV_CACHE_DIR = "REPRO_CRYPTO_KERNEL_CACHE"

#: Structural version of the exported kernel — bump whenever the traced
#: computation or its calling convention changes. v2 = GLV 8-slot ladder.
KERNEL_VERSION = 2

_HITS = 0
_MISSES = 0


def cache_root() -> Path:
    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        return Path(env)
    from repro.compile_cache import cache_dir
    return cache_dir() / "crypto-kernels"


def _jax_tag() -> str:
    """Cache subdirectory isolating (jax version, device backend)."""
    import jax
    return f"jax{jax.__version__}-{jax.default_backend()}"


def kernel_path(steps: int, lanes: int) -> Path:
    return (cache_root() / _jax_tag()
            / f"rlc-v{KERNEL_VERSION}-s{steps}-l{lanes}.jaxexport")


def load_kernel(steps: int, lanes: int) -> Optional[bytes]:
    global _HITS, _MISSES
    path = kernel_path(steps, lanes)
    try:
        blob = path.read_bytes()
    except OSError:
        _MISSES += 1
        return None
    _HITS += 1
    return blob


def save_kernel(steps: int, lanes: int, blob: bytes) -> Path:
    path = kernel_path(steps, lanes)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp%d" % os.getpid())
    tmp.write_bytes(blob)
    os.replace(tmp, path)  # atomic: concurrent processes race benignly
    return path


def has_cached_kernels() -> bool:
    """Any serialized kernel for *this* jax install (version + backend)?
    The auto-calibration probe keys off this: no blobs means the jax
    candidate would pay a cold compile and is not worth probing."""
    try:
        tag_dir = cache_root() / _jax_tag()
    except Exception:  # pragma: no cover - jax import failure
        return False
    return any(tag_dir.glob(f"rlc-v{KERNEL_VERSION}-*.jaxexport"))


def stats() -> dict:
    out = {"hits": _HITS, "misses": _MISSES, "root": str(cache_root())}
    try:
        out["tag"] = _jax_tag()
    except Exception:  # pragma: no cover - jax-less install
        pass
    return out


def _main(argv: Optional[list] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.core.crypto.aotcache",
        description="Warm or smoke-test the AOT kernel cache.")
    ap.add_argument("--warm", action="store_true",
                    help="trace+export any missing lane buckets")
    ap.add_argument("--smoke", action="store_true",
                    help="assert the warm-start path works end to end")
    ap.add_argument("--lanes", default="16",
                    help="comma-separated pow2 lane buckets (default: 16)")
    ap.add_argument("--expect-hit", action="store_true",
                    help="with --smoke: fail unless every bucket came "
                         "from a serialized blob (CI cache-restore check)")
    args = ap.parse_args(argv)
    if not (args.warm or args.smoke):
        print(json.dumps(stats(), indent=2))
        return 0

    from repro.core.crypto.backends import jax as jax_backend
    lanes = [int(x) for x in args.lanes.split(",") if x]
    report = {"stats": stats(), "buckets": []}
    failures = []
    for lane_count in lanes:
        info = jax_backend.warm_bucket(lane_count)
        report["buckets"].append(info)
        if args.smoke:
            if info.get("error"):
                failures.append(f"l{lane_count}: {info['error']}")
            elif args.expect_hit and info["source"] != "aot":
                failures.append(
                    f"l{lane_count}: expected AOT cache hit, got "
                    f"{info['source']} (cold compile)")
    report["ok"] = not failures
    report["failures"] = failures
    print(json.dumps(report, indent=2))
    return 1 if failures else 0


if __name__ == "__main__":  # pragma: no cover - CLI
    raise SystemExit(_main())

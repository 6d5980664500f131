"""The one owner of JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/*.py``) call :func:`enable`
before their first compile; importing this module changes nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and no other
  directory is set here.
* unset: the cache lives in ``.jax_cache`` at the root of the checkout.
  The path is fixed (never a temp name, pid or time) because a cache
  that moves never hits; ``.gitignore`` keeps it out of commits.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> Path:
    """Where compiled programs are cached: the env var, else the checkout."""
    env = os.environ.get(ENV_VAR)
    return Path(env) if env else DEFAULT_DIR


def enable() -> Path:
    """Turn the persistent cache on at :func:`cache_dir`; returns it."""
    import jax
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        path.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path

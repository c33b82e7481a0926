"""Placement of JAX's persistent compilation cache.

The command-line entry points (`chip_smoke.py`, `python -m
repro.workloads.sweep`, the `benchmarks/` scripts) call `enable()` once
before their first compile; no library module calls it at import.

  * `JAX_COMPILATION_CACHE_DIR` set: JAX reads it at import and the cache
    goes there; nothing here overrides it.
  * unset: the cache goes to the fixed path `<checkout>/.jax_cache`
    (gitignored).  The path is fixed because it is part of what a later
    process must find again: a per-run, per-pid or tmp directory would
    never be hit.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on for this process; returns its dir."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path

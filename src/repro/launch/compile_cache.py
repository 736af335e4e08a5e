"""JAX persistent compilation cache for the entry points.

A cold run recompiles every engine — per partition, per pow2 bucket, per
tier of the escalating engine.  ``enable()`` points JAX's persistent cache
at a fixed directory so a repeated run (and every identically shaped
partition engine within a run) reads its executables back instead:

  * ``JAX_COMPILATION_CACHE_DIR`` set → JAX already reads it; nothing else
    is configured here;
  * otherwise, on an accelerator → ``<checkout>/.jax_cache`` (listed in
    ``.gitignore``); CPU runs (the test suite) compile fresh.

The path never depends on a temporary name, a process id or the time: the
directory is part of the cache's key, so a moving path would never hit.
Entry points call ``enable()`` explicitly; importing this module changes
nothing.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable() -> Optional[str]:
    """Turn the persistent compilation cache on; returns its directory
    (None on a CPU-only run without ``JAX_COMPILATION_CACHE_DIR``)."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)

"""Process set-up shared by the entry points: where run-time caches live.

``CHECKOUT`` is the source checkout this package runs from. What the
program caches at run time (JAX's compiled executables, the kernel
autotuners' picks) lives under it, in gitignored directories at fixed
paths — never under ``$HOME``, and never under a name that depends on
a temporary directory, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to
    ``<checkout>/.jax_cache``. Called at the start of ``chip_smoke.py``
    and of the serve and train launchers — never on import and never in
    tests."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

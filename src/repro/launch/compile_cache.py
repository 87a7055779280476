"""Where JAX's persistent compilation cache lives.

A step program of a full-width model takes tens of seconds to compile, and
each process that runs it compiles it again unless the compiled program is on
disk.  The cache's directory is part of what a later run must find again, so
it never moves: ``JAX_COMPILATION_CACHE_DIR`` where that is set (JAX reads it
itself), otherwise ``.jax_cache`` at the root of the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
FALLBACK_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Called by the entry points (``main()`` of the launchers, the chip smoke
    run), never at import, so a library caller's own setting stands.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(FALLBACK_DIR))
    return str(FALLBACK_DIR)

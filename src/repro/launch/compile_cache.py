"""Where JAX's persistent compilation cache lives, and how often this
process compiled.

A step program of a full-width model takes tens of seconds to compile, and
each process that runs it compiles it again unless the compiled program is on
disk.  The cache's directory is part of what a later run must find again, so
it never moves: ``JAX_COMPILATION_CACHE_DIR`` where that is set (JAX reads it
itself), otherwise ``.jax_cache`` at the root of the checkout.

``compile_counts()`` counts this process's traces to a jaxpr and backend
compiles (a load from the persistent cache included) by function name, from
JAX's own compile events; a call that finds its program already compiled
counts nothing.  A caller takes it before and after a stretch of work and
compares.
"""

from __future__ import annotations

import os
import threading
from collections import Counter
from pathlib import Path
from typing import Dict, Tuple

import jax
from jax import monitoring

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


# The compile events JAX records, and the short names they are counted by.
COMPILE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                  "/jax/core/compile/backend_compile_duration": "compile"}

_counts: Counter = Counter()
_lock = threading.Lock()


def _on_event(event: str, duration_s: float, **kwargs) -> None:
    kind = COMPILE_EVENTS.get(event)
    if kind is not None:
        with _lock:
            _counts[(kind, str(kwargs.get("fun_name", "?")))] += 1


monitoring.register_event_duration_secs_listener(_on_event)


def compile_counts() -> Dict[Tuple[str, str], int]:
    """A copy of the counts so far: ``("trace" | "compile", fun_name) -> n``,
    since this module was first imported."""
    with _lock:
        return dict(_counts)

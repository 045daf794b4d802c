"""JAX's persistent compile cache, switched on by entry-point scripts.

A chip run compiles the engine scan and the Pallas kernels once per shape;
the persistent cache lets the next process (or the next run in the same
checkout) load them instead.  Call :func:`enable_compile_cache` from a
script's ``main`` — never at import, so library users and the test suite
keep JAX's defaults.

:func:`cache_events` counts what the cache was asked and what it answered,
whether or not this module turned it on, through the one
``jax.monitoring`` listener this module registers; the engine reports the
counts of each call in ``RunResult.counters()``.
"""
from __future__ import annotations

import os
import threading
from pathlib import Path

import jax
from jax import monitoring

_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_counts = {_REQUEST_EVENT: 0, _HIT_EVENT: 0}
_lock = threading.Lock()


def _count(event: str, **_):
    if event in _counts:
        with _lock:
            _counts[event] += 1


monitoring.register_event_listener(_count)


def cache_events() -> tuple[int, int]:
    """``(requests, hits)`` of the persistent compile cache in this process
    since this module was imported: compilations that asked the cache, and
    those it answered."""
    return _counts[_REQUEST_EVENT], _counts[_HIT_EVENT]


#: Fixed cache directory inside the checkout (listed in ``.gitignore``).
#: The path is part of each entry's key, so it must not move between runs.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and no other path is set here; otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path

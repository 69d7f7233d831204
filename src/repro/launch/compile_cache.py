"""JAX's persistent compilation cache, placed from outside the program.

With ``JAX_COMPILATION_CACHE_DIR`` set, that directory is the cache and no
other is configured.  Without it the cache lives at a fixed path inside
the checkout, ``<repo>/.jax_cache`` (listed in .gitignore).  The path is
part of what a later run looks up, so it is never derived from a temp
directory, a pid or a time.
"""

from __future__ import annotations

import collections
import os
from pathlib import Path

import jax

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

# Persistent-cache lookups seen by this process, keyed "hits" / "misses".
CACHE_EVENTS: collections.Counter = collections.Counter()
_EVENT_KEYS = {"/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "misses"}
_listening = []


def _count(event: str, **_kwargs) -> None:
    key = _EVENT_KEYS.get(event)
    if key is not None:
        CACHE_EVENTS[key] += 1


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    if not _listening:
        _listening.append(_count)
        jax.monitoring.register_event_listener(_count)
    return path

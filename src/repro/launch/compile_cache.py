"""JAX persistent compilation cache for the entry points.

Called by the CLIs (``launch/train.py``, ``launch/serve.py``) and
``chip_smoke.py`` before their first compile — never at library import, so
importing ``repro`` leaves JAX's configuration alone.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is changed here. Otherwise the cache goes to ``<repo>/.jax_cache``: a fixed
path, because the path is part of the cache key and a moving directory
never hits.
"""
from __future__ import annotations

import os
from collections import Counter

REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")

# JAX records "cache_misses" when it writes a fresh entry
_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "writes"}
counts: Counter = Counter()
_listening = False


def _on_event(event: str, **_kw) -> None:
    if event in _EVENTS:
        counts[_EVENTS[event]] += 1


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory. Cache hits and
    writes from then on are counted in :data:`counts`."""
    global _listening
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return path

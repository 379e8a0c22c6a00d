"""JAX's persistent compilation cache at a fixed path, with hit counts.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
:func:`enable_compile_cache` sets no directory.  Otherwise the cache goes
to ``<root>/.jax_cache`` — a fixed path, never a temporary one, because
the path is part of what a later run has to find.  Every executable is
written however short its compile: the serving prewarm compiles many
small fold executables, each under JAX's default one-second threshold.

Call it before the first compile.  :func:`cache_stats` then counts the
persistent-cache hits and misses (writes) since.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax
from jax import monitoring

__all__ = ["enable_compile_cache", "cache_stats"]

_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_counts = {"hits": 0, "misses": 0}
_listening = False


def _on_event(event: str, **_kwargs) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        _counts[name] += 1


def enable_compile_cache(root) -> str:
    """Turn the persistent cache on; return the directory it uses."""
    global _listening
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(Path(root) / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not _listening:
        monitoring.register_event_listener(_on_event)
        _listening = True
    return str(jax.config.jax_compilation_cache_dir)


def cache_stats() -> dict:
    """Persistent-cache ``hits`` and ``misses`` counted in this process."""
    return dict(_counts)

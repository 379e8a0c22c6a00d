"""Runtime helpers: the `REPRO_DEBUG` validation gate and timed spans.

Structural ``validate()`` methods (monotone CSR pointers, nested level
ranges, sorted postings, shard partition exactness — see
:mod:`repro.core.hier_index` / :mod:`repro.core.device_engine`) cost real
time on large indexes, so production builds skip them.  They run when

* the ``REPRO_DEBUG`` environment variable is set to anything but
  ``""``/``"0"``/``"false"`` — the CI sanitize job sets ``REPRO_DEBUG=1``
  so every index/plan built during the gated test subset self-checks; or
* a test forces the flag locally with :func:`force_debug`.

Call sites gate through :func:`maybe_validate` so the fast path stays a
single dict lookup.

:func:`span` is the one timing primitive of the serving path: a profiler
trace annotation whose wall-clock duration also lands in an ``info``
dict, so a ``t_*_s`` key and its trace span are the same interval.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Optional

__all__ = ["debug_enabled", "force_debug", "maybe_validate", "span"]

_FALSY = ("", "0", "false", "False", "no")

# tri-state override: None = follow the environment variable.
_forced: list = [None]


def debug_enabled() -> bool:
    """True when structural validation should run (env or forced)."""
    if _forced[0] is not None:
        return bool(_forced[0])
    return os.environ.get("REPRO_DEBUG", "") not in _FALSY


@contextlib.contextmanager
def force_debug(value: bool = True):
    """Override the ``REPRO_DEBUG`` environment gate within a block —
    how property tests turn validation on without mutating ``os.environ``
    (subprocess tests inherit the real environment, not this)."""
    prev = _forced[0]
    _forced[0] = value
    try:
        yield
    finally:
        _forced[0] = prev


def maybe_validate(obj):
    """Run ``obj.validate()`` when debugging is enabled; always returns
    ``obj`` so builders can gate in tail position."""
    if debug_enabled():
        obj.validate()
    return obj


@contextlib.contextmanager
def span(name: str, info: Optional[dict] = None, key: Optional[str] = None, **args):
    """A ``jax.profiler.TraceAnnotation`` named ``name`` (``args`` become
    its trace arguments) that also adds its ``time.perf_counter``
    duration to ``info[key]``, and yields its start on that clock.
    Spans sharing a key sum; to time one interval across consecutive
    spans, time it from the first one's start, since the instants
    between the spans (where the interpreter may collect garbage) count
    in the trace's interval and not in a sum.  With no profiler running
    the annotation costs one check.  (jax is imported here, not at
    module level, so the lint head stays importable without it.)"""
    from jax.profiler import TraceAnnotation

    with TraceAnnotation(name, **args):
        t0 = time.perf_counter()
        yield t0
        if key is not None:
            info[key] = info.get(key, 0.0) + (time.perf_counter() - t0)

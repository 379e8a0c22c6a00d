"""The fold's stages after the first in the profiler's trace.

Per chip (averaged over the chips that ran anything): the fold modules
(``fold_modules``), the union of the device intervals of the ops under
``seclud.fold/stage<s>`` for s >= 2 (``late_ns``), and of those under
its ``seclud.fold/stage<s>/bounds`` scope, the gather of every cell's
segment bounds (``bounds_ns``).  The trace is loaded through
``bench/span_reduce.py`` once per run.  A program without the scopes
reduces to zeros, which the readers report as nothing read.
"""

import re

from bench import span_reduce as _sr
from bench.trace_reduce import _union, is_fold

STAGE = re.compile(r"seclud\.fold/stage(\d+)(/bounds(?:/|$))?")


def reduce(trace: dict) -> dict:
    devs = [d for d in trace["devices"].values() if d["ops"] or d["modules"]]
    fold_modules = late = bounds = 0.0
    for d in devs:
        fold_modules += sum(is_fold(name) for name, _s, _du in d["modules"])
        later = [(m, s, s + du) for m, s, du in
                 ((STAGE.search(scope), s, du) for _n, s, du, scope in d["ops"])
                 if m and int(m.group(1)) >= 2]
        late += sum(b - a for a, b in _union([(a, b) for _m, a, b in later]))
        bounds += sum(b - a for a, b in _union([(a, b) for m, a, b in later if m.group(2)]))
    n = max(len(devs), 1)
    return {"fold_modules": fold_modules / n, "late_ns": late / n, "bounds_ns": bounds / n}


_CACHE = {"rec": None, "out": None}


def for_record(rec):
    """``reduce(span_reduce.load(...))`` of the run ``rec`` describes,
    read once per run; None for a run without a trace."""
    if rec.trace is None:
        return None
    if _CACHE["rec"] is not rec:
        _CACHE["rec"], _CACHE["out"] = rec, reduce(_sr.load(str(_sr.TRACE_DIR)))
    return _CACHE["out"]


def per_fold_ms(rec, key: str):
    t = for_record(rec)
    if not t or not t["fold_modules"] or not t[key]:
        return None
    return t[key] / t["fold_modules"] * 1e-6

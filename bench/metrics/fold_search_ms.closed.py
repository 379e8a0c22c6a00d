"""Device time of the fold's binary searches per fold execution (ms),
from the profiler's trace: the union of the device intervals of the
operations under the program's ``seclud.fold/stage<s>`` scopes, over the
fold modules' count (``bench/span_reduce.py``)."""

from bench import span_reduce as _sr


def read(rec):
    t = _sr.for_record(rec)
    if not t or not t["fold_modules"] or not t["search_ns"]:
        return None
    return t["search_ns"] / t["fold_modules"] * 1e-6

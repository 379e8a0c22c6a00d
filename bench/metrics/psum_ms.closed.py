"""Device time of the sharded fold's cross-chip sum per fold (ms), from
the profiler's trace: the union of the device intervals of the fold's
all-reduce (``%psum.<n>``), over the fold modules' count, averaged over
the chips (``bench/span_reduce.py``).  The ``shard_map`` is synchronous,
so on a faster chip this time includes its wait for the slowest: the
imbalance as the device sees it."""

from bench import span_reduce as _sr


def read(rec):
    t = _sr.for_record(rec)
    if not t or not t["fold_modules"] or not t["psum_ns"]:
        return None
    return t["psum_ns"] / t["fold_modules"] * 1e-6

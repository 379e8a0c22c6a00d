"""Device time of the segment-bounds gathers of the fold's stages after
the first per fold execution (ms), from the profiler's trace: the union
of the device intervals of the operations under the program's
``seclud.fold/stage<s>/bounds`` scopes for s >= 2, over the fold
modules' count, averaged over the chips
(``bench/metrics/_late_stages.py``).  Each such gather covers every
padded cell, the stage's active prefix or not."""

from bench.metrics import _late_stages as _late


def read(rec):
    return _late.per_fold_ms(rec, "bounds_ns")

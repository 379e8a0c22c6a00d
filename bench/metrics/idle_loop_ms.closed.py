"""Device-idle time per fold in the window (ms) while the innermost
program span open on the host is only ``seclud.batch``,
``seclud.seal`` or ``seclud.reply``: the serving loop's own work
around the engine (``bench/span_reduce.py``)."""

from bench import span_reduce as _sr


def read(rec):
    return _sr.idle_ms(rec, "loop")

"""Device-idle time per fold in the window (ms) while the innermost
program span open on the host is ``seclud.plan``: host planning (``bench/span_reduce.py``)."""

from bench import span_reduce as _sr


def read(rec):
    return _sr.idle_ms(rec, "plan")

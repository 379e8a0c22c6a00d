"""Device-idle time per fold in the window (ms) while the innermost
program span open on the host is ``seclud.upload``,
``seclud.dispatch`` or ``seclud.readback``: the fold's upload, dispatch and
readback (``bench/span_reduce.py``)."""

from bench import span_reduce as _sr


def read(rec):
    return _sr.idle_ms(rec, "xfer")

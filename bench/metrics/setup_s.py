"""Seconds from the process's start to the window's: JAX start-up, data,
fit and index, prewarm (compiles) and the traffic's warm-up."""


def read(rec):
    return rec.setup_s

"""Mean ``t_fold_s`` per batch dispatched in the window (ms): the fold's round trip (device_put, dispatch, device_get)."""

from bench.metrics import _batch_info as _info


def read(rec):
    return _info.mean_ms(rec, "t_fold_s")

"""Mean ``t_lower_s`` per batch dispatched in the window (ms): lowering (lower_plan)."""

from bench.metrics import _batch_info as _info


def read(rec):
    return _info.mean_ms(rec, "t_lower_s")

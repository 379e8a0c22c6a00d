"""Mean ``t_plan_s`` per batch dispatched in the window (ms): host planning (plan_segment_pairs)."""

from bench.metrics import _batch_info as _info


def read(rec):
    return _info.mean_ms(rec, "t_plan_s")

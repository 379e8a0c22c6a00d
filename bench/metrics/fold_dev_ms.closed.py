"""Device time of the fused fold per execution (ms), from the profiler's
trace: the summed durations of the fold module's events over their count."""


def read(rec):
    t = rec.trace
    if not t or not t["fold_modules"]:
        return None
    return t["fold_device_s"] / t["fold_modules"] * 1e3

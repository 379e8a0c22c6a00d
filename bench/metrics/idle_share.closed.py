"""Share of the traced window (%) in which no operation ran on the device:
1 minus the union of the device's operation intervals over the window."""


def read(rec):
    t = rec.trace
    if not t or not t["window_s"] or not t["n_devices"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])

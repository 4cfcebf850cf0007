"""Share of the traced window in which no operation ran on the device:
1 - union of device op intervals / window (device)."""


def read(rec):
    if rec.trace is None:
        return None
    t = rec.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])

"""device_idle_pct.steady: share of the traced window in which no operation ran on the
device (1 - union of device op intervals / window), from the profiler
trace."""


def read(rec):
    tr = rec["trace"]
    if not tr or tr["window_s"] <= 0 or not tr["n_devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

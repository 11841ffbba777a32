"""Share of the traced window with no operation on the device (latency
cells): 1 - union of device-op intervals / window, from the trace."""


def read(rec):
    t = rec["trace"]
    if not t or t["window_s"] <= 0 or t["devices"] == 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])

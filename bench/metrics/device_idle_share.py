"""Device: share of the traced window with no operation on the device, in %."""


def read(win):
    t = win.trace
    if not t or t["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_ns"] / t["window_ns"])

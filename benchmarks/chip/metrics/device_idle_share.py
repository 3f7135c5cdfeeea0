"""Share of the traced window in which no operation runs on the device
(mean over the chips used)."""


def read(r):
    if r.traced is None or r.traced.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.traced.busy_s / r.traced.window_s)

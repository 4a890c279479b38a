"""Share of the traced window in which no operation ran, on the device
that was idle longest (a step waits for its slowest chip)."""


def read(h):
    red = h.reduced
    if red is None or red.window_s <= 0 or not red.busy_s_by_device:
        return None
    return 100.0 * (1.0 - min(red.busy_s_by_device.values()) / red.window_s)

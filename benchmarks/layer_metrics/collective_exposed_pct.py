"""Share of the traced window in which a collective operation ran and
no compute operation did, mean over the devices."""


def read(h):
    red = h.reduced
    if red is None or red.window_s <= 0 or not red.busy_by_device:
        return None
    return 100.0 * red.collective_exposed_s / red.window_s

"""Share of the traced window in which no program ran on the device,
averaged over the chips, in %."""


def read(r):
    if r.red is None or r.red.busy_s <= 0:
        return None    # no program ran on a device
    return 100.0 * (1.0 - r.red.busy_s / r.red.window_s)

"""Device: share of the traced window in which no operation ran on the
chip (1 - union of device-op intervals / window), mean over chips, %."""


def read(r):
    span = r.window_s()
    if span <= 0 or not r.devices:
        return None
    return 100.0 * (1.0 - r.busy_s() / span)

"""Device: percent of the traced window in which no operation ran on the
chip."""


def read(r):
    w = r.window_s()
    return 100.0 * (1.0 - r.busy_s() / w) if w > 0 else None

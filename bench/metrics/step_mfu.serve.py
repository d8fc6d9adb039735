"""Whole decode step: the operations the model needs for the ticks'
served tokens (dense sites and attention over each row's valid context,
``bench/costs``) over the decode programs' device time, as a percent of
the chip's int8 peak (the dense sites run int8 dots)."""


def read(r):
    pairs = r.matched("generate")
    if pairs is None:
        return None
    ops = sum(c["ops"] for _, c, _ in pairs)
    secs = sum(s for _, _, s in pairs)
    return 100.0 * ops / secs / r.peaks["int8_ops"] if secs > 0 else None

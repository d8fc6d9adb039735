"""Whole window: the operations the model needs for every token processed
in the traced window, prompt and output (``bench/costs``), over the
window's length, as a percent of the chip's int8 peak."""


def read(r):
    ops = sum(c["ops"] for c in r.counts)
    w = r.window_s()
    return 100.0 * ops / w / r.peaks["int8_ops"] if w > 0 else None

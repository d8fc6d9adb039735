"""Kernel ``flash_attend`` (cache attention, decode and prefill): the least
time of all its calls in the window -- each row's cache read only up to
its fill level -- over the trace's time of its events, in percent."""


def read(r):
    return r.roofline("flash_attend", "attn_least_s")

"""Kernel ``fused_qmm_call`` (every dense site): the least time of all its
calls in the window, from ``bench/costs`` and the chip's peaks, over the
trace's time of its events, in percent."""


def read(r):
    return r.roofline("fused_qmm_call", "qmm_least_s")

"""Output tokens per second: every token served inside the window over
the window's length."""


def read(r):
    w = r.window
    n = sum(1 for x in w.records for t in x.token_t if w.t0 < t <= w.t1)
    return n / (w.t1 - w.t0)

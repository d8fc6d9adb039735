"""Device: percent of the time the engine had work in which no operation
ran on the chip; the load generator's ``wait_arrival`` spans (an empty engine
waiting for the next arrival) are left out."""
from bench import trace as T


def read(r):
    a, b = r.span
    waits = [(s.start, s.end) for s in r.wait_spans()]
    active = (b - a) - sum(e - s for s, e in waits)
    if active <= 0:
        return None
    busy = sum(y - x for x, y in r.busy) - sum(
        T.overlap(r.busy, s, e) for s, e in waits)
    return 100.0 * (1.0 - busy / active)

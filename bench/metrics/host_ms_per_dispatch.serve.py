"""Engine (``StagedEngine.step``): mean over the load generator's ``step``
spans of the span's length less the device's busy time inside it, in ms."""
from bench import trace as T


def read(r):
    steps = [s for s in r.red.spans if s.name == "step"]
    if not steps:
        return None
    host = [s.dur - T.overlap(r.busy, s.start, s.end) for s in steps]
    return 1e-6 * sum(host) / len(host)

"""What the per-layer metric readers read: the load generator's window, the
reduced trace and the per-dispatch counts, with the arithmetic they
share.  Each reader (``bench/metrics/<name>.py``) returns a number, or
None when its trace holds nothing for it to read."""
from __future__ import annotations

import importlib
import math
from typing import List, Optional

from bench import trace as T


def nearest_rank(values: List[float], q: float) -> float:
    """The q-th percentile (0-100) by nearest rank."""
    v = sorted(values)
    return v[max(0, math.ceil(q / 100 * len(v)) - 1)]


class Readings:
    def __init__(self, window, red: Optional[T.Reduced], config: dict,
                 mix: dict, peaks: dict, setup_s: float = 0.0):
        self.window, self.red, self.setup_s = window, red, setup_s
        self.config, self.mix, self.peaks = config, mix, peaks
        # the window's own dispatches: not set-up's slot fill, not the
        # drain's (stepped after the trace stops)
        self.dispatches = [d for d in window.dispatches
                           if window.t0 <= d.t0 < window.t1]
        costs = importlib.import_module(f"bench.costs.{config['reference']}")
        self.counts = [costs.dispatch(config, peaks, d.kind, d.valid, d.start,
                                      d.size) for d in self.dispatches]
        if red is not None:
            self.busy = T.busy_intervals(red)
            self.span = T.window(red)
            self.modules = T.classify_modules(red)

    # -- device trace --------------------------------------------------
    def matched(self, kind: str):
        """(dispatch, counts, device seconds) of each dispatch of ``kind``
        paired in order with its program execution; None when the trace
        holds another number of them than the load generator dispatched."""
        secs = [m.dur * 1e-9 for m, k in self.modules if k == kind]
        ds = [(d, c) for d, c in zip(self.dispatches, self.counts)
              if d.kind == kind]
        if len(secs) != len(ds) or not ds:
            return None
        return [(d, c, s) for (d, c), s in zip(ds, secs)]

    def full_chunk_ms(self) -> Optional[float]:
        """Mean device ms of the prefill programs that consumed a full
        chunk."""
        pairs = self.matched("prefill")
        if pairs is None:
            return None
        full = self.mix["engine"]["prefill_chunk"]
        secs = [s for d, _, s in pairs if d.size == full]
        return 1e3 * sum(secs) / len(secs) if secs else None

    def kernel_seconds(self, kind: str) -> float:
        a, b = self.span
        return sum(o.dur for o in self.red.ops
                   if o.kind == kind and o.start >= a and o.end <= b) * 1e-9

    def roofline(self, kernel: str, key: str) -> Optional[float]:
        """Percent: the least seconds of every call of ``kernel`` in the
        window (``bench/costs``) over the trace's seconds of its events."""
        spent = self.kernel_seconds(kernel)
        if spent <= 0:
            return None
        return 100.0 * sum(c[key] for c in self.counts) / spent

    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy) * 1e-9

    def window_s(self) -> float:
        a, b = self.span
        return (b - a) * 1e-9

    def wait_spans(self):
        return [s for s in self.red.spans if s.name == "wait_arrival"]

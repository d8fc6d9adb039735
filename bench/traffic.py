"""The one traffic generator: a mix file of parameters -> requests.

A mix (``bench/traffic/<name>.json``) gives length distributions, the
loop (``open``: arrivals on a schedule at ``rate_per_s``; ``closed``: the
queue always holds the job's next request) and the engine shape.

Open loop: the arrivals of ``warm_s`` seconds before the window (set-up
serves them, so the window opens on an engine in its steady state) and
those due inside the window are two phases.  Each phase holds a fixed set
of prompt lengths, output lengths and inter-arrival gaps -- the midpoint
quantiles of the stated distributions -- and ``--seed`` draws their order
and the token ids: every seed serves the same work in another order.
Closed loop: ``cycles`` passes over a pool of ``pool`` requests; a mix
that names an ``order_seed`` orders every pass from it, so that seeds
differ in token ids alone.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Spec:
    uid: int
    prompt: List[int]
    max_new: int
    # offset from the window's start (negative: due before it, in set-up);
    # None: closed loop
    due_s: Optional[float]


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """n evenly spaced quantiles (midpoints) of a clipped distribution."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(x) for x in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "fixed":
        v = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return np.clip(np.round(v), dist["min"], dist["max"]).astype(np.int64)


def _gaps(rate: float, n: int, seconds: float) -> np.ndarray:
    """n Poisson inter-arrival gaps (exponential quantiles), scaled so the
    n-th arrival lands before ``seconds`` at the stated mean rate."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u) / rate
    return g * (seconds * n / (n + 1)) / g.sum()


def _phase(mix: dict, order, seconds: float, start: float):
    """(prompt lengths, output lengths, due offsets) of the arrivals due in
    [start, start + seconds)."""
    n = max(1, int(math.floor(mix["rate_per_s"] * seconds)))
    prompts = order.permutation(_quantiles(mix["prompt"], n))
    outs = order.permutation(_quantiles(mix["output"], n))
    due = start + np.cumsum(order.permutation(_gaps(mix["rate_per_s"], n,
                                                    seconds)))
    return prompts, outs, due


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> List[Spec]:
    """The requests of one run, in the order they are due."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(mix.get("order_seed", [seed, 1]))
    max_len = mix["engine"]["max_len"]
    if mix["loop"] == "open":
        warm = float(mix.get("warm_s", 0))
        phases = [_phase(mix, order, warm, -warm)] if warm > 0 else []
        phases.append(_phase(mix, order, seconds, 0.0))
        prompts, outs, due = (np.concatenate(x) for x in zip(*phases))
    else:
        pool = int(mix["pool"])
        p, o = _quantiles(mix["prompt"], pool), _quantiles(mix["output"], pool)
        prompts = np.concatenate([order.permutation(p) for _ in range(mix["cycles"])])
        outs = np.concatenate([order.permutation(o) for _ in range(mix["cycles"])])
        due = [None] * len(prompts)
    specs = []
    for i, (pl, ol, d) in enumerate(zip(prompts, outs, due)):
        pl = int(pl)
        # the engine ends a slot at max_len - 1: keep every request whole
        ol = int(min(ol, max_len - 2 - pl))
        if ol < 1:
            raise ValueError(f"mix leaves no room for output at prompt {pl}")
        toks = rng.integers(0, vocab, pl).tolist()
        specs.append(Spec(i, toks, ol, None if d is None else float(d)))
    return specs

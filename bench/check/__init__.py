"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests the window served -- drawn from the seed, the longest always
in it -- is run through the reference (``bench/references/<family>.py``)
over each prompt followed by its served tokens.  For every served token
the number compared is its gap: how far its reference logit lies below
the reference's best at that position, in units of that position's logit
standard deviation.  The run is correct when the widest gap is within the
configuration's limit (``check.worst_gap_limit``), every request due in
the window got its first token, and nothing compiled inside the window.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

import numpy as np


def sample(records, seed: int, k: int) -> List:
    """Up to ``k`` records: the finished ones first, the one with the most
    tokens always in it, the rest drawn from ``seed``; then in-flight ones
    with the most served tokens, when too few finished."""
    done = [r for r in records if r.req.status == "finished" and r.req.output]
    rng = np.random.default_rng(seed + 1)
    out = []
    if done:
        longest = max(done, key=lambda r: len(r.req.prompt) + len(r.req.output))
        rest = [r for r in done if r is not longest]
        pick = rng.permutation(len(rest))[:k - 1]
        out = [longest] + [rest[i] for i in sorted(pick)]
    if len(out) < k:
        busy = sorted((r for r in records if r.req.status != "finished"
                       and r.req.output), key=lambda r: -len(r.req.output))
        out += busy[:k - len(out)]
    return out


def reference(config: dict, seed: int, act_bits: int = 8):
    from bench import cell

    mod = importlib.import_module(f"bench.references.{config['reference']}")
    return mod.Reference({"model": cell.reference_model(config)}, seed,
                         act_bits=act_bits)


def rows_of(records):
    """Each record's sequence (prompt, then served tokens but the last) and
    the positions whose logits chose its served tokens."""
    seqs, rows, toks = [], [], []
    for r in records:
        p, o = list(r.req.prompt), list(r.req.output)
        seqs.append(p + o[:-1])
        rows.append(list(range(len(p) - 1, len(p) + len(o) - 1)))
        toks.append(o)
    return seqs, rows, np.concatenate([np.asarray(t, np.int32) for t in toks])


def padded_length(records, max_len: int, step: int = 4096) -> int:
    """Sequence length the reference pads a sample to: the longest served
    sequence rounded up to ``step`` (a handful of shapes, each compiled
    once), at most ``max_len``."""
    longest = max(len(r.req.prompt) + len(r.req.output) for r in records)
    return min(max_len, -(-longest // step) * step)


def served_gaps(ref, records, t_pad: int) -> np.ndarray:
    """The gap of every served token of ``records`` under ``ref``."""
    seqs, rows, toks = rows_of(records)
    h = ref.hidden(seqs, rows, t_pad)
    st = ref.head_stats(h, [toks])
    return (st["max"] - st["at"][:, 0]) / st["std"]


def control_gaps(ref, low, records, t_pad: int) -> np.ndarray:
    """The gap, under ``ref``, of the token the lower-precision ``low``
    puts first at each of the same positions."""
    seqs, rows, _ = rows_of(records)
    first = low.head_stats(low.hidden(seqs, rows, t_pad), [])["argmax"]
    st = ref.head_stats(ref.hidden(seqs, rows, t_pad), [first])
    return (st["max"] - st["at"][:, 0]) / st["std"]


def judge(numbers: Dict[str, tuple]) -> bool:
    """Every compared number within its limit: {name: (value, limit)}."""
    return all(v is not None and lim is not None and v <= lim
               for v, lim in numbers.values())

"""Operations and bytes of each kernel call, from the algorithm's shapes.

Rows are the tokens actually served (not padded slots, not a kernel's
row bucket), and attention reads the cache only up to each row's valid
length, at the format's bytes per value.  A kernel rewritten to skip
masked tiles or to pad less changes its time, never these counts.
Activations enter and leave a dense site in the model dtype (2 bytes).
"""
from __future__ import annotations

from typing import Iterable, Tuple

ACT_BYTES = 2  # bfloat16 activations in and out of a site
# bits per stored weight value and bytes per scale entry, by format
WEIGHT_BITS = {"ternary": 2, "int4": 4, "nf4": 4, "int8": 8, "mx": 8}
SCALE_BYTES = 1  # one int8 mantissa per (cluster, output column)
# bytes per (token, kv head) of keys or of values: kv_int8 holds an int8
# mantissa a value and an int8 exponent a token; kv_mx int4 mantissas and
# one exponent per 32 tokens; kv_bf16 two bytes a value
KV_BYTES = {"kv_int8": lambda hd: hd + 1, "kv_mx": lambda hd: hd / 2 + 1 / 32,
            "kv_bf16": lambda hd: 2 * hd}


def qmm(rows: int, k: int, n: int, fmt: str, group: int) -> Tuple[float, float]:
    """(operations, bytes) of one dense site over ``rows`` served rows:
    2*rows*k*n integer multiply-adds; the packed weights, their scale
    table, and the activations in and out."""
    ops = 2.0 * rows * k * n
    weights = k * n * WEIGHT_BITS[fmt] / 8 + (k // group) * n * SCALE_BYTES
    return ops, weights + rows * (k + n) * ACT_BYTES


def attention(contexts: Iterable[int], fills: Iterable[int], n_heads: int,
              n_kv_heads: int, hd: int, kv_fmt: str) -> Tuple[float, float]:
    """(operations, bytes) of one cache attention.  ``contexts``: keys
    each query row attends (its causal prefix, capped by its fill level);
    ``fills``: the valid length of each cache row read.  Scores and the
    weighted sum are 2*hd multiply-adds per (query head, key) each; the
    cache is read once up to each fill level, queries and outputs move
    once in the model dtype."""
    contexts = list(contexts)
    ops = 4.0 * n_heads * hd * sum(contexts)
    per_token = 2 * n_kv_heads * KV_BYTES[kv_fmt](hd)
    io = 2 * len(contexts) * n_heads * hd * ACT_BYTES
    return ops, float(sum(fills)) * per_token + io


def least_seconds(ops: float, nbytes: float, op_peak: float,
                  bw_peak: float) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops, t_bytes = ops / op_peak, nbytes / bw_peak
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "bytes")

"""Per-dispatch counts of the decoder-only transformer (configurations
whose ``reference`` is ``transformer``), from the shapes in the
configuration file and the load generator's record of what each dispatch served.
Another family brings ``bench/costs/<reference>.py`` with the same
``dispatch``."""
from __future__ import annotations

from typing import Dict, List, Tuple

from bench import costs


def sites(config: dict) -> List[Tuple[str, int, int, str, int]]:
    """(path, k, n, fmt, group) of every dense site of one layer, then the
    lm_head."""
    c = config
    d, ff = c["hidden_size"], c["intermediate_size"]
    h, kh, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    f = c["quant"]["formats"]
    b, hf = f["blocks"], f["lm_head"]
    pad = c["vocab_pad_to"]
    vocab = -(-c["vocab_size"] // pad) * pad
    out = [("attn/wq", d, h * hd), ("attn/wk", d, kh * hd),
           ("attn/wv", d, kh * hd), ("attn/wo", h * hd, d),
           ("mlp/gate", d, ff), ("mlp/up", d, ff), ("mlp/down", ff, d)]
    rows = [(p, k, n, b["fmt"], b["group"]) for p, k, n in out]
    return rows + [("lm_head", d, vocab, hf["fmt"], hf["group"])]


def dispatch(config: dict, peaks: dict, kind: str, valid: List[int],
             start: int = 0, size: int = 0) -> Dict[str, float]:
    """Counts of one engine dispatch.  A generate tick serves one row per
    active slot (``valid``: each row's fill level after its write); a
    prefill chunk serves ``size`` rows of one request from ``start`` and
    projects only its last row through the lm_head.

    Returns the model's operations (``ops``) and rows (``tokens``), and
    for each kernel the least seconds the chip could take over all its
    calls (``qmm_least_s``, ``attn_least_s``) with the share of those
    seconds that bytes, not operations, bound (``*_bytes_bound_s``)."""
    c = config
    layers = c["num_hidden_layers"]
    h, kh, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    i8, bf16, bw = peaks["int8_ops"], peaks["bf16_flops"], peaks["hbm_bytes_per_s"]
    if kind == "generate":
        rows, head_rows = len(valid), len(valid)
        contexts, fills = list(valid), list(valid)
    else:
        rows, head_rows = size, 1
        contexts, fills = range(start + 1, start + size + 1), [start + size]
    out = {"ops": 0.0, "tokens": float(rows), "qmm_least_s": 0.0,
           "qmm_bytes_bound_s": 0.0}
    for path, k, n, fmt, group in sites(c):
        lm_head = path == "lm_head"
        ops, nbytes = costs.qmm(head_rows if lm_head else rows, k, n, fmt, group)
        times = 1 if lm_head else layers
        t, bound = costs.least_seconds(ops, nbytes, i8, bw)
        out["ops"] += times * ops
        out["qmm_least_s"] += times * t
        out["qmm_bytes_bound_s"] += times * t * (bound == "bytes")
    ops, nbytes = costs.attention(contexts, fills, h, kh, hd, c["kv_fmt"])
    t, bound = costs.least_seconds(ops, nbytes, bf16, bw)
    out["ops"] += layers * ops
    out["attn_least_s"] = layers * t
    out["attn_bytes_bound_s"] = layers * t * (bound == "bytes")
    return out

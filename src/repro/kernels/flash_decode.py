"""Flash-decode over a packed quantized KV cache: the S == 1 special case.

The kernel itself lives in ``kernels/flash_prefill.py`` as the unified
``flash_attend`` (grid (B, S/bq, T/bk), online softmax, in-VMEM
dequant of the packed kv_bf16 / kv_int8 / kv_mx leaves); a decode step is
a one-row chunk whose start IS its query position.  This module keeps the
original decode-shaped entry point -- (B, Kh, G, hd) queries, no S axis --
so PR-7 call sites and the S == 1 parity matrix
(``tests/test_flash_decode.py``) are untouched.

Masking per batch row: k_pos < valid[b] (cache fill level), k_pos <=
q_pos[b] (causal), q_pos[b] - k_pos < window (sliding-window layers; pass
2**30 for global).  The XLA fold-the-scales path in
``models/attention.py::_attend_dense`` stays as the oracle.
"""
from __future__ import annotations

import jax

from repro.kernels.flash_prefill import (  # noqa: F401  (re-exports)
    NEG_INF,
    _dequant_tile,
    flash_attend,
    pick_kv_block,
)


def flash_decode(
    q: jax.Array,  # (B, Kh, G, hd) queries, one token per batch row
    k: jax.Array,  # (B, T, Kh, hd) | (B, T, Kh, hd//2) packed mantissas
    v: jax.Array,
    ke,  # None | (B, T, Kh, 1) | (B, T/32, Kh, 1) int8 exponents
    ve,
    q_pos: jax.Array,  # (B, 1) int32 write position of the query token
    valid: jax.Array,  # (B, 1) int32 cache fill level per batch row
    window: jax.Array,  # (1, 1) int32 sliding window (2**30 = global)
    *,
    fmt: str,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (B, Kh, G, hd) f32 attention output."""
    out = flash_attend(
        q[:, None], k, v, ke, ve, q_pos, valid, window,
        fmt=fmt, block_k=block_k, interpret=interpret,
    )
    return out[:, 0]

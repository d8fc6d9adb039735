"""Pallas TPU kernel: int8 activations x packed 2-bit ternary weights.

Grid (M/bm, N/bn, K/bk); the k axis is an accumulation ("arbitrary") axis
revisiting the same (bm, bn) output tile.  Per k-step:

  1. fetch a (bk/16, bn) uint32 weight-word tile HBM->VMEM  (2 bits/weight:
     8x less HBM traffic than bf16 -- the TPU-native payoff of ternary),
  2. decode to int8 lanes in VMEM,
  3. for each of the bk/G clusters in the tile: one MXU int8 dot with int32
     accumulation over the G-element segment, then ONE multiply by the
     cluster's 8-bit scale mantissa (the paper's 1-multiply-per-N*K^2-accs),
  4. accumulate into the f32 output tile.

Shared exponents (weight scale_e + activation e) are powers of two applied
by the ops.py wrapper outside the kernel.  Both entry points wrap the shared
builders in ``kernels/_common`` (``packed_qmm_call`` / ``fused_qmm_call``):
the scaffolding above is format-independent, only the 2-bit tile decode is
ternary's own.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels._common import (
    TERNARY_PER_WORD,
    decode2_tile,
    fused_qmm_call,
    packed_qmm_call,
)


@functools.partial(
    jax.jit, static_argnames=("group", "block_m", "block_n", "block_k", "interpret")
)
def ternary_matmul(
    x_q: jax.Array,  # int8 (M, K)
    packed: jax.Array,  # uint32 (K/16, N)
    scale_m: jax.Array,  # int8 (K/group, N)
    *,
    group: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    return packed_qmm_call(
        x_q, packed, scale_m,
        fmt="ternary", decode=decode2_tile, words_per_k=TERNARY_PER_WORD, group=group,
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "group", "act", "act_bits", "act_exponent",
        "block_m", "block_n", "block_k", "interpret",
    ),
)
def ternary_matmul_fused(
    x: jax.Array,  # f32/bf16 (M, K) RAW activations (quantized in-kernel)
    packed: jax.Array,  # uint32 (K/16, N)
    scale_m: jax.Array,  # int8 (K/group, N)
    scale_e: jax.Array,  # int32 scalar
    *,
    group: int,
    bias: jax.Array = None,  # (N,) fused into the epilogue
    act: str = None,
    act_bits: int = 8,
    act_exponent: int = None,  # static DFP exponent; None -> per-row dynamic
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Whole dense site in one pallas_call: quantize prologue + ternary
    matmul + exp2/bias/activation epilogue (exponents applied in-kernel)."""
    return fused_qmm_call(
        x, packed, scale_m, scale_e,
        fmt="ternary", decode=decode2_tile, words_per_k=TERNARY_PER_WORD, n=packed.shape[1],
        group=group, bias=bias, act=act, act_bits=act_bits,
        act_exponent=act_exponent, block_m=block_m, block_n=block_n,
        block_k=block_k, interpret=interpret,
    )

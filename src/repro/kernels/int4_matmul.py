"""Pallas TPU kernel: int8 activations x packed 4-bit DFP weights.

Same tiling/accumulation structure as ternary_matmul (see that module), with
4-bit two's-complement decode (8 weights per uint32 word -> 4x HBM traffic
reduction vs bf16) and per-cluster 8-bit scale mantissas.  Both entry points
wrap the shared builders in ``kernels/_common`` (``packed_qmm_call`` /
``fused_qmm_call``): the grid/BlockSpec scaffolding is format-independent,
only the tile decode differs.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels._common import (
    INT4_PER_WORD,
    decode4_tile,
    fused_qmm_call,
    packed_qmm_call,
)


@functools.partial(
    jax.jit, static_argnames=("group", "block_m", "block_n", "block_k", "interpret")
)
def int4_matmul(
    x_q: jax.Array,  # int8 (M, K)
    packed: jax.Array,  # uint32 (K/8, N)
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
        fmt="int4", decode=decode4_tile, words_per_k=INT4_PER_WORD, group=group,
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
def int4_matmul_fused(
    x: jax.Array,  # f32/bf16 (M, K) RAW activations (quantized in-kernel)
    packed: jax.Array,  # uint32 (K/8, N)
    scale_m: jax.Array,  # int8 (K/group, N)
    scale_e: jax.Array,  # int32 scalar
    *,
    group: int,
    bias: jax.Array = None,
    act: str = None,
    act_bits: int = 8,
    act_exponent: int = None,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Whole dense site in one pallas_call: quantize prologue + int4 matmul
    + exp2/bias/activation epilogue (exponents applied in-kernel)."""
    return fused_qmm_call(
        x, packed, scale_m, scale_e,
        fmt="int4", decode=decode4_tile, words_per_k=INT4_PER_WORD, n=packed.shape[1],
        group=group, bias=bias, act=act, act_bits=act_bits,
        act_exponent=act_exponent, block_m=block_m, block_n=block_n,
        block_k=block_k, interpret=interpret,
    )

"""Pallas TPU kernel: int8 activations x packed 4-bit NormalFloat weights.

Same tiling/accumulation structure as int4_matmul (8 codes per uint32 word,
4x HBM traffic reduction vs bf16), but the 4-bit fields are *lookup-table
indices*, not two's-complement mantissas: each code selects one of the 16
NF4 quantiles, stored on the int8 grid (``repro.core.quantizer.NF4_LUT_I8``)
so the decoded tile feeds the MXU int8 contraction exactly like every other
format.  The per-cluster scale is the cluster's absmax / 127, re-quantized
to 8-bit DFP -- one multiply per cluster, the paper's arithmetic budget,
with the LUT soaking up the normal-shaped weight distribution that a uniform
int4 grid wastes codes on.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels._common import (
    NF4_PER_WORD,
    decode_nf4_tile,
    fused_qmm_call,
    packed_qmm_call,
)


@functools.partial(
    jax.jit, static_argnames=("group", "block_m", "block_n", "block_k", "interpret")
)
def nf4_matmul(
    x_q: jax.Array,  # int8 (M, K)
    packed: jax.Array,  # uint32 (K/8, N) of 4-bit LUT codes
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
        fmt="nf4", decode=decode_nf4_tile, words_per_k=NF4_PER_WORD, group=group,
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
def nf4_matmul_fused(
    x: jax.Array,  # f32/bf16 (M, K) RAW activations (quantized in-kernel)
    packed: jax.Array,  # uint32 (K/8, N) of 4-bit LUT codes
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
    """Whole dense site in one pallas_call: quantize prologue + in-kernel
    16-entry-LUT nf4 decode + matmul + exp2/bias/activation epilogue."""
    return fused_qmm_call(
        x, packed, scale_m, scale_e,
        fmt="nf4", decode=decode_nf4_tile, words_per_k=NF4_PER_WORD, n=packed.shape[1],
        group=group, bias=bias, act=act, act_bits=act_bits,
        act_exponent=act_exponent, block_m=block_m, block_n=block_n,
        block_k=block_k, interpret=interpret,
    )

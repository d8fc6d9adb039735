"""Pallas TPU kernel: int8 x int8 matmul with per-cluster DFP scales.

Used for the layers the policy pins to 8-bit (embedding/C1 analogue,
lm_head, MoE router).  int8 MXU contraction at 2x bf16 throughput, int32
accumulation, one scale multiply per cluster.  Both entry points wrap the
shared builders in ``kernels/_common`` (``packed_qmm_call`` /
``fused_qmm_call``) with the identity decode: raw int8 storage, the tile IS
the mantissas (words_per_k=1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels._common import fused_qmm_call, packed_qmm_call


def _decode_raw(words: jnp.ndarray, bk: int) -> jnp.ndarray:
    return words  # raw int8 storage: the tile IS the mantissas


@functools.partial(
    jax.jit, static_argnames=("group", "block_m", "block_n", "block_k", "interpret")
)
def int8_matmul(
    x_q: jax.Array,  # int8 (M, K)
    w_q: jax.Array,  # int8 (K, N)
    scale_m: jax.Array,  # int8 (K/group, N)
    *,
    group: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    return packed_qmm_call(
        x_q, w_q, scale_m,
        fmt="int8", decode=_decode_raw, words_per_k=1, group=group,
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
def int8_matmul_fused(
    x: jax.Array,  # f32/bf16 (M, K) RAW activations (quantized in-kernel)
    w_q: jax.Array,  # int8 (K, N)
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
    """Whole dense site in one pallas_call: quantize prologue + int8 matmul
    + exp2/bias/activation epilogue (exponents applied in-kernel)."""
    return fused_qmm_call(
        x, w_q, scale_m, scale_e,
        fmt="int8", decode=_decode_raw, words_per_k=1, n=w_q.shape[1],
        group=group, bias=bias, act=act, act_bits=act_bits,
        act_exponent=act_exponent, block_m=block_m, block_n=block_n,
        block_k=block_k, interpret=interpret,
    )

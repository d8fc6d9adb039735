"""Pallas TPU kernel: fused per-row dynamic activation quantization.

One VMEM pass per row block: max|x| -> shared exponent -> round-to-nearest
int8 mantissas.  Fusing the three steps avoids two extra HBM round-trips of
the f32 activation tensor (the dominant cost of dynamic quantization on a
bandwidth-bound chip).

This is the standalone prologue used by the *unfused* qmatmul pipeline
(``quantize_activations`` selects it on TPU); the fused ``qdense`` path goes
further and runs the same quantization inside the matmul kernel itself
(``kernels/_common.fused_qmm_call``) so the int8 mantissas never touch HBM
at all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dfp import exp2i, qmax
from repro.kernels._common import m_bucket, pick_block

_COMPILER_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel",))
# The input and int8 output row blocks, each double-buffered, take at most
# this much of the TPU's scoped VMEM (16 MiB on a v5e), leaving the rest to
# the kernel's f32 temporaries: 64 f32 rows of D = 32768 alone need 16 MiB.
_BLOCK_VMEM_BYTES = 8 << 20


def _kernel(x_ref, q_ref, e_ref, *, bits: int):
    x = x_ref[...].astype(jnp.float32)  # (bm, D)
    max_abs = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    safe = jnp.maximum(max_abs, jnp.finfo(jnp.float32).tiny)
    e = jnp.ceil(jnp.log2(safe / qmax(bits)))
    e = jnp.where(max_abs > 0, e, jnp.zeros_like(e))
    q = jnp.clip(jnp.round(x * exp2i(-e)), -qmax(bits), qmax(bits))
    q_ref[...] = q.astype(jnp.int8)
    e_ref[...] = e.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bits", "block_m", "interpret"))
def quantize_rows(
    x: jax.Array,  # f32/bf16 (M, D)
    *,
    bits: int = 8,
    block_m: int = 256,
    interpret: bool = False,
):
    """Returns (int8 mantissas (M, D), int32 exponents (M, 1))."""
    m, d = x.shape
    # ragged serving batches: pad rows to a power-of-two bucket (same policy
    # as the matmul backends -- aligned blocks, one trace per bucket) rather
    # than shrinking the block to an arbitrary divisor of M
    mp = m_bucket(m)
    if mp != m:
        x = jnp.pad(x, ((0, mp - m), (0, 0)))  # zero rows -> q=0, e=0
    rows = _BLOCK_VMEM_BYTES // (2 * d * (x.dtype.itemsize + 1))
    bm = pick_block(mp, max(8, min(block_m, rows)))
    kern = functools.partial(_kernel, bits=bits)
    q, e = pl.pallas_call(
        kern,
        grid=(mp // bm,),
        in_specs=[pl.BlockSpec((bm, d), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, d), jnp.int8),
            jax.ShapeDtypeStruct((mp, 1), jnp.int32),
        ],
        compiler_params=None if interpret else _COMPILER_PARAMS,
        interpret=interpret,
        name="quantize_rows",
    )(x)
    return (q[:m], e[:m]) if mp != m else (q, e)

"""Pallas TPU kernel: causal flash attention (online softmax over KV tiles).

The serving/training hot spot next to the quantized GEMMs.  Grid
(B*H, S/bq, T/bk) with the KV axis innermost ("arbitrary"): each (batch*head,
query-tile) revisits its output tile across KV tiles carrying running
(max, denom) statistics in VMEM scratch -- the S x T score matrix never
exists, mirroring the XLA-level chunked formulation in models/attention.py
(which remains the ref oracle / portable path).

Causal masking is positional: the KV tile index against the query tile
index; fully-masked tiles still run (grid is static) but contribute zero
via the -inf bias.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, bq, bk, causal, scale):
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale  # (bq, hd)
    k = k_ref[0].astype(jnp.float32)  # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bq, bk)
    if causal:
        q_pos = q_idx * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = kv_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_ref[...]  # (bq, 1)
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jax.Array,  # (BH, S, hd)
    k: jax.Array,  # (BH, T, hd)
    v: jax.Array,  # (BH, T, hd)
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    bh, s, hd = q.shape
    t = k.shape[1]
    bq, bk = min(block_q, s), min(block_k, t)
    assert s % bq == 0 and t % bk == 0, (s, t, bq, bk)
    scale = hd**-0.5

    kern = functools.partial(_kernel, bq=bq, bk=bk, causal=causal, scale=scale)
    return pl.pallas_call(
        kern,
        grid=(bh, s // bq, t // bk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[
            # running max / denom / accumulator live across the kv axis
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=None if interpret else _COMPILER_PARAMS,
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)


def flash_attention_ref(q, k, v, causal: bool = True) -> jax.Array:
    """Pure-jnp oracle (dense softmax)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bsh,bth->bst", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    if causal:
        sq, t = s.shape[1], s.shape[2]
        mask = jnp.arange(t)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bst,bth->bsh", p, v.astype(jnp.float32)).astype(q.dtype)

"""Shared Pallas helpers: in-VMEM sub-8-bit decode, tiling math, and the
prologue/epilogue-fused quantized-dense kernel builder.

TPU adaptation notes (see DESIGN.md Sec. 2.1): weights live in HBM packed
2-bit (16/uint32) or 4-bit (8/uint32).  A weight tile is decoded once in
VMEM to int8 lanes and contracted on the MXU with int32 accumulation; the
per-cluster scale is applied to the int32 partial -- one multiply per
cluster, exactly the paper's arithmetic budget.

``fused_qmm_call`` builds the whole dense-site pipeline as ONE pallas_call:

  prologue  : f32/bf16 activations quantized to int8 DFP mantissas in VMEM
              (per-row dynamic exponents computed on the first k-step, or a
              calibrated static exponent baked in as a compile-time scalar),
  matmul    : the per-format decode + per-cluster int32 accumulation loop,
  epilogue  : ``out * exp2(scale_e + xe)``, bias add, optional activation
              applied inside the resident output tile on the last k-step.

The unfused path round-trips the activation tensor through HBM three extra
times per projection (int8 write, raw f32 write, scaled/bias re-write); the
fused form reads x once and writes the finished output once.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dfp import exp2i as _exp2i

_FUSED_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode: everywhere but a TPU.

    The one place the platform decides this.  On the TPU every kernel
    compiles through Mosaic; elsewhere (CPU tests) the interpreter runs the
    same kernel bodies so parity tests see the exact semantics."""
    return jax.default_backend() != "tpu"


TERNARY_PER_WORD = 16
INT4_PER_WORD = 8
NF4_PER_WORD = 8  # 4-bit LUT codes per uint32 (packed like int4)
MX_BLOCK = 32  # mx shared-exponent block length along K


def decode2_tile(words: jnp.ndarray, bk: int) -> jnp.ndarray:
    """(bk/16, bn) uint32 -> (bk, bn) int8 in {-1, 0, 1}."""
    lanes = []
    for i in range(TERNARY_PER_WORD):
        c = ((words >> (2 * i)) & jnp.uint32(3)).astype(jnp.int32)
        lanes.append((((c + 1) & 3) - 1).astype(jnp.int8))
    return jnp.stack(lanes, axis=1).reshape(bk, words.shape[-1])


def decode4_tile(words: jnp.ndarray, bk: int) -> jnp.ndarray:
    """(bk/8, bn) uint32 -> (bk, bn) int8 in [-8, 7]."""
    lanes = []
    for i in range(INT4_PER_WORD):
        c = ((words >> (4 * i)) & jnp.uint32(0xF)).astype(jnp.int32)
        lanes.append(jnp.where(c >= 8, c - 16, c).astype(jnp.int8))
    return jnp.stack(lanes, axis=1).reshape(bk, words.shape[-1])


def decode_nf4_tile(words: jnp.ndarray, bk: int) -> jnp.ndarray:
    """(bk/8, bn) uint32 of nf4 LUT codes -> (bk, bn) int8 LUT mantissas.

    The 16-entry lookup runs in-kernel as a select chain over the constant
    table (gathers from VMEM constants do not lower on all Pallas targets;
    16 vector selects per lane do, and vectorize on the VPU).  The resulting
    mantissas are ordinary int8 lanes, so the MXU contraction and per-cluster
    scale application downstream are identical to every other format."""
    from repro.core.quantizer import NF4_LUT_I8

    lanes = []
    for i in range(NF4_PER_WORD):
        c = ((words >> (4 * i)) & jnp.uint32(0xF)).astype(jnp.int32)
        v = jnp.zeros_like(c)
        for code, val in enumerate(NF4_LUT_I8):
            v = jnp.where(c == code, jnp.int32(val), v)
        lanes.append(v.astype(jnp.int8))
    return jnp.stack(lanes, axis=1).reshape(bk, words.shape[-1])


def pick_block(dim: int, want: int) -> int:
    """Largest divisor of ``dim`` that is <= want (block shape helper)."""
    b = min(dim, want)
    while dim % b:
        b -= 1
    return b


def m_bucket(m: int) -> int:
    """Power-of-two row bucket (>= 8) ragged batches pad up to.

    Serving batches come in every size; padding M to the next power of two
    collapses them onto a handful of kernel specializations instead of one
    fresh trace/compile per distinct batch size."""
    b = 8
    while b < m:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# The unfused packed-matmul kernel (shared across weight formats).
# ---------------------------------------------------------------------------
def _packed_kernel(x_ref, w_ref, s_ref, out_ref, *, decode, bk: int, group: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w8 = decode(w_ref[...], bk)  # (bk, bn) int8 mantissa lanes
    x = x_ref[...]  # (bm, bk) int8
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for s in range(bk // group):
        xs = jax.lax.slice_in_dim(x, s * group, (s + 1) * group, axis=1)
        ws = jax.lax.slice_in_dim(w8, s * group, (s + 1) * group, axis=0)
        part = jax.lax.dot_general(
            xs, ws, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
        )
        # one multiply per cluster: scale mantissa applied to the int32 partial
        acc = acc + part.astype(jnp.float32) * s_ref[s, :].astype(jnp.float32)[None, :]
    out_ref[...] += acc


def packed_qmm_call(
    x_q: jax.Array,  # int8 (M, K) activation mantissas
    packed: jax.Array,  # per-format packed weights ((K/words_per_k, N))
    scale_m: jax.Array,  # int8 (K/group, N)
    *,
    fmt: str,  # weight format: the kernel is named packed_qmm_<fmt>
    decode: Callable,  # (words tile, bk) -> (bk, bn) int8
    words_per_k: int,
    group: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """One pallas_call for the unfused per-format matmul: tile decode +
    per-cluster int32 accumulation.  The grid/BlockSpec scaffolding is
    identical for every weight encoding -- only ``decode``/``words_per_k``
    vary -- so every per-format kernel module (ternary/int4/int8/nf4; mx
    aliases int8) wraps this builder instead of copying the tiling loop
    (the fused twin is ``fused_qmm_call``).  Exponents (scale_e +
    activation e) are applied by the caller."""
    m, k = x_q.shape
    n = packed.shape[1]
    bm, bn = min(block_m, m), min(block_n, n)
    bk = min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    assert bk % group == 0 and bk % words_per_k == 0, (bk, group, words_per_k)

    kern = functools.partial(_packed_kernel, decode=decode, bk=bk, group=group)
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // words_per_k, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // group, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        # same parallel/parallel/arbitrary semantics as the fused builder
        compiler_params=None if interpret else _FUSED_COMPILER_PARAMS,
        interpret=interpret,
        name=f"packed_qmm_{fmt}",
    )(x_q, packed, scale_m)


# ---------------------------------------------------------------------------
# The fused quantized-dense kernel (shared across weight formats).
# ---------------------------------------------------------------------------
# The ONE activation-name table: both the fused kernel epilogue and the
# unfused jnp epilogue (quant/backends.apply_act) dispatch through it, so
# the supported-name sets can never drift apart.
ACTIVATIONS = {
    None: lambda y: y,
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
}


def activation_fn(name: Optional[str]) -> Callable:
    try:
        return ACTIVATIONS[name]
    except KeyError:
        raise ValueError(
            f"unknown activation {name!r}; supported: "
            f"{sorted(k for k in ACTIVATIONS if k)}"
        ) from None


def _fused_kernel(
    x_ref,  # (bm, K) f32/bf16: the full activation row block, resident per i
    w_ref,  # (bk/words_per_k, bn): packed weight words for this k-tile
    s_ref,  # (bk/group, bn) int8: per-cluster scale mantissas
    se_ref,  # (1, 1) int32: shared weight-scale exponent
    *rest,  # [b_ref (1, bn) f32 when has_bias,] out_ref (bm, bn) f32, e_scr
    decode: Callable,
    bk: int,
    group: int,
    nk: int,
    act_bits: int,
    static_e: Optional[int],
    act: Optional[str],
    has_bias: bool,
    exact: bool,
):
    if has_bias:
        b_ref, out_ref, e_scr = rest
    else:
        (out_ref, e_scr), b_ref = rest, None
    kk = pl.program_id(2)
    qmax = float(2 ** (act_bits - 1) - 1)
    # interpret mode pins bit-parity with the jnp oracle: the barrier forces
    # each f32 product to round before it feeds an add, which XLA:CPU would
    # otherwise contract into an fma (single rounding, 1-ulp drift)
    rnd = jax.lax.optimization_barrier if exact else (lambda v: v)

    @pl.when(kk == 0)
    def _prologue():
        out_ref[...] = jnp.zeros_like(out_ref)
        if static_e is None:
            # per-row dynamic DFP exponent over the FULL row (the row block
            # is resident, so the first k-step sees all of K); bit-identical
            # to kernels/quantize.py and dfp.choose_exponent
            x = x_ref[...].astype(jnp.float32)
            max_abs = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
            safe = jnp.maximum(max_abs, jnp.finfo(jnp.float32).tiny)
            e = jnp.ceil(jnp.log2(safe / qmax))
            e_scr[...] = jnp.where(max_abs > 0, e, jnp.zeros_like(e))

    if static_e is None:
        e = e_scr[...]  # (bm, 1) f32
    else:
        e = jnp.full((x_ref.shape[0], 1), float(static_e), jnp.float32)

    # quantize just this k-tile of the resident row block (VMEM -> VMEM);
    # exp2i builds the power-of-two scale exactly (jnp.exp2 is approximated
    # on some backends, which breaks the DFP contract AND bit parity)
    xs = x_ref[:, pl.ds(kk * bk, bk)].astype(jnp.float32)
    xq = jnp.clip(jnp.round(xs * _exp2i(-e)), -qmax, qmax).astype(jnp.int8)

    w8 = decode(w_ref[...], bk)  # (bk, bn) int8 lanes
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for s in range(bk // group):
        xg = jax.lax.slice_in_dim(xq, s * group, (s + 1) * group, axis=1)
        wg = jax.lax.slice_in_dim(w8, s * group, (s + 1) * group, axis=0)
        part = jax.lax.dot_general(
            xg, wg, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
        )
        # one multiply per cluster: scale mantissa applied to the int32 partial
        acc = acc + rnd(
            part.astype(jnp.float32) * s_ref[s, :].astype(jnp.float32)[None, :]
        )
    out_ref[...] += acc

    @pl.when(kk == nk - 1)
    def _epilogue():
        y = out_ref[...] * _exp2i(se_ref[0, 0].astype(jnp.float32) + e)
        if has_bias:
            y = rnd(y) + b_ref[...]
        out_ref[...] = activation_fn(act)(y)


def fused_qmm_call(
    x: jax.Array,  # f32/bf16 (M, K) raw activations
    packed: jax.Array,  # per-format packed weights
    scale_m: jax.Array,  # int8 (K/group, N)
    scale_e: jax.Array,  # int32 scalar
    *,
    fmt: str,  # weight format: the kernel is named fused_qmm_<fmt>
    decode: Callable,  # (words tile, bk) -> (bk, bn) int8
    words_per_k: int,  # K rows per packed row (1 for raw int8 storage)
    n: int,
    group: int,
    bias: Optional[jax.Array] = None,  # (N,) f32, fused into the epilogue
    act: Optional[str] = None,
    act_bits: int = 8,
    act_exponent: Optional[int] = None,  # static exponent; None -> dynamic
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """One pallas_call for quantize-prologue + qmatmul + scale/bias/act."""
    m, k = x.shape
    bm, bn = min(block_m, m), min(block_n, n)
    bk = min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    assert bk % group == 0 and bk % words_per_k == 0, (bk, group, words_per_k)
    nk = k // bk

    kern = functools.partial(
        _fused_kernel,
        decode=decode, bk=bk, group=group, nk=nk, act_bits=act_bits,
        static_e=None if act_exponent is None else int(act_exponent),
        act=act, has_bias=bias is not None, exact=interpret,
    )
    in_specs = [
        # full activation row block: resident across the j and kk axes, so x
        # is read from HBM once per row tile, not once per (j, kk) step
        pl.BlockSpec((bm, k), lambda i, j, kk: (i, 0)),
        pl.BlockSpec((bk // words_per_k, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((bk // group, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
    ]
    args = [x, packed, scale_m, jnp.asarray(scale_e, jnp.int32).reshape(1, 1)]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        args.append(bias.astype(jnp.float32).reshape(1, n))
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, 1), jnp.float32)],
        compiler_params=None if interpret else _FUSED_COMPILER_PARAMS,
        interpret=interpret,
        name=f"fused_qmm_{fmt}",
    )(*args)

"""Pallas TPU kernel: unified flash attention over a packed quantized KV cache.

``flash_attend`` generalizes the PR-7 flash-decode kernel from S == 1 to
whole prefill chunks: a (B, S, Kh, G, hd) query block attends against the
full cache with grid (B, S/bq, T/bk), the KV axis innermost
("arbitrary").  Each (batch, query-block) program holds every kv head and
revisits its output tile across KV tiles carrying running (m, l, acc)
online-softmax statistics per head in VMEM scratch -- the (S, T) score
plane never exists, and the cache streams from HBM once per query block,
*packed*:

  * kv_bf16  tiles load as bf16 and cast,
  * kv_int8  tiles load int8 mantissas + a (bk, Kh, 1) exponent block and
    dequantize in-VMEM via exact power-of-two scales (``dfp.exp2i``),
  * kv_mx    tiles load nibble-packed int4 mantissas (bk, Kh, hd/2) + one
    exponent per 32-token block (bk/32, Kh, 1), unpack and shift in-VMEM.

All G query heads of a KV group ride in one tile as bq*G rows, so GQA and
MHA (G == 1) share the layout.  Masking is positional per query row: the
chunk's traced ``q_start[b]`` anchors row r of query block qi at absolute
position q_start[b] + qi*bq + r//G, and a key column is live iff

    k_pos < valid[b]  (cache fill level -- ragged rows)
    k_pos <= q_pos    (causal, against the absolute chunk offset)
    q_pos - k_pos < window  (sliding-window layers; 2**30 = global)

Query rows are assumed CONTIGUOUS from ``q_start`` (position q_start + s
for chunk row s) -- exactly what ``transformer.prefill_chunk`` and the
decode step produce.  The grid is static, but a (batch row, query block)
reads and computes only the KV tiles that hold a live key for some row of
the block (``live_kv_tiles``): the K/V index maps clamp the KV step to
that range, so the steps outside it name the block already in VMEM and
issue no DMA, and the body skips them.  The result is the same as running
every tile: a tile after the last live key adds p = 0 with corr = 1, and
one before the first (sliding window) is wiped by corr = 0.

The XLA fold-the-scales path in ``models/attention.py::_attend_dense``
stays as the oracle; ``tests/test_flash_prefill.py`` holds the S > 1
parity matrix (formats x masking x head mapping x ragged starts) next to
the S == 1 matrix in ``tests/test_flash_decode.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import dfp
from repro.kernels._common import interpret_mode
from repro.models.kv_cache import MX_KV_BLOCK

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)

NEG_INF = -1e30


def _dequant_tile(ref, eref, h: int, fmt: str, bk: int, hd: int) -> jax.Array:
    """Head ``h``'s (bk, hd) f32 KV tile from a packed all-heads VMEM block."""
    tile = ref[:, h, :]
    if fmt == "kv_bf16":
        return tile.astype(jnp.float32)
    if fmt == "kv_int8":
        e = eref[:, h, :]  # (bk, 1) int8
        return tile.astype(jnp.float32) * dfp.exp2i(e)
    # kv_mx: unpack nibble pairs along head_dim, one exponent per 32 tokens
    b32 = tile.astype(jnp.int32)  # (bk, hd//2) uint8 widened
    lo, hi = b32 & 0xF, (b32 >> 4) & 0xF
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    # lanes come out [even channels | odd channels]: flash_attend feeds the
    # queries in that order and restores it on the output (an in-kernel
    # lane interleave costs Mosaic minutes of compile)
    codes = jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32)
    e = eref[:, h, :]  # (bk // 32, 1) int8
    nb = bk // MX_KV_BLOCK
    e_tok = jnp.broadcast_to(
        e.reshape(nb, 1, 1), (nb, MX_KV_BLOCK, 1)
    ).reshape(bk, 1)
    return codes * dfp.exp2i(e_tok)


def live_kv_tiles(q_start, valid, window, qi, *, bq: int, bk: int, xp=jnp):
    """First and last KV tile holding a live key for query block ``qi``.

    The block's rows sit at q_start + qi*bq .. q_start + qi*bq + bq - 1; a
    key is live for one of them iff k_pos < valid, k_pos <= the last row
    (causal) and k_pos > the first row - window.  ``xp=jnp`` evaluates it
    on traced scalars (index maps, kernel body); ``xp=np`` on host integers
    or arrays (``live_kv_steps``).  The range is empty when first > last.
    """
    lo = q_start + qi * bq
    first = xp.maximum(lo - window + 1, 0) // bk
    last = xp.minimum(valid - 1, lo + bq - 1) // bk
    return first, last


def live_kv_steps(q_start, valid, window: int, *, s: int, g: int, t: int,
                  fmt: str) -> tuple:
    """(live, grid): the KV grid steps ``flash_attend`` runs its body on
    for rows at ``q_start`` / ``valid`` (host arrays, one entry a row) with
    S queries each, summed over rows and query blocks, and the grid's KV
    steps (B * S/bq * T/bk)."""
    bq, bk = pick_q_block(s, g), pick_kv_block(t, fmt)
    q_start = np.asarray(q_start, np.int64).reshape(-1, 1)
    valid = np.asarray(valid, np.int64).reshape(-1, 1)
    qi = np.arange(s // bq)[None, :]
    first, last = live_kv_tiles(q_start, valid, window, qi, bq=bq, bk=bk,
                                xp=np)
    live = int(np.maximum(last - first + 1, 0).sum())
    return live, q_start.shape[0] * (s // bq) * (t // bk)


def kv_tile(bi, qi, ji, qs_ref, vl_ref, win_ref, *, bq: int, bk: int):
    """The KV tile grid step (bi, qi, ji) loads: ``ji`` clamped to the
    block's live tiles.  A dead step names its neighbouring live tile,
    whose block is already in VMEM, so the pipeline issues no DMA for it;
    the outer clamp keeps an empty range (valid == 0) on the grid."""
    first, last = live_kv_tiles(qs_ref[bi], vl_ref[bi], win_ref[0], qi,
                                bq=bq, bk=bk)
    return jnp.maximum(jnp.minimum(jnp.maximum(ji, first), last), 0)


def _kernel(qs_ref, vl_ref, win_ref, *refs, fmt, kh, rows, bk, g, hd, scale):
    if fmt == "kv_bf16":
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        ke_ref = ve_ref = None
    else:
        (q_ref, k_ref, v_ref, ke_ref, ve_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
    b_idx = pl.program_id(0)
    q_idx = pl.program_id(1)
    kv_idx = pl.program_id(2)
    valid, win = vl_ref[b_idx], win_ref[0]
    first, last = live_kv_tiles(qs_ref[b_idx], valid, win, q_idx,
                                bq=rows // g, bk=bk)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((first <= kv_idx) & (kv_idx <= last))
    def _attend():
        # all G heads of a kv group ride as interleaved rows: row r of
        # query block q_idx is chunk position (q_idx * rows + r) // G
        k_pos = kv_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        row = q_idx * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        q_pos = qs_ref[b_idx] + row // g  # (rows, 1) absolute
        ok = (k_pos < valid) & (k_pos <= q_pos) & (q_pos - k_pos < win)

        for h in range(kh):  # the block holds every kv head of these tokens
            q = q_ref[h].astype(jnp.float32) * scale  # (rows, hd)
            kf = _dequant_tile(k_ref, ke_ref, h, fmt, bk, hd)  # (bk, hd)
            s = jax.lax.dot_general(
                q, kf, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (rows, bk)
            s = jnp.where(ok, s, NEG_INF)

            m_prev, l_prev = m_ref[h], l_ref[h]  # (rows, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            vf = _dequant_tile(v_ref, ve_ref, h, fmt, bk, hd)  # (bk, hd)
            pv = jax.lax.dot_general(
                p, vf, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            l_ref[h] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + pv
            m_ref[h] = m_new

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def pick_kv_block(t: int, fmt: str, want: int = 128) -> int:
    """Largest divisor of T that is <= want; a 32-multiple for kv_mx."""
    if fmt == "kv_mx":
        nb = t // MX_KV_BLOCK
        b = min(nb, max(1, want // MX_KV_BLOCK))
        while nb % b:
            b -= 1
        return b * MX_KV_BLOCK
    b = min(t, want)
    while t % b:
        b -= 1
    return b


def pick_q_block(s: int, g: int, want: int = 64) -> int:
    """Largest divisor of S keeping bq*G query rows near ``want``.

    The kernel flattens a query block to bq*G rows (all G heads of the KV
    group), so the row budget -- not bq alone -- is what VMEM sees.  A
    block short of the whole chunk keeps bq*G a multiple of 8 (the TPU's
    sublane tile); when no divisor does, the whole chunk is one block."""
    b = min(s, max(1, want // g))
    while s % b or (b != s and (b * g) % 8):
        b -= 1
        if b == 0:
            return s
    return b


def flash_attend(
    q: jax.Array,  # (B, S, Kh, G, hd) chunk queries, grouped heads
    k: jax.Array,  # (B, T, Kh, hd) | (B, T, Kh, hd//2) packed mantissas
    v: jax.Array,
    ke,  # None | (B, T, Kh, 1) | (B, T/32, Kh, 1) int8 exponents
    ve,
    q_start: jax.Array,  # (B, 1) int32 absolute position of chunk row 0
    valid: jax.Array,  # (B, 1) int32 cache fill level per batch row
    window: jax.Array,  # (1, 1) int32 sliding window (2**30 = global)
    *,
    fmt: str,
    block_q: int = 64,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (B, S, Kh, G, hd) f32 attention output.

    Query row s of batch b sits at absolute position q_start[b] + s (the
    contiguous-chunk contract); masking is causal against that offset plus
    the fill level and sliding window.  S == 1 with q_start = q_pos is
    exactly the flash-decode special case.

    TPU layout: a grid step owns one (batch row, query block, KV tile) and
    every kv head in it.  A cache block is then (bk, Kh, lanes) -- its last
    two dimensions whole, as Mosaic requires -- so the cache is read in
    place, once per query block.  The queries are regrouped to
    (B, Kh, S*G, hd) (a chunk-sized copy), and the per-row scalars ride in
    SMEM as scalar-prefetch operands."""
    b, s, kh, g, hd = q.shape
    t = k.shape[1]
    if interpret is None:
        interpret = interpret_mode()
    bq = pick_q_block(s, g, block_q)
    bk = pick_kv_block(t, fmt, block_k)
    rows = bq * g
    scale = hd**-0.5

    if fmt == "kv_mx":  # head_dim in the unpacked tiles' lane order
        q = jnp.concatenate([q[..., 0::2], q[..., 1::2]], axis=-1)
    qg = q.transpose(0, 2, 1, 3, 4).reshape(b, kh, s * g, hd)
    q_spec = pl.BlockSpec(
        (None, kh, rows, hd), lambda bi, qi, ji, *_: (bi, 0, qi, 0)
    )

    def kv_map(bi, qi, ji, *refs):
        return bi, kv_tile(bi, qi, ji, *refs, bq=bq, bk=bk), 0, 0

    kv_spec = pl.BlockSpec((None, bk, kh, k.shape[-1]), kv_map)
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [qg, k, v]
    if fmt != "kv_bf16":
        eb = bk if fmt == "kv_int8" else bk // MX_KV_BLOCK
        e_spec = pl.BlockSpec((None, eb, kh, 1), kv_map)
        in_specs += [e_spec, e_spec]
        args += [ke, ve]

    kern = functools.partial(
        _kernel, fmt=fmt, kh=kh, rows=rows, bk=bk, g=g, hd=hd, scale=scale
    )
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, s // bq, t // bk),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                # running max / denom / accumulator survive the kv axis
                pltpu.VMEM((kh, rows, 1), jnp.float32),
                pltpu.VMEM((kh, rows, 1), jnp.float32),
                pltpu.VMEM((kh, rows, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kh, s * g, hd), jnp.float32),
        compiler_params=None if interpret else _COMPILER_PARAMS,
        interpret=interpret,
        name="flash_attend",
    )(
        q_start.reshape(b).astype(jnp.int32),
        valid.reshape(b).astype(jnp.int32),
        window.reshape(1).astype(jnp.int32),
        *args,
    )
    out = out.reshape(b, kh, s, g, hd).transpose(0, 2, 1, 3, 4)
    if fmt == "kv_mx":
        out = jnp.stack(
            [out[..., : hd // 2], out[..., hd // 2:]], axis=-1
        ).reshape(out.shape)
    return out

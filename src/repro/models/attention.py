"""Grouped-query attention with the flavours the assigned archs need:
qk-norm (qwen3), qkv-bias (qwen1.5), M-RoPE (qwen2-vl), sliding-window local
layers (gemma3 5:1), cross-attention (whisper), KV-cache decode.

Training/prefill uses an online-softmax chunked formulation (flash-attention
scheme at the XLA level): KV is scanned in blocks with running max/sum so the
S x S score matrix is never materialized -- this is what keeps the roofline
memory term linear in S.

The decode KV cache is a registered block format (``models/kv_cache.py``:
kv_bf16 / kv_int8 / kv_mx) quantized on write.  Two read paths exist:

  * the XLA fold-the-scales path (``_attend_dense``): per-token power-of-two
    scales fold into the score/probability tensors, so the dequantized
    cache never materializes.  This is the oracle and the portable default.
  * the Pallas flash kernel (``kernels/flash_prefill.py::flash_attend``):
    loads the *packed* leaves and dequantizes tile-by-tile in VMEM -- one
    HBM pass over the packed bytes.  ``cfg.flash_decode`` routes S == 1
    steps; ``cfg.flash_prefill`` routes S > 1 cache-attends (chunked
    prefill) and the in-chunk self-attention tail.  Both are serving-time
    knobs (the kernel has no VJP) and fall back to the oracle whenever a
    multi-device activation mesh is installed -- a pallas_call cannot read
    a kv-head- or sequence-sharded (KV_SEQ_SHARD) cache correctly, so the
    bypass is structural, not best-effort.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import kv_cache, layers
from repro.models.layers import QuantCtx, dense
from repro.parallel import sharding as _sh

NEG_INF = -1e30


def init_attention(key, cfg, dtype, cross: bool = False) -> dict:
    hd = cfg.hd()
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": layers.init_dense_layer(kq, cfg.d_model, cfg.n_heads * hd, cfg.qkv_bias, dtype),
        "wk": layers.init_dense_layer(kk, cfg.d_model, cfg.n_kv_heads * hd, cfg.qkv_bias, dtype),
        "wv": layers.init_dense_layer(kv, cfg.d_model, cfg.n_kv_heads * hd, cfg.qkv_bias, dtype),
        "wo": layers.init_dense_layer(ko, cfg.n_heads * hd, cfg.d_model, False, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = layers.init_rmsnorm(hd, dtype)
        p["k_norm"] = layers.init_rmsnorm(hd, dtype)
    return p


def _split_heads(x: jax.Array, n: int) -> jax.Array:
    return x.reshape(*x.shape[:-1], n, x.shape[-1] // n)


def _mask_bias(
    q_pos: jax.Array,  # (S,) or (B, S)
    k_pos: jax.Array,  # (T,)
    causal: bool,
    window: Optional[int],
    valid_len: Optional[jax.Array] = None,  # (B,) cache fill level
) -> jax.Array:
    """Additive mask (..., S, T)."""
    qp = q_pos[..., :, None].astype(jnp.int32)
    kp = k_pos[None, :].astype(jnp.int32)
    ok = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        ok &= kp <= qp
    if window is not None:
        ok &= qp - kp < window
    if valid_len is not None:
        ok &= kp < valid_len[:, None, None]
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _attend_dense(q, k, v, bias, kscale=None, vscale=None):
    """q (B,S,Kh,G,hd), k/v (B,T,Kh,hd), bias broadcastable to (B,Kh,G,S,T).

    Grouped-KV layout: used on the decode path where the score tensor is
    (..., 1, T) and repeating KV would blow up cache traffic.

    kscale/vscale: optional per-token cache scales (B,T,Kh) -- exact powers
    of two from the kv format's exponent planes.  They are folded into the
    score/probability tensors so the dequantized cache is never
    materialized (``kv_cache.attend_view`` supplies integer codes).
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bskgh,btkh->bkgst", q.astype(jnp.float32), k.astype(jnp.float32))
    if kscale is not None:  # fold key scales into the scores
        s = s * kscale.transpose(0, 2, 1)[:, :, None, None, :]
    s = s * scale + bias
    p = jax.nn.softmax(s, axis=-1)
    if vscale is not None:  # fold value scales into the probabilities
        p = p * vscale.transpose(0, 2, 1)[:, :, None, None, :]
    out = jnp.einsum("bkgst,btkh->bskgh", p, v.astype(jnp.float32))
    return out


def _attend_dense_mha(q, k, v, bias):
    """Full-head layout: q/k/v (B,S|T,H,hd); bias (..., S, T).  KV heads are
    pre-repeated so the head axis shards over 'model' (Kh alone often does
    not divide the TP width, e.g. 8 kv heads on 16-way TP)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale + bias
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p, v.astype(jnp.float32))


def _attend_chunked(q, k, v, q_pos, causal, window, chunk: int):
    """Online-softmax over KV chunks (flash-attention scheme at XLA level).

    q (B,S,H,hd); k/v (B,T,H,hd) (KV pre-repeated to full heads).  Only the
    (m, l, acc) carries survive a chunk; scores/probs are recomputed in the
    backward pass (jax.checkpoint).  T need not divide the chunk size: the
    trailing T % chunk tokens run as one final partial chunk instead of
    silently falling back to the O(S*T)-materializing dense path.
    """
    b, s, h, hd = q.shape
    t = k.shape[1]
    scale = hd**-0.5
    qf = q.astype(jnp.float32) * scale
    n_full, rem = divmod(t, chunk)

    def step(carry, ks, vs, k_pos):
        m, l, acc = carry
        bias = _mask_bias(q_pos, k_pos, causal, window)  # (S, c) or (B,S,c)
        bias = bias[None] if bias.ndim == 2 else bias[:, None]
        sc = jnp.einsum("bshd,bthd->bhst", qf, ks.astype(jnp.float32))
        sc = sc + bias  # (B,H,S,c)
        m_new = jnp.maximum(m, sc.max(-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        upd = jnp.einsum("bhst,bthd->bshd", p, vs.astype(jnp.float32))
        acc_new = acc * corr.transpose(0, 2, 1)[..., None] + upd
        return m_new, l_new, acc_new

    def body(carry, idx):
        ks = jax.lax.dynamic_slice_in_dim(k, idx * chunk, chunk, 1)
        vs = jax.lax.dynamic_slice_in_dim(v, idx * chunk, chunk, 1)
        k_pos = idx * chunk + jnp.arange(chunk)
        return step(carry, ks, vs, k_pos), None

    carry = (
        jnp.full((b, h, s), NEG_INF, jnp.float32),
        jnp.zeros((b, h, s), jnp.float32),
        jnp.zeros((b, s, h, hd), jnp.float32),
    )
    if n_full:
        carry, _ = jax.lax.scan(
            jax.checkpoint(body), carry, jnp.arange(n_full)
        )
    if rem:  # final partial chunk (static shape: compiled once per length)
        carry = step(
            carry, k[:, n_full * chunk:], v[:, n_full * chunk:],
            n_full * chunk + jnp.arange(rem),
        )
    m, l, acc = carry
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return acc / denom


def _win_arg(window) -> jax.Array:
    return jnp.asarray(
        2**30 if window is None else window, jnp.int32
    ).reshape(1, 1)


def _flash_cache_path(q, cache, fmt, q_pos, valid, window, cfg):
    """Route an S >= 1 cache-attend through the packed-cache Pallas kernel.

    S == 1 is the flash-decode step; S > 1 is a prefill chunk, whose rows
    the kernel assumes CONTIGUOUS from q_pos's first entry -- exactly what
    ``transformer.prefill_chunk`` traces (start + arange(S))."""
    from repro.kernels.flash_prefill import flash_attend

    b, s = q.shape[0], q.shape[1]
    hd = cfg.hd()
    kh = cfg.n_kv_heads
    g = cfg.n_heads // kh
    qf = q.reshape(b, s, kh, g, hd).astype(jnp.float32)
    if q_pos.ndim == 2:  # (B, S) per-row positions
        qs = q_pos[:, 0]
    else:  # (S,) traced positions shared by every row
        qs = jnp.broadcast_to(q_pos.reshape(-1)[0], (b,))
    out = flash_attend(
        qf, cache["k"], cache["v"], cache.get("ke"), cache.get("ve"),
        qs.astype(jnp.int32).reshape(b, 1),
        valid.astype(jnp.int32).reshape(b, 1),
        _win_arg(window), fmt=fmt,
    )
    return out.reshape(b, s, cfg.n_heads * hd)


def _flash_self_path(q, k, v, window, cfg):
    """In-chunk self-attention tail through the flash kernel.

    The chunk's own just-projected bf16 K/V stand in for a packed cache
    (fmt="kv_bf16"): positions are chunk-relative (causality and window
    distance are offset-invariant within one chunk), fill level is the
    whole chunk."""
    from repro.kernels.flash_prefill import flash_attend

    b, s = q.shape[0], q.shape[1]
    hd = cfg.hd()
    kh = cfg.n_kv_heads
    g = cfg.n_heads // kh
    qf = q.reshape(b, s, kh, g, hd).astype(jnp.float32)
    out = flash_attend(
        qf, k, v, None, None,
        jnp.zeros((b, 1), jnp.int32),
        jnp.full((b, 1), k.shape[1], jnp.int32),
        _win_arg(window), fmt="kv_bf16",
    )
    return out.reshape(b, s, cfg.n_heads * hd)


def attention(
    p: dict,
    x: jax.Array,  # (B, S, d)
    positions: jax.Array,  # (S,) | (B,S) | (3,B,S) for mrope
    cfg,
    ctx: QuantCtx,
    path: str,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    kv_src: Optional[jax.Array] = None,  # cross-attention source (B, T, d)
    cache: Optional[Dict[str, jax.Array]] = None,  # kv leaves (B, Smax, ...)
    cache_index: Optional[jax.Array] = None,  # scalar write position
    chunk: int = 1024,
    rope: bool = True,
    attend_cache: bool = False,  # S>1 chunk attends over the whole cache
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Returns (output (B,S,d), updated cache dict or None).

    ``cache`` is a kv-format leaf dict ({"k","v"} plus {"ke","ve"} exponent
    planes for quantized formats) as allocated by ``kv_cache.init_cache``;
    the format itself resolves from ``cfg`` (``kv_fmt`` / ``kv_bits``).

    ``attend_cache`` forces the cache-attend (decode) path for S > 1: after
    the chunk's K/V are written at ``cache_index``, scores run against the
    FULL cache, so earlier chunks of the same prompt are visible.  This is
    what chunked prefill needs -- the plain prefill path only attends over
    the chunk's own K/V and would drop history for any chunk after the
    first.  S == 1 decode behaves exactly as before.
    """
    hd = cfg.hd()
    g = cfg.n_heads // cfg.n_kv_heads
    src = x if kv_src is None else kv_src

    q = _split_heads(dense(p["wq"], x, f"{path}/wq", ctx), cfg.n_heads)
    k = _split_heads(dense(p["wk"], src, f"{path}/wk", ctx), cfg.n_kv_heads)
    v = _split_heads(dense(p["wv"], src, f"{path}/wv", ctx), cfg.n_kv_heads)
    if cfg.qk_norm:
        q = layers.rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = layers.rmsnorm(p["k_norm"], k, cfg.norm_eps)

    use_rope = rope and kv_src is None  # no rope on cross-attention
    if use_rope:
        if cfg.mrope:
            q = layers.apply_mrope(q, positions, cfg.rope_theta)
            k = layers.apply_mrope(k, positions, cfg.rope_theta)
            q_pos = positions[0]  # temporal component orders causality
        else:
            q = layers.apply_rope(q, positions, cfg.rope_theta)
            k = layers.apply_rope(k, positions, cfg.rope_theta)
            q_pos = positions
    else:
        q_pos = positions

    new_cache = None
    decode = cache is not None and (x.shape[1] == 1 or attend_cache)
    if cache is not None:
        fmt = kv_cache.resolve_kv_fmt(cfg)
        with jax.named_scope("kv_write"):
            new_cache, valid = kv_cache.write(fmt, cache, k, v, cache_index)

    if decode:
        # flash routing: S == 1 under cfg.flash_decode, S > 1 cache-attends
        # (chunked prefill) under cfg.flash_prefill -- independent knobs.
        # Both require a whole-per-device cache (kernels_routable); S > 1
        # additionally requires a causal layer (the kernel's masking
        # contract), which every self-attention prefill chunk is.
        flash = (
            getattr(cfg, "flash_decode", False)
            if x.shape[1] == 1
            else getattr(cfg, "flash_prefill", False) and causal
        )
        with jax.named_scope("attention"):
            if flash and _sh.kernels_routable():
                out = _flash_cache_path(
                    q, new_cache, fmt, q_pos, valid, window, cfg
                )
            else:
                # XLA fold-the-scales oracle: grouped-KV layout over the
                # whole cache, (..., S, T) scores, per-token scales folded in
                ck, cv, kscale, vscale = kv_cache.attend_view(fmt, new_cache)
                t = ck.shape[1]
                k_pos = jnp.arange(t)
                bias = _mask_bias(q_pos, k_pos, causal, window, valid)
                if bias.ndim == 2:
                    bias = bias[None, None, None]  # (1,1,1,S,T)
                else:
                    bias = bias[:, None, None]  # (B,1,1,S,T)
                qh = q.reshape(*q.shape[:2], cfg.n_kv_heads, g, hd)
                out = _attend_dense(qh, ck, cv, bias, kscale=kscale,
                                    vscale=vscale)
                out = out.reshape(*x.shape[:2], cfg.n_heads * hd)
            out = out.astype(x.dtype)
        return dense(p["wo"], out, f"{path}/wo", ctx), new_cache

    # in-chunk self-attention tail: a full-prompt prefill (cache written,
    # chunk attends only its own K/V) can run the flash kernel on the
    # just-projected bf16 K/V instead of the chunked/dense XLA paths.
    # `cache is not None` keeps training out (the kernel has no VJP).
    if (
        cache is not None
        and x.shape[1] > 1
        and causal
        and kv_src is None
        and getattr(cfg, "flash_prefill", False)
        and _sh.kernels_routable()
    ):
        with jax.named_scope("attention"):
            out = _flash_self_path(q, k, v, window, cfg).astype(x.dtype)
        return dense(p["wo"], out, f"{path}/wo", ctx), new_cache

    # training / prefill: repeat KV to full heads so the head axis shards
    # over 'model' even when n_kv_heads does not divide the TP width.
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    q = _sh.constrain(q, ("batch", None, "heads", None))
    k = _sh.constrain(k, ("batch", None, "heads", None))
    v = _sh.constrain(v, ("batch", None, "heads", None))
    t = k.shape[1]
    with jax.named_scope("attention"):
        if t > chunk:
            out = _attend_chunked(q, k, v, q_pos, causal, window, chunk)
        else:
            k_pos = jnp.arange(t)
            if causal or window is not None:
                bias = _mask_bias(q_pos, k_pos, causal, window)
                bias = bias[None] if bias.ndim == 2 else bias[:, None]
            else:
                bias = jnp.zeros((), jnp.float32)
            out = _attend_dense_mha(q, k, v, bias)
        out = out.reshape(*x.shape[:2], cfg.n_heads * hd).astype(x.dtype)
    return dense(p["wo"], out, f"{path}/wo", ctx), new_cache

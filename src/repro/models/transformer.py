"""Decoder-only LM covering the dense / MoE / VLM families.

Layers are stacked on a leading axis and driven by lax.scan (fast compiles at
80 layers, and the unit XLA overlaps FSDP all-gathers against).  Blocks are
optionally rematerialized.  gemma3-style 5:1 local:global attention is a
per-layer window array scanned alongside the params (window == S acts as
global).  KV caches are scan-carried (L, B, Smax, Kh, hd) arrays.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import kv_cache, layers, moe
from repro.models.layers import QuantCtx
from repro.parallel import sharding


def window_schedule(cfg, seq_len: int) -> Optional[jax.Array]:
    """Per-layer attention window; None when the arch has no local layers."""
    if not cfg.sliding_window:
        return None
    ratio = cfg.local_global_ratio
    win = []
    for i in range(cfg.n_layers):
        is_global = ratio and ((i + 1) % (ratio + 1) == 0)
        win.append(seq_len + 1 if is_global else cfg.sliding_window)
    return jnp.asarray(win, jnp.int32)


def init_block(key, cfg, dtype) -> Dict[str, Any]:
    ka, km = jax.random.split(key)
    p = {
        "ln1": layers.init_rmsnorm(cfg.d_model, dtype),
        "attn": attn_lib.init_attention(ka, cfg, dtype),
        "ln2": layers.init_rmsnorm(cfg.d_model, dtype),
    }
    if cfg.n_experts:
        p["moe"] = moe.init_moe(km, cfg, dtype)
    else:
        p["mlp"] = layers.init_mlp(km, cfg.d_model, cfg.d_ff, dtype)
    return p


def init_lm(key, cfg) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.dtype)
    ke, kb, kh = jax.random.split(key, 3)
    block_keys = jax.random.split(kb, cfg.n_layers)
    params = {
        "embed": layers.init_embedding(ke, cfg.padded_vocab, cfg.d_model, dtype),
        # vmapped over the layer keys: the same values as initializing each
        # block and stacking, in a program that does not grow with depth
        "blocks": jax.vmap(lambda k: init_block(k, cfg, dtype))(block_keys),
        "final_norm": layers.init_rmsnorm(cfg.d_model, dtype),
        "lm_head": layers.init_dense_layer(kh, cfg.d_model, cfg.padded_vocab, False, dtype),
    }
    return params


def _block_apply(
    bp, x, positions, cfg, ctx: QuantCtx, window, cache=None, cache_index=None,
    attend_cache=False,
):
    # NOTE (Perf iteration B2, REFUTED): constraining the attention/MoE
    # sublayer outputs to seq-sharded here (Megatron-SP style) halves the
    # TP-pair all-reduce but forces a full KV re-gather in every layer's
    # attention -- net collective bytes DOUBLED (4.3 -> 9.2 GB/step on
    # grok x prefill_32k).  The per-block residual constrain in forward()
    # is the right granularity; sublayer outputs stay unconstrained.
    h = layers.rmsnorm(bp["ln1"], x, cfg.norm_eps)
    a, new_cache = attn_lib.attention(
        bp["attn"], h, positions, cfg, ctx, "blocks/attn",
        causal=True, window=window, cache=cache, cache_index=cache_index,
        attend_cache=attend_cache,
    )
    x = x + a
    h = layers.rmsnorm(bp["ln2"], x, cfg.norm_eps)
    if cfg.n_experts:
        x = x + moe.moe_layer(bp["moe"], h, "blocks/moe", cfg, ctx)
    else:
        x = x + layers.mlp(bp["mlp"], h, "blocks/mlp", ctx)
    return x, new_cache


def hidden(
    params,
    tokens: jax.Array,  # (B, S) int32
    cfg,
    ctx: QuantCtx,
    positions: Optional[jax.Array] = None,
    extra_embeds: Optional[jax.Array] = None,  # VLM: (B, n_vis, d) prepended
) -> jax.Array:
    x = layers.embed(params["embed"], tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    s = x.shape[1]
    if positions is None:
        positions = jnp.arange(s)
    win = window_schedule(cfg, s)

    def body(h, scanned):
        bp = scanned["p"]
        w = scanned.get("w")
        h = sharding.constrain(h, ("batch", "seq", None))
        h, _ = _block_apply(bp, h, positions, cfg, ctx, w)
        return h, None

    if cfg.remat:
        body = jax.checkpoint(body)
    scanned = {"p": params["blocks"]}
    if win is not None:
        scanned["w"] = win
    x, _ = jax.lax.scan(body, x, scanned)
    return layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)


def forward(params, tokens, cfg, ctx: QuantCtx, positions=None, extra_embeds=None):
    x = hidden(params, tokens, cfg, ctx, positions, extra_embeds)
    return layers.dense(params["lm_head"], x, "lm_head", ctx)


def loss_fn(params, batch, cfg, ctx: QuantCtx) -> jax.Array:
    x = hidden(
        params, batch["tokens"], cfg, ctx,
        positions=batch.get("positions"),
        extra_embeds=batch.get("extra_embeds"),
    )
    labels = batch["labels"]
    if x.shape[1] != labels.shape[1]:  # VLM: loss on the text tail only
        x = x[:, -labels.shape[1] :]
    return layers.lm_head_loss(
        params["lm_head"], x, labels, cfg.vocab, "lm_head", ctx
    )


# ---------------------------------------------------------------------------
# KV-cache serving path
# ---------------------------------------------------------------------------
def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Registered kv-format leaves stacked (L, B, Smax, ...); see
    ``models/kv_cache.py`` (``cfg.kv_fmt`` knob, ``kv_bits==8`` back-compat)."""
    return kv_cache.init_cache(cfg, (cfg.n_layers, batch), max_len, dtype)


# leaf names the kv formats may allocate, in scan-carry order
KV_LEAF_NAMES = ("k", "v", "ke", "ve")


def _cache_scan(params, x, positions, cfg, ctx, cache, cache_index, win,
                attend_cache=False):
    kv_keys = [n for n in KV_LEAF_NAMES if n in cache]

    def body(h, scanned):
        bp = scanned["p"]
        w = scanned.get("w")
        c = {n: scanned[n] for n in kv_keys}
        h, new = _block_apply(
            bp, h, positions, cfg, ctx, w, cache=c, cache_index=cache_index,
            attend_cache=attend_cache,
        )
        return h, {n: new[n] for n in kv_keys}

    scanned = {"p": params["blocks"]}
    scanned.update({k: v for k, v in cache.items()})
    if win is not None:
        scanned["w"] = win
    x, new_cache = jax.lax.scan(body, x, scanned)
    return x, new_cache


def prefill(params, tokens, cfg, ctx: QuantCtx, cache, extra_embeds=None):
    """Fill the cache with S tokens; returns (last-token logits, cache)."""
    x = layers.embed(params["embed"], tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    s = x.shape[1]
    positions = jnp.arange(s)
    win = window_schedule(cfg, cache["k"].shape[2])
    x, cache = _cache_scan(params, x, positions, cfg, ctx, cache, jnp.int32(0), win)
    x = layers.rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    return layers.dense(params["lm_head"], x, "lm_head", ctx), cache


def prefill_chunk(params, tokens, start, cfg, ctx: QuantCtx, cache):
    """Consume one chunk of a prompt against a partially-filled cache.

    ``tokens`` (B, S) land at cache positions [start, start + S); attention
    runs over the WHOLE cache (``attend_cache``), so chunks after the first
    see every earlier chunk of the same prompt.  ``start`` is a traced
    scalar -- the graph compiles once per chunk LENGTH, never per offset.
    Returns (last-token logits, cache); only the final chunk's logits are
    meaningful to a caller sampling the first generated token.
    """
    x = layers.embed(params["embed"], tokens)
    s = x.shape[1]
    positions = start + jnp.arange(s)
    if cfg.mrope:  # text-only serving prompt: all three components temporal
        positions = jnp.broadcast_to(
            positions[None, None, :], (3, tokens.shape[0], s)
        )
    win = window_schedule(cfg, cache["k"].shape[2])
    x, cache = _cache_scan(
        params, x, positions, cfg, ctx, cache, start, win, attend_cache=True
    )
    x = layers.rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    return layers.dense(params["lm_head"], x, "lm_head", ctx), cache


def decode_step(params, token, pos, cfg, ctx: QuantCtx, cache):
    """One decode step. token (B, 1) int32; pos scalar OR per-slot (B,)."""
    x = layers.embed(params["embed"], token)
    if jnp.ndim(pos) == 1:
        positions = pos[:, None].astype(jnp.int32)
    else:
        positions = jnp.full((token.shape[0], 1), pos, jnp.int32)
    if cfg.mrope:
        positions = jnp.broadcast_to(positions, (3, *positions.shape))
    win = window_schedule(cfg, cache["k"].shape[2])
    x, cache = _cache_scan(params, x, positions, cfg, ctx, cache, pos, win)
    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return layers.dense(params["lm_head"], x, "lm_head", ctx), cache

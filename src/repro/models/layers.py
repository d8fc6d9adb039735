"""Building-block layers (pure pytree params, no framework dependency).

Every projection goes through ``dense()`` which consults the quantization
context (``repro.quant.QuantCtx``, a thin view over a compiled ``QuantPlan``
or a raw ``PrecisionPolicy``): full precision, QAT fake-quant (STE, Sec. 4
of the paper), or PTQ with real QTensor weights through the registry-driven
``qdense`` -- one whole-site call that carries the bias and an optional
activation into the kernel epilogue, so on fused backends (pallas) a
projection is a single pallas_call with no intermediate HBM round-trips.
With a compiled plan, per-site precision is a dict lookup (no per-call
regex), PTQ activations use the plan's calibrated static exponents where
profiled (per-site ``fused``/``static_act`` knobs), and a ctx carrying an
``observer`` records activation ranges for calibration.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import ste
from repro.quant.api import observe_site
from repro.quant.backends import apply_act, qdense
from repro.quant.plan import QuantCtx  # noqa: F401  (canonical re-export)
from repro.quant.qtensor import QTensor

Params = Dict[str, Any]


def _init_dense(key, d_in: int, d_out: int, bias: bool, dtype) -> Params:
    std = d_in**-0.5
    p = {"w": jax.random.normal(key, (d_in, d_out), dtype) * std}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(
    p: Params, x: jax.Array, path: str, ctx: QuantCtx,
    act: Optional[str] = None,
) -> jax.Array:
    """Quantization-aware projection x @ W (+ b) (+ activation ``act``).

    ``act`` ("silu"/"gelu"/"relu") rides into the PTQ kernel epilogue on
    fused backends; on the fp/QAT paths it is applied after the bias, so all
    modes compute the same function.  Its device ops carry the scope
    ``qdense/<leaf of path>`` (``qdense/wq``, ``qdense/lm_head``).
    """
    with jax.named_scope(f"qdense/{path.rsplit('/', 1)[-1]}"):
        return _dense(p, x, path, ctx, act)


def _dense(p: Params, x: jax.Array, path: str, ctx: QuantCtx,
           act: Optional[str]) -> jax.Array:
    w = p["w"]
    if ctx.observer is not None:  # calibration pass: record this site's range
        observe_site(ctx.observer, path, x)
    if isinstance(w, QTensor):  # PTQ path: full integer pipeline, one call
        prec = ctx.resolve(path)
        y = qdense(
            x, w,
            bias=p.get("b"), act=act, backend=ctx.backend,
            act_bits=prec.act_bits if prec else 8,
            act_exponent=ctx.act_exponent(path),
            fused=prec.fused if prec else True, site=path,
        )
        return y.astype(x.dtype)
    if ctx.mode == "qat" and (ctx.plan is not None or ctx.policy is not None):
        prec = ctx.resolve(path)
        if prec is not None and prec.quantized:
            wf = w.astype(jnp.float32)
            if "inq_mask" in p:  # learned-grid INQ: the whole tensor
                # fake-quantizes onto the TRAINED cluster grid (codes
                # re-derived from w/s exactly as deployment derives them);
                # events freeze w updates, the grid keeps training
                wq = ste.inq_ste(
                    wf, p["inq_mask"], p["inq_scales"], prec.w_bits,
                    prec.group_size, prec.filter_size, prec.refit_scale,
                    fmt=prec.fmt,
                ).astype(x.dtype)
            elif prec.fmt == "ttq" and "ttq_scales" in p:
                wq = ste.ttq_ste(
                    wf, p["ttq_scales"], prec.group_size
                ).astype(x.dtype)
            else:
                wq = ste.weights_ste(
                    wf,
                    prec.w_bits,
                    prec.group_size,
                    prec.filter_size,
                    prec.refit_scale,
                    fmt=prec.fmt,
                ).astype(x.dtype)
            xq = ste.act_ste(x.astype(jnp.float32), prec.act_bits).astype(x.dtype)
            y = xq @ wq
        else:
            y = x @ w
    else:
        y = x @ w
    if "b" in p:
        y = y + p["b"]
    return apply_act(y, act)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def init_rmsnorm(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * p["scale"] + p["bias"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (standard + M-RoPE)
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    sin, cos = jnp.sin(angles)[..., None, :], jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array, positions: jax.Array, theta: float, sections=(1, 1, 2)
) -> jax.Array:
    """Qwen2-VL multimodal RoPE: positions (3, ..., S) = (t, h, w) ids, the
    hd/2 frequency lanes are split across the three components in the ratio
    ``sections`` (defaults to paper's 1:1:2 t:h:w split)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    n = hd // 2
    total = sum(sections)
    bounds = [n * sum(sections[: i + 1]) // total for i in range(3)]
    lane = jnp.arange(n)
    comp = jnp.where(lane < bounds[0], 0, jnp.where(lane < bounds[1], 1, 2))
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32)[..., None] * jnp.ones_like(freqs),
        jnp.broadcast_to(comp, positions.shape[1:] + (n,))[None],
        axis=0,
    )[0]  # (..., S, hd/2): per-lane position from its component
    angles = pos * freqs
    sin, cos = jnp.sin(angles)[..., None, :], jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU) and embedding
# ---------------------------------------------------------------------------
def init_mlp(key, d_model: int, d_ff: int, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "up": _init_dense(k1, d_model, d_ff, False, dtype),
        "gate": _init_dense(k2, d_model, d_ff, False, dtype),
        "down": _init_dense(k3, d_ff, d_model, False, dtype),
    }


def mlp(p: Params, x: jax.Array, path: str, ctx: QuantCtx) -> jax.Array:
    # silu rides into the gate projection's kernel epilogue on fused backends
    h = dense(p["gate"], x, f"{path}/gate", ctx, act="silu")
    h = h * dense(p["up"], x, f"{path}/up", ctx)
    return dense(p["down"], h, f"{path}/down", ctx)


def init_embedding(key, vocab: int, d: int, dtype) -> Params:
    return {"table": jax.random.normal(key, (vocab, d), dtype) * (d**-0.5)}


def embed(p: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0)


def lm_loss(logits: jax.Array, labels: jax.Array, vocab: int) -> jax.Array:
    """Cross entropy with vocab padding masked out of the partition function."""
    from repro.parallel import sharding as _sh

    logits = _sh.constrain(logits, ("batch", None, "feat"))
    logits = logits.astype(jnp.float32)
    pad = logits.shape[-1] - vocab
    if pad > 0:
        mask = jnp.concatenate(
            [jnp.zeros((vocab,), jnp.float32), jnp.full((pad,), -1e30, jnp.float32)]
        )
        logits = logits + mask
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def lm_head_loss(
    head: Params,
    x: jax.Array,  # (B, S, d) final hidden states
    labels: jax.Array,  # (B, S)
    vocab: int,
    path: str,
    ctx: "QuantCtx",
    chunk_tokens: int = 8192,
) -> jax.Array:
    """Fused lm_head + cross entropy, chunked over tokens.

    The full (B, S, V) f32 logits tensor is never materialized: each chunk's
    logits are computed, reduced to (lse, gold) and recomputed in the
    backward pass (jax.checkpoint).  Peak logits memory drops from
    O(B*S*V) to O(chunk*V) -- the dominant activation for large-vocab archs.
    """
    from repro.parallel import sharding as _sh

    b, s, d = x.shape
    t = b * s
    xt = _sh.constrain(x.reshape(t, d), ("batch", None))
    lt = labels.reshape(t)
    n_chunks = max(1, t // max(chunk_tokens, 1))
    while t % n_chunks:
        n_chunks -= 1
    tc = t // n_chunks
    padded = head["w"].shape[-1]
    pad = padded - vocab
    mask = None
    if pad > 0:
        mask = jnp.concatenate(
            [jnp.zeros((vocab,), jnp.float32), jnp.full((pad,), -1e30, jnp.float32)]
        )

    def body(acc, inp):
        xc, lc = inp
        xc = _sh.constrain(xc, ("batch", None))
        logits = dense(head, xc, path, ctx)
        logits = _sh.constrain(logits, ("batch", "feat")).astype(jnp.float32)
        if mask is not None:
            logits = logits + mask
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return acc + jnp.sum(lse - gold), None

    if n_chunks == 1:
        loss, _ = body(jnp.zeros((), jnp.float32), (xt, lt))
    else:
        loss, _ = jax.lax.scan(
            jax.checkpoint(body),
            jnp.zeros((), jnp.float32),
            (xt.reshape(n_chunks, tc, d), lt.reshape(n_chunks, tc)),
        )
    return loss / t


def init_dense_layer(key, d_in, d_out, bias, dtype) -> Params:
    return _init_dense(key, d_in, d_out, bias, dtype)

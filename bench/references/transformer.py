"""Plain reference of the quantized decoder-only transformer.

Straightforward ``jax.numpy`` in float32 at HIGHEST matmul precision, with
no kernels, cache or batching, and nothing imported from the program: the
semantics the configuration states, written out.

  * weights: every site's integer codes times its cluster scale mantissa
    times two to its shared exponent, from ``bench.weights.raw_site`` (the
    same seeded draws the packed tree was built from);
  * activations: per-row dynamic fixed point at ``act_bits`` at the input
    of every dense site -- the exponent is ceil(log2(max|x| / qmax)), the
    mantissas round half to even -- then one float32 product;
  * keys and values: 8-bit dynamic fixed point per (token, head) after the
    rotary embedding, as the kv_int8 cache stores them;
  * the residual stream, norms and site outputs in the configuration's
    dtype (bfloat16), as the model stores them between operations.

``logits`` runs whole sequences causally, one layer at a time (each
layer's weights drawn on the device when it runs), in query blocks, so
that it fits a chip beside nothing else.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256  # query rows per attention block
ROW_BLOCK = 512  # logit rows per head call


def _dfp(x, bits: int, axis: int = -1):
    """(mantissas as f32, 2**exponent) of x along ``axis``."""
    qmax = float(2 ** (bits - 1) - 1)
    m = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    e = jnp.ceil(jnp.log2(jnp.maximum(m, jnp.finfo(jnp.float32).tiny) / qmax))
    e = jnp.where(m > 0, e, 0.0).astype(jnp.int32)
    scale = jnp.ldexp(jnp.float32(1.0), e)
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    return q, scale


def _weight(key, fmt: str, k: int, n: int, group: int):
    codes, sm, se = W.raw_site(key, fmt, k, n, group)
    scale = jnp.repeat(sm.astype(jnp.float32), group, axis=0)
    return codes.astype(jnp.float32) * scale * jnp.ldexp(jnp.float32(1.0), se)


def _dense(x, w, act_bits: int, act=None):
    q, s = _dfp(x.astype(jnp.float32), act_bits)
    y = jnp.matmul(q * s, w, precision=HIGHEST)
    if act == "silu":
        y = jax.nn.silu(y)
    return y


def _rmsnorm(x, eps):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x (B, T, H, hd) f32 at positions 0..T-1; halves rotate together."""
    t, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


class Reference:
    """The model of one configuration file (``spec``), drawn from ``seed``."""

    def __init__(self, spec: dict, seed: int, act_bits: int = 8):
        m = spec["model"]
        self.m = m
        self.dtype = jnp.dtype(m["dtype"])
        self.act_bits = act_bits
        # an argument of every jitted call, not a constant inside it: one
        # compiled program serves every seed
        self.key = W.base_key(seed)
        self.vocab = m["padded_vocab"]
        self.hd = m["head_dim"]

    def _site(self, key, path: str, index, k: int, n: int,
              site: str = "blocks"):
        f = self.m["formats"][site]
        key = jax.random.fold_in(W.path_key(key, path), index)
        return _weight(key, f["fmt"], k, n, f["group"])

    def _attention(self, q, k, v):
        """q (B,T,H,hd) f32, k/v (B,T,Kh,hd) f32 dequantized: causal."""
        b, t, h, hd = q.shape
        g = h // k.shape[2]
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        qn = min(Q_BLOCK, t)
        nb = t // qn

        def block(i):
            qb = jax.lax.dynamic_slice_in_dim(q, i * qn, qn, 1)
            s = jnp.einsum("bshd,bthd->bhst", qb * hd ** -0.5, k,
                           precision=HIGHEST)
            qpos = i * qn + jnp.arange(qn)
            ok = jnp.arange(t)[None, :] <= qpos[:, None]
            s = jnp.where(ok[None, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhst,bthd->bshd", p, v, precision=HIGHEST)

        out = jax.lax.map(block, jnp.arange(nb))  # (nb, B, Q, H, hd)
        return jnp.moveaxis(out, 0, 1).reshape(b, t, h * hd)  # t % qn == 0

    @functools.partial(jax.jit, static_argnums=0)
    def _layer(self, key, x, layer):
        m, dt, bits = self.m, self.dtype, self.act_bits
        d, h, kh, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                            self.hd, m["d_ff"])
        site = lambda name, k, n: self._site(key, f"blocks/{name}", layer, k, n)
        hx = _rmsnorm(x, m["norm_eps"]).astype(dt)
        b, t, _ = x.shape
        q = _dense(hx, site("attn/wq", d, h * hd), bits).astype(dt)
        k = _dense(hx, site("attn/wk", d, kh * hd), bits).astype(dt)
        v = _dense(hx, site("attn/wv", d, kh * hd), bits).astype(dt)
        q = _rope(q.reshape(b, t, h, hd).astype(jnp.float32), m["rope_theta"])
        k = _rope(k.reshape(b, t, kh, hd).astype(jnp.float32), m["rope_theta"])
        q, k = q.astype(dt), k.astype(dt)
        kq, ks = _dfp(k.astype(jnp.float32), 8)  # the kv_int8 cache
        vq, vs = _dfp(v.reshape(b, t, kh, hd).astype(jnp.float32), 8)
        a = self._attention(q.astype(jnp.float32), kq * ks, vq * vs).astype(dt)
        x = x + _dense(a, site("attn/wo", h * hd, d), bits).astype(dt)
        hx = _rmsnorm(x, m["norm_eps"]).astype(dt)
        gate = _dense(hx, site("mlp/gate", d, ff), bits, "silu").astype(dt)
        up = _dense(hx, site("mlp/up", d, ff), bits).astype(dt)
        return x + _dense(gate * up, site("mlp/down", ff, d), bits).astype(dt)

    @functools.partial(jax.jit, static_argnums=0)
    def _embed(self, key, tokens):
        d = self.m["d_model"]
        table = W.raw_table(W.path_key(key, "embed/table"), self.vocab, d)
        scale = jnp.ldexp(jnp.float32(1.0), W.embed_exponent(d))
        return (table[tokens].astype(jnp.float32) * scale).astype(self.dtype)

    @functools.partial(jax.jit, static_argnums=0)
    def _head(self, key, x, tokens):
        """Per row of x: the largest logit, the logits' standard deviation,
        the argmax, and the logit of each token in ``tokens`` (R, n)."""
        d = self.m["d_model"]
        w = self._site(key, "lm_head", 0, d, self.vocab, site="lm_head")
        hx = _rmsnorm(x, self.m["norm_eps"]).astype(self.dtype)
        z = _dense(hx, w, self.act_bits)
        at = jnp.take_along_axis(z, tokens, axis=1)
        return jnp.max(z, axis=1), jnp.std(z, axis=1), jnp.argmax(z, axis=1), at

    def hidden(self, seqs: Sequence[Sequence[int]],
               rows: Sequence[Sequence[int]], t: int):
        """Final hidden states (sum(len(r)), d) of sequence i at positions
        ``rows[i]``, each sequence run causally from position 0.  Sequences
        pad to ``t`` positions, so a cell compiles one program whatever its
        sample holds."""
        tokens = np.zeros((len(seqs), t), np.int32)
        for i, s in enumerate(seqs):
            tokens[i, :len(s)] = s
        x = self._embed(self.key, jnp.asarray(tokens))
        for layer in range(self.m["n_layers"]):
            x = self._layer(self.key, x, jnp.int32(layer))
        which = np.asarray([(i, p) for i, r in enumerate(rows) for p in r],
                           np.int32)
        return x[which[:, 0], which[:, 1]]

    def head_stats(self, h, tokens: Sequence[np.ndarray]) -> dict:
        """``_head`` over the rows of ``h`` in blocks of ROW_BLOCK: numpy
        arrays "max", "std", "argmax" and "at" (rows, len(tokens))."""
        n = h.shape[0]
        cols = np.stack([np.asarray(t, np.int32) for t in tokens], 1) \
            if tokens else np.zeros((n, 1), np.int32)
        pad = -n % ROW_BLOCK
        h = jnp.pad(h, ((0, pad), (0, 0)))
        cols = np.pad(cols, ((0, pad), (0, 0)))
        parts = [self._head(self.key, h[i:i + ROW_BLOCK],
                            jnp.asarray(cols[i:i + ROW_BLOCK]))
                 for i in range(0, n + pad, ROW_BLOCK)]
        out = {k: np.concatenate([np.asarray(p[j]) for p in parts])[:n]
               for j, k in enumerate(("max", "std", "argmax", "at"))}
        return out

"""Seed-made quantized weights, built on the device in one jitted call.

Serving cold-starts from a quantized artifact (``load_servable``); the
benchmark stands in for that load with codes and scale tables drawn from
``--seed`` and packed with each weight format's own packer.  No float
model exists and no post-training quantization runs: kernel speed does not
depend on the code values.

``raw_site`` is the one source of every site's integer codes and scale
table.  The packed tree (what the program serves) and the plain reference
(``bench/references``) both call it, so the reference dequantizes the same
numbers without reading anything the program made.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

# Share of non-zero ternary codes, and the spread of int8 / int4 codes:
# the weights then have about the standard deviation d_in**-0.5 that
# the model's float initializer gives.
TERNARY_NONZERO = 0.6
INT8_CODE_STD = 40.0
INT4_CODE_STD = 2.5
SCALE_M_LO, SCALE_M_HI = 64, 127  # scale mantissas: a top-bit-set int8
EMBED_CODE_STD = 40.0
BLOCK = 256  # columns (rows of the embedding) drawn at a time


def base_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (all 64 bits count)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def path_key(key: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _exponent(target: float) -> int:
    """Shared scale exponent putting the mean mantissa near ``target``."""
    mean_m = 0.5 * (SCALE_M_LO + SCALE_M_HI)
    return int(round(math.log2(target / mean_m)))


def _code_std(fmt: str) -> float:
    """Standard deviation of one decoded code of ``fmt``."""
    if fmt == "ternary":
        return math.sqrt(TERNARY_NONZERO)
    return {"int8": INT8_CODE_STD, "int4": INT4_CODE_STD}[fmt]


def scale_exponent(fmt: str, k: int) -> int:
    """The shared exponent of a (k, n) site: weights of std k**-0.5."""
    return _exponent(k ** -0.5 / _code_std(fmt))


def _by_blocks(draw, key, n: int, axis: int):
    """``draw(key_i, width)`` for 256-wide blocks i along ``axis`` (one
    block when ``n`` is not a multiple of 256), joined along ``axis``: a
    wide matrix never has more than one block of random bits alive.
    ``draw`` may return a tuple of arrays; each is joined."""
    width = BLOCK if n % BLOCK == 0 else n
    parts = jax.lax.map(lambda i: draw(jax.random.fold_in(key, i), width),
                        jnp.arange(n // width))

    def join(p):  # (blocks, ..., width, ...) -> (..., n, ...)
        p = jnp.moveaxis(p, 0, axis)
        return p.reshape(p.shape[:axis] + (n,) + p.shape[axis + 2:])

    return jax.tree.map(join, parts)


def raw_site(key: jax.Array, fmt: str, k: int, n: int, group: int):
    """Integer codes (k, n) int8 in ``fmt``'s range, scale mantissas
    (k/group, n) int8 and the shared exponent (int32) of one matrix; drawn
    in blocks of 256 output columns."""
    def draw(kb, w):
        kc, ks = jax.random.split(kb)
        if fmt == "ternary":
            u = jax.random.uniform(kc, (k, w))
            half = 0.5 * TERNARY_NONZERO
            codes = jnp.where(u < half, -1, jnp.where(u < 2 * half, 1, 0))
        else:
            qmax = {"int8": 127, "int4": 7}[fmt]
            z = jax.random.normal(kc, (k, w)) * _code_std(fmt)
            codes = jnp.clip(jnp.round(z), -qmax, qmax)
        scale_m = jax.random.randint(
            ks, (k // group, w), SCALE_M_LO, SCALE_M_HI + 1, jnp.int32)
        return codes.astype(jnp.int8), scale_m.astype(jnp.int8)

    codes, scale_m = _by_blocks(draw, key, n, 1)
    return codes, scale_m, jnp.int32(scale_exponent(fmt, k))


def embed_exponent(d: int) -> int:
    return int(round(math.log2(d ** -0.5 / EMBED_CODE_STD)))


def raw_table(key: jax.Array, vocab: int, d: int) -> jax.Array:
    """Embedding mantissas (vocab, d) int8, drawn in blocks of 256 rows;
    every row shares the exponent ``embed_exponent(d)``, so the table lies
    on the per-row 8-bit DFP grid that quantized serving snaps it to."""
    def draw(kb, rows):
        z = jax.random.normal(kb, (rows, d)) * EMBED_CODE_STD
        return jnp.clip(jnp.round(z), -127, 127).astype(jnp.int8)

    return _by_blocks(draw, key, vocab, 0)


def _site_fmt(prec) -> str:
    from repro.quant.formats import format_for_bits, get_format

    return (get_format(prec.fmt) if prec.fmt else format_for_bits(prec.w_bits)).name


def _packed_site(key, shape, prec):
    """QTensor of a (..., K, N) site: each matrix drawn from its own key
    (``fold_in`` of its flat index over the leading axes) and packed by
    the format's encoder, one matrix at a time."""
    from repro.core.quantizer import QTensor
    from repro.quant.formats import get_format

    fmt = _site_fmt(prec)
    f = get_format(fmt)
    *lead, k, n = shape
    g = prec.group_size

    def one(i):
        codes, sm, se = raw_site(jax.random.fold_in(key, i), fmt, k, n, g)
        return f.encode(codes), sm, se

    count = math.prod(lead)
    if lead:
        packed, sm, se = jax.lax.map(one, jnp.arange(count))
        packed = packed.reshape(*lead, *packed.shape[1:])
        sm = sm.reshape(*lead, *sm.shape[1:])
        se = se.reshape(lead)
    else:
        packed, sm, se = one(0)
    return QTensor(packed, sm, se, f.bits, g, (k, n), fmt=fmt)


def make_params(api, plan, seed: int):
    """The packed parameter tree of ``api``'s model under ``plan``, drawn
    from ``seed`` on the default device in one jitted call: projection
    sites as QTensors, the embedding table on its 8-bit DFP grid in the
    model dtype, norm scales at one."""
    from repro.quant.plan import is_projection_site, site_subpath

    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    d = api.cfg.d_model

    def build(key):
        def walk(node, path):
            out = {}
            for name, val in node.items():
                sub = site_subpath(path, name)
                if is_projection_site(name, val):
                    prec = plan.resolve(path)
                    if prec is None or not prec.quantized:
                        raise ValueError(f"site {path!r} is not quantized")
                    out[name] = _packed_site(path_key(key, path), val.shape, prec)
                elif isinstance(val, dict):
                    out[name] = walk(val, sub)
                elif name == "table":
                    m = raw_table(path_key(key, sub), *val.shape)
                    scale = 2.0 ** embed_exponent(d)
                    out[name] = (m.astype(jnp.float32) * scale).astype(val.dtype)
                elif name == "scale":
                    out[name] = jnp.ones(val.shape, val.dtype)
                else:
                    raise ValueError(f"no seed rule for parameter {sub!r}")
            return out

        return walk(shapes, "")

    return jax.jit(build)(base_key(seed))

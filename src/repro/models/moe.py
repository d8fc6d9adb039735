"""Mixture-of-Experts layer: top-k routing with static-capacity sort-based
dispatch (all shapes static => pjit/dry-run friendly).

Dispatch: token replicas are sorted by expert id; each token's rank within
its expert group is computed with searchsorted; ranks beyond the expert
capacity are dropped (standard capacity-factor semantics).  Under the
production mesh the expert axis of the (E, C, d) buffer is sharded over
'model' (expert parallelism) and the scatter/gather lowers to all-to-alls.
PTQ serving under the "pallas_ep" backend goes further: the whole expert
FFN runs as one shard_map over the expert axis (``_expert_ffn``) with the
dispatch/combine all-to-alls inside the body and the fused ``qdense``
decoding only local expert slices.

The router is pinned to 8-bit by the precision policy (paper's rule that
accuracy-critical control paths keep higher precision); expert FFN weights
are ternary/4-bit clustered like any other projection.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core import ste
from repro.quant.api import observe_site
from repro.quant.backends import (
    ep_divisible,
    expert_ffn_ep,
    expert_ffn_local,
    qmatmul,
    resolve_backend,
)
from repro.quant.qtensor import QTensor
from repro.models import layers
from repro.models.layers import QuantCtx, dense
from repro.parallel import sharding

# Perf iteration B1 toggle (EXPERIMENTS.md): flat-token chunking is the
# pre-B1 baseline; sequence-aligned chunking is the default.
FLAT_CHUNKING: list = [False]


def init_moe(key, cfg, dtype) -> Dict[str, Any]:
    kr, ku, kg, kd, km = jax.random.split(key, 5)
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    std_in, std_out = d**-0.5, ff**-0.5
    p = {
        "router": layers.init_dense_layer(kr, d, e, False, dtype),
        "experts": {
            "gate": {"w": jax.random.normal(kg, (e, d, ff), dtype) * std_in},
            "up": {"w": jax.random.normal(ku, (e, d, ff), dtype) * std_in},
            "down": {"w": jax.random.normal(kd, (e, ff, d), dtype) * std_out},
        },
    }
    if cfg.moe_dense_residual:
        p["residual_mlp"] = layers.init_mlp(km, d, cfg.d_ff, dtype)
    return p


def _quantize_expert_weights(experts, ctx: QuantCtx, path: str):
    """QAT: fake-quantize the stacked expert weights once per layer call.

    NOTE (Perf iteration A2, REFUTED then reverted to lazy form): hoisting
    the Algorithm-1 fake-quant out of the dispatch-chunk scan was predicted
    to remove the re-sort cost, but XLA's loop-invariant code motion had
    already hoisted it -- the explicit hoist only pinned the quantized
    copies as live values (+5% bytes, +6.6 GiB temps on arctic x train_4k).
    The lazy per-matmul form below lets XLA place the computation."""
    if ctx.mode != "qat" or (ctx.plan is None and ctx.policy is None):
        return experts
    out = {}
    for name, leaf in experts.items():
        prec = ctx.resolve(f"{path}/experts/{name}")
        w = leaf["w"]
        out[name] = {"w": w, "_prec": prec}  # quantized lazily in the matmul
    return out


def _expert_matmul(w, x, path: str, ctx: QuantCtx, prec=None, buf_axes=None) -> jax.Array:
    """x (E, C, d_in) @ w (E, d_in, d_out); weights already fake-quantized
    (QAT) or QTensor (PTQ)."""
    if ctx.observer is not None:
        # calibration pass: record the dispatched (E, C, d) buffer's range so
        # expert MLP sites get profiled static DFP exponents like dense()
        # sites do (one shared exponent per site across experts and chunks;
        # the capacity buffer's zero padding never raises max_abs)
        observe_site(ctx.observer, path, x)
    if isinstance(w, QTensor):
        # NOTE (Perf iteration B7, REFUTED then reverted): inlining the PTQ
        # matmul with per-intermediate sharding constraints was predicted to
        # stop the partitioner replicating the f32 act-quant tensors inside
        # the chunk loop; instead it un-hoisted the weight dequantization
        # (8.5x flops, +12 GiB temps on grok x prefill_32k).  The vmapped
        # qmatmul below lets XLA hoist.  The kernel backends bypass this
        # function (_expert_ffn below): on one device through the fused
        # per-expert sites, under a mesh through the shard_map EP path,
        # which decodes only the local expert slices.
        site_prec = ctx.resolve(path)
        return jax.vmap(
            lambda qt, xe: qmatmul(
                xe, qt, backend=ctx.backend,
                act_bits=site_prec.act_bits if site_prec else 8,
                act_exponent=ctx.act_exponent(path),
            )
        )(w, x)
    if ctx.mode == "qat" and prec is not None and prec.quantized:
        wq = jax.vmap(
            lambda we: ste.weights_ste(
                we.astype(jnp.float32), prec.w_bits, prec.group_size,
                prec.filter_size, prec.refit_scale, fmt=prec.fmt,
            )
        )(w).astype(x.dtype)
        xq = ste.act_ste(x.astype(jnp.float32), prec.act_bits).astype(x.dtype)
        return jnp.einsum("ecd,edf->ecf", xq, wq)
    return jnp.einsum("ecd,edf->ecf", x, w)


def _ep_cap_axes(mesh, c: int):
    """Data-parallel mesh axes the capacity axis can additionally shard over
    (only taken when C stays divisible; else capacity shards over EP alone
    and the buffer replicates across the data axes at the shard_map edge)."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    total = mesh.shape.get("model", 1)
    for a in axes:
        total *= mesh.shape[a]
    return axes if (axes and c % total == 0) else ()


def _use_ep(experts, e: int, c: int, ctx: QuantCtx) -> bool:
    """Route this chunk's expert FFN through the shard_map EP path?  Only
    for PTQ (QTensor weights) under the "pallas_ep" backend with a mesh
    installed whose expert/capacity axes divide the (E, C) buffer."""
    mesh = sharding._ACT_MESH[0]
    return (
        isinstance(experts["gate"]["w"], QTensor)
        and resolve_backend(ctx.backend) == "pallas_ep"
        and mesh is not None
        and ep_divisible(e, c, mesh, "model", _ep_cap_axes(mesh, c))
    )


def _expert_ffn(experts, xb: jax.Array, path: str, ctx: QuantCtx, buf_axes):
    """gate/up/down over the dispatched (E, C, d) buffer.

    PTQ under the "pallas_ep" backend with an installed mesh runs the whole
    FFN as ONE shard_map over the expert ('model') axis: dispatch/combine
    all-to-alls inside the body, fused qdense on the local expert slices
    (gate silu in the kernel epilogue).  PTQ on a kernel backend with no
    mesh runs the same body on one device, without the all-to-alls.  Every
    other mode composes the three ``_expert_matmul`` sites, so both have a
    bit-identical oracle (the ``ref`` backend)."""
    mesh = sharding._ACT_MESH[0]
    # (no observer handling below: calibration always runs on float params,
    # so the QTensor guard keeps the observing pass on the oracle path)
    def site_kw(name):
        site = f"{path}/experts/{name}"
        prec = ctx.resolve(site)
        return {
            "act_bits": prec.act_bits if prec else 8,
            "act_exponent": ctx.act_exponent(site),
            "fused": prec.fused if prec else True,
        }

    weights = lambda: {n: experts[n]["w"] for n in ("gate", "up", "down")}
    sites = lambda: {n: site_kw(n) for n in ("gate", "up", "down")}
    if _use_ep(experts, xb.shape[0], xb.shape[1], ctx):
        return expert_ffn_ep(
            weights(), xb, mesh=mesh, ep_axis="model",
            cap_axes=_ep_cap_axes(mesh, xb.shape[1]),
            backend=ctx.backend, site_kwargs=sites(),
        )
    if (
        isinstance(experts["gate"]["w"], QTensor)
        and resolve_backend(ctx.backend) in ("pallas", "pallas_ep")
        and sharding.kernels_routable()
    ):
        # one device: the EP body's fused sites, without the all-to-alls
        return expert_ffn_local(weights(), xb, backend=ctx.backend,
                                site_kwargs=sites())
    em = lambda name, val: _expert_matmul(
        experts[name]["w"], val, f"{path}/experts/{name}", ctx,
        prec=experts[name].get("_prec"), buf_axes=buf_axes,
    )
    h = jax.nn.silu(em("gate", xb))
    h = h * em("up", xb)
    return em("down", h)


def capacity(n_tokens: int, top_k: int, n_experts: int, factor: float) -> int:
    c = int(math.ceil(n_tokens * top_k * factor / n_experts))
    return max(8, -(-c // 8) * 8)  # round up to 8 for tiling


def _dispatch_chunk(p, experts, xt: jax.Array, path: str, cfg, ctx: QuantCtx, buf_axes):
    """Route one chunk of tokens (tc, d) through the (pre-quantized) experts."""
    with jax.named_scope("moe_dispatch"):
        tc, d = xt.shape
        e, k = cfg.n_experts, cfg.top_k
        c = capacity(tc, k, e, cfg.capacity_factor)

        logits = dense(p["router"], xt, f"{path}/router", ctx).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)  # (tc, E)
        top_vals, top_ids = jax.lax.top_k(probs, k)
        top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)

        flat_ids = top_ids.reshape(-1)  # (tc*k,)
        flat_gate = top_vals.reshape(-1)
        flat_src = jnp.arange(tc * k, dtype=jnp.int32) // k
        order = jnp.argsort(flat_ids)
        sorted_ids = flat_ids[order]
        sorted_src = flat_src[order]
        rank = jnp.arange(tc * k, dtype=jnp.int32) - jnp.searchsorted(
            sorted_ids, sorted_ids, side="left"
        ).astype(jnp.int32)
        keep = rank < c
        # out-of-bounds scatter indices are dropped by XLA => capacity overflow
        dest = jnp.where(keep, sorted_ids * c + rank, e * c)

        buf = jnp.zeros((e * c, d), xt.dtype).at[dest].set(
            xt[sorted_src], mode="drop"
        )
        use_ep = _use_ep(experts, e, c, ctx)
        xb = buf.reshape(e, c, d)
        if not use_ep:  # EP: shard_map's capacity-sharded in_spec IS the layout
            xb = sharding.constrain(xb, buf_axes)

        yb = _expert_ffn(experts, xb, path, ctx, buf_axes)
        # combine in the model dtype: the gather/scatter-add below crosses the
        # expert->token sharding boundary, so its collectives move these bytes
        # (f32 here doubled the MoE collective term -- Perf iteration B4)
        yb = yb.astype(xt.dtype)
        if not use_ep:  # EP: the combine all-to-all already ran inside shard_map
            yb = sharding.constrain(yb, buf_axes)

        vals = yb.reshape(e * c, d).at[dest].get(
            mode="fill", fill_value=0
        ) * flat_gate[order][:, None].astype(xt.dtype)
        out = jnp.zeros((tc, d), xt.dtype).at[sorted_src].add(vals)
        return sharding.constrain(out, ("batch", None))


def moe_layer(p, x: jax.Array, path: str, cfg, ctx: QuantCtx) -> jax.Array:
    """Chunked MoE: the token stream is processed in bounded-size chunks via
    lax.scan so dispatch buffers stay O(chunk) instead of O(global batch) --
    capacity is enforced per chunk (finer-grained drops, standard under
    microbatching).  EP shards experts over 'model' when divisible; archs
    with fewer experts than the TP width (grok: 8e on 16-way) fall back to
    capacity-over-data + FFN-over-model sharding."""
    b, s, d = x.shape
    t = b * s
    e = cfg.n_experts
    buf_axes = ("expert", None, None)
    mesh = sharding._ACT_MESH[0]
    if mesh is not None and "model" in mesh.shape and e % mesh.shape["model"]:
        buf_axes = (None, "batch", None)
    experts = _quantize_expert_weights(p["experts"], ctx, path)

    # Chunk along the SEQUENCE axis: (B, sc, d) chunks keep the batch axis
    # sharded, so slicing/stacking never reshards the token stream.  (A flat
    # (T,)-axis chunking interleaves the sharded token axis and XLA inserts
    # a full all-gather of the stacked outputs -- 24 GiB/step on the
    # grok x prefill_32k cell; see EXPERIMENTS.md Perf iteration B1.)
    target = getattr(cfg, "moe_chunk_tokens", 8192)
    n_chunks = max(1, t // max(target, 1))
    if FLAT_CHUNKING[0]:  # pre-B1 baseline: flat (T,)-axis chunking
        while t % n_chunks:
            n_chunks -= 1
        xt = sharding.constrain(x.reshape(t, d), ("batch", None))
        if n_chunks == 1:
            out = _dispatch_chunk(p, experts, xt, path, cfg, ctx, buf_axes)
        else:
            def fbody(carry, xc):
                yc = _dispatch_chunk(p, experts, xc, path, cfg, ctx, buf_axes)
                return carry, yc
            _, out = jax.lax.scan(
                jax.checkpoint(fbody), 0.0, xt.reshape(n_chunks, t // n_chunks, d)
            )
        out = sharding.constrain(
            out.reshape(b, s, d), ("batch", None, None)
        ).astype(x.dtype)
        if "residual_mlp" in p:
            out = out + layers.mlp(p["residual_mlp"], x, f"{path}/residual_mlp", ctx)
        return out
    while s % n_chunks:
        n_chunks -= 1
    sc = s // n_chunks

    if n_chunks == 1:
        xt = sharding.constrain(x.reshape(t, d), ("batch", None))
        out = _dispatch_chunk(p, experts, xt, path, cfg, ctx, buf_axes).reshape(b, s, d)
    else:
        def body(carry, xc):  # xc: (B, sc, d)
            xc = sharding.constrain(xc.reshape(b * sc, d), ("batch", None))
            yc = _dispatch_chunk(p, experts, xc, path, cfg, ctx, buf_axes)
            return carry, yc.reshape(b, sc, d)

        xcs = jnp.moveaxis(x.reshape(b, n_chunks, sc, d), 1, 0)
        _, out = jax.lax.scan(jax.checkpoint(body), 0.0, xcs)
        out = jnp.moveaxis(out, 0, 1).reshape(b, s, d)

    out = sharding.constrain(out, ("batch", None, None)).astype(x.dtype)
    if "residual_mlp" in p:  # arctic: dense MLP in parallel with the experts
        out = out + layers.mlp(p["residual_mlp"], x, f"{path}/residual_mlp", ctx)
    return out


def aux_load_balance_loss(logits: jax.Array, top_ids: jax.Array, n_experts: int):
    """Switch-style auxiliary loss (exposed for the trainer)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
    me = probs.mean(0)
    ce = jnp.bincount(top_ids.reshape(-1), length=n_experts) / top_ids.size
    return n_experts * jnp.sum(me * ce)

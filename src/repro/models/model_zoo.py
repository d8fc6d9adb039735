"""Unified model API: family dispatch, input specs, PTQ conversion.

``build_model(cfg)`` returns a ``ModelApi`` whose members all have fixed
signatures so the trainer / server / dry-run treat every family uniformly:

  init(key) -> params
  train_loss(params, batch) -> scalar
  forward(params, batch) -> logits
  init_cache(batch, max_len) -> cache            (decode state)
  prefill(params, batch, cache) -> (logits, cache)
  decode(params, token, pos, cache) -> (logits, cache)
  input_specs(shape_cfg) -> (batch/spec pytree, kind)

Staged-serving members (the prefill / insert / generate engine split):

  prefill_chunk(params, tokens, start, cache) -> (logits, cache)
      consume one (B, S) chunk of prompt tokens at cache positions
      [start, start+S), attending over the whole cache so earlier chunks
      stay visible; None for families whose decode state cannot replay a
      chunk in one graph (ssm/hybrid/encdec -- the staged engine falls
      back to budgeted per-token decode prefill there).
  insert(cache, prefix, slot) -> cache
      write a B=1 prefix cache (a finished prefill) into slot ``slot`` of
      a B=n_slots decode cache -- every leaf's batch row is overwritten,
      so stale state from the slot's previous occupant cannot leak.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig, config_from_dict, config_to_dict
from repro.core.policy import PrecisionPolicy
from repro.models import encdec, hybrid, ssm_lm, transformer, vlm
from repro.quant import api as quant_api
from repro.quant.plan import QuantCtx, QuantPlan


@dataclasses.dataclass
class ModelApi:
    cfg: ArchConfig
    ctx: QuantCtx
    init: Callable
    train_loss: Callable
    forward: Callable
    init_cache: Callable
    prefill: Optional[Callable]
    decode: Callable
    # staged serving: chunked prompt consumption + per-slot cache insertion
    prefill_chunk: Optional[Callable] = None
    insert: Optional[Callable] = None

    def with_ctx(self, ctx: QuantCtx) -> "ModelApi":
        """Rebind every member to a new quantization context."""
        return build_model(self.cfg, ctx)

    def with_plan(self, plan: QuantPlan) -> "ModelApi":
        """View of this model driven by a compiled QuantPlan."""
        return self.with_ctx(QuantCtx.for_plan(plan))

    def compiled(self, params) -> "ModelApi":
        """Compile this api's policy against ``params`` (kills per-call regex
        resolution in dense(); a no-op view for fp contexts)."""
        if self.ctx.policy is None:
            return self
        plan = self.ctx.policy.compile(
            params, mode=self.ctx.mode, backend=self.ctx.backend
        )
        return self.with_plan(plan)


def make_ctx(cfg: ArchConfig) -> QuantCtx:
    """Deprecated alias: use ``repro.quant.QuantCtx.from_config(cfg.quant)``."""
    return QuantCtx.from_config(cfg.quant)


def _insert_leaf(buf, pre, slot: Any, axis: int):
    return jax.lax.dynamic_update_slice_in_dim(
        buf, pre.astype(buf.dtype), slot, axis=axis
    )


def insert_prefix(cache, prefix, slot, batch_axis_overrides: Optional[Dict[str, int]] = None):
    """Write a B=1 ``prefix`` cache into batch row ``slot`` of ``cache``.

    Every model family stacks its decode state as (layers, B, ...), so the
    batch axis is 1 for every leaf; ``batch_axis_overrides`` names top-level
    leaves that deviate (encdec's (B, T, d) ``enc_out`` is axis 0).  ``slot``
    may be traced -- one compile covers every slot.
    """
    over = batch_axis_overrides or {}
    if not over:
        return jax.tree.map(lambda b, p: _insert_leaf(b, p, slot, 1), cache, prefix)
    out = {}
    for name, leaf in cache.items():
        ax = over.get(name, 1)
        out[name] = jax.tree.map(
            lambda b, p, a=ax: _insert_leaf(b, p, slot, a), leaf, prefix[name]
        )
    return out


def build_model(cfg: ArchConfig, ctx: Optional[QuantCtx] = None) -> ModelApi:
    ctx = ctx or QuantCtx.from_config(cfg.quant)
    fam = cfg.family
    if fam in ("dense", "moe"):
        return ModelApi(
            cfg, ctx,
            init=lambda key: transformer.init_lm(key, cfg),
            train_loss=lambda p, b: transformer.loss_fn(p, b, cfg, ctx),
            forward=lambda p, b: transformer.forward(p, b["tokens"], cfg, ctx),
            init_cache=lambda b, m: transformer.init_cache(cfg, b, m),
            prefill=lambda p, b, c: transformer.prefill(p, b["tokens"], cfg, ctx, c),
            decode=lambda p, t, pos, c: transformer.decode_step(p, t, pos, cfg, ctx, c),
            prefill_chunk=lambda p, t, start, c: transformer.prefill_chunk(
                p, t, start, cfg, ctx, c
            ),
            insert=insert_prefix,
        )
    if fam == "vlm":
        return ModelApi(
            cfg, ctx,
            init=lambda key: transformer.init_lm(key, cfg),
            train_loss=lambda p, b: vlm.loss_fn(p, b, cfg, ctx),
            forward=lambda p, b: vlm.forward(p, b, cfg, ctx),
            init_cache=lambda b, m: transformer.init_cache(cfg, b, m),
            prefill=lambda p, b, c: vlm.prefill(p, b, cfg, ctx, c),
            decode=lambda p, t, pos, c: transformer.decode_step(p, t, pos, cfg, ctx, c),
            prefill_chunk=lambda p, t, start, c: transformer.prefill_chunk(
                p, t, start, cfg, ctx, c
            ),
            insert=insert_prefix,
        )
    if fam == "hybrid":
        return ModelApi(
            cfg, ctx,
            init=lambda key: hybrid.init_hybrid(key, cfg),
            train_loss=lambda p, b: hybrid.loss_fn(p, b, cfg, ctx),
            forward=lambda p, b: hybrid.forward(p, b["tokens"], cfg, ctx),
            init_cache=lambda b, m: hybrid.init_cache(cfg, b, m),
            prefill=None,  # hybrid prefill == forward + state replay (engine-level)
            decode=lambda p, t, pos, c: hybrid.decode_step(p, t, pos, cfg, ctx, c),
            insert=insert_prefix,  # ssm states + per-superblock KV: all (L, B, ...)
        )
    if fam == "ssm":
        return ModelApi(
            cfg, ctx,
            init=lambda key: ssm_lm.init_ssm_lm(key, cfg),
            train_loss=lambda p, b: ssm_lm.loss_fn(p, b, cfg, ctx),
            forward=lambda p, b: ssm_lm.forward(p, b["tokens"], cfg, ctx),
            init_cache=lambda b, m: ssm_lm.init_cache(cfg, b, m),
            prefill=None,
            decode=lambda p, t, pos, c: ssm_lm.decode_step(p, t, pos, cfg, ctx, c),
            insert=insert_prefix,
        )
    if fam == "encdec":
        return ModelApi(
            cfg, ctx,
            init=lambda key: encdec.init_encdec(key, cfg),
            train_loss=lambda p, b: encdec.loss_fn(p, b, cfg, ctx),
            forward=lambda p, b: encdec.forward(p, b, cfg, ctx),
            init_cache=lambda b, m: encdec.init_cache(cfg, b, m),
            prefill=lambda p, b, c: encdec.prefill(p, b, cfg, ctx, c),
            decode=lambda p, t, pos, c: encdec.decode_step(p, t, pos, cfg, ctx, c),
            insert=lambda c, pre, s: insert_prefix(
                c, pre, s, batch_axis_overrides={"enc_out": 0}
            ),
        )
    raise ValueError(fam)


# ---------------------------------------------------------------------------
# Input specs: one cell = (arch x shape); used by smoke tests (concrete) and
# the dry-run (ShapeDtypeStruct, no allocation).
# ---------------------------------------------------------------------------
def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Tuple[Dict[str, Any], str]:
    """Returns ({name: ShapeDtypeStruct}, kind). Token count semantics:
    train/prefill feed (B, S); decode feeds one token with an S-long cache."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f = jnp.dtype(cfg.dtype)
    if shape.kind == "train":
        if cfg.family == "encdec":
            return (
                {
                    "frames": jax.ShapeDtypeStruct((b, cfg.n_audio_frames, cfg.d_model), f),
                    "tokens": jax.ShapeDtypeStruct((b, s), i32),
                    "labels": jax.ShapeDtypeStruct((b, s), i32),
                },
                "train",
            )
        if cfg.family == "vlm":
            nv = cfg.n_frontend_tokens
            return (
                {
                    "tokens": jax.ShapeDtypeStruct((b, s - nv), i32),
                    "labels": jax.ShapeDtypeStruct((b, s - nv), i32),
                    "vision_embeds": jax.ShapeDtypeStruct((b, nv, cfg.d_model), f),
                    "positions": jax.ShapeDtypeStruct((3, b, s), i32),
                },
                "train",
            )
        return (
            {
                "tokens": jax.ShapeDtypeStruct((b, s), i32),
                "labels": jax.ShapeDtypeStruct((b, s), i32),
            },
            "train",
        )
    if shape.kind == "prefill":
        if cfg.family == "encdec":
            return (
                {
                    "frames": jax.ShapeDtypeStruct((b, cfg.n_audio_frames, cfg.d_model), f),
                    "tokens": jax.ShapeDtypeStruct((b, s), i32),
                },
                "prefill",
            )
        if cfg.family == "vlm":
            nv = cfg.n_frontend_tokens
            return (
                {
                    "tokens": jax.ShapeDtypeStruct((b, s - nv), i32),
                    "vision_embeds": jax.ShapeDtypeStruct((b, nv, cfg.d_model), f),
                    "positions": jax.ShapeDtypeStruct((3, b, s), i32),
                },
                "prefill",
            )
        return ({"tokens": jax.ShapeDtypeStruct((b, s), i32)}, "prefill")
    # decode: one new token against an S-long cache
    return ({"token": jax.ShapeDtypeStruct((b, 1), i32)}, "decode")


def make_smoke_batch(key, cfg: ArchConfig, batch: int, seq: int) -> Dict[str, Any]:
    """Concrete small training batch for CPU smoke tests."""
    kt, kv = jax.random.split(key)
    f = jnp.dtype(cfg.dtype)
    out: Dict[str, Any] = {
        "tokens": jax.random.randint(kt, (batch, seq), 0, cfg.vocab, jnp.int32),
        "labels": jax.random.randint(kv, (batch, seq), 0, cfg.vocab, jnp.int32),
    }
    if cfg.family == "encdec":
        out["frames"] = (
            jax.random.normal(kv, (batch, cfg.n_audio_frames, cfg.d_model)) * 0.1
        ).astype(f)
    if cfg.family == "vlm":
        nv = cfg.n_frontend_tokens
        out["vision_embeds"] = (
            jax.random.normal(kv, (batch, nv, cfg.d_model)) * 0.1
        ).astype(f)
        out["positions"] = vlm.build_mrope_positions(batch, nv, seq)
        out["labels"] = out["labels"]
    return out


# ---------------------------------------------------------------------------
# PTQ: convert trained params to QTensor weights per a compiled QuantPlan.
# ---------------------------------------------------------------------------
def quantize_model_params(params, policy: PrecisionPolicy):
    """Deprecated alias for ``repro.quant.quantize_model`` (plan discarded).

    Prefer ``quantize_and_plan`` (or ``repro.quant.quantize_model`` directly)
    so the compiled, serializable plan travels with the quantized params.
    """
    qparams, _ = quant_api.quantize_model(params, policy)
    return qparams


def quantize_and_plan(
    api: ModelApi, params, calib_batches=None
) -> Tuple[Any, QuantPlan, ModelApi]:
    """One-call PTQ for a zoo model: returns (qparams, plan, plan-bound api).

    With ``calib_batches`` (iterable of forward-compatible batches), a
    full-precision observing pass profiles per-site activation ranges and
    the plan carries static DFP exponents (paper's profiled mode); without,
    PTQ inference uses dynamic per-row exponents everywhere.
    """
    qc = api.cfg.quant
    qparams, plan = quant_api.quantize_model(
        params,
        api.ctx.policy,
        mode="ptq",
        backend=qc.backend,
        calib_batches=calib_batches,
        forward=lambda p, b, ctx: api.with_ctx(ctx).forward(p, b),
        act_bits=qc.act_bits,
    )
    return qparams, plan, api.with_plan(plan)


def init_and_quantize(
    api: ModelApi, key, calib_batches=None
) -> Tuple[Any, QuantPlan, ModelApi, int]:
    """Quantize-on-boot from random float weights: (qparams, plan, api,
    float bytes).

    The float model is initialized on the device in one compiled program,
    then parked in host memory while ``quantize_and_plan`` moves one
    projection leaf at a time to the device.  The boot's device peak is
    the quantized model plus one leaf's working set -- not the float model
    on top of them, which at published widths (Phi-4-mini: 8.9 GB in bf16)
    leaves a 16 GB chip too little room for the quantizer's temporaries."""
    params = jax.device_get(jax.jit(api.init)(key))
    float_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    qparams, plan, qapi = quantize_and_plan(
        api, params, calib_batches=calib_batches
    )
    del params
    return jax.device_put(qparams), plan, qapi, float_bytes


# ---------------------------------------------------------------------------
# Quantized artifacts: quantize once, cold-start serving many times.
# ---------------------------------------------------------------------------
def save_servable(
    artifact_dir: str, api: ModelApi, qparams, plan: QuantPlan, mesh=None
) -> str:
    """Persist (qparams, plan) as a self-contained serving artifact.

    The serialized ArchConfig travels in the manifest, so ``load_servable``
    needs nothing but the directory.  With ``mesh`` the payloads write
    per-host sharded (``payload.shard{k}``; see the checkpoint module
    docstring for the layout)."""
    return quant_api.save_artifact(
        artifact_dir, qparams, plan,
        extra={"arch_config": config_to_dict(api.cfg)},
        mesh=mesh,
    )


def load_servable(
    artifact_dir: str, mesh=None
) -> Tuple[ModelApi, Any, "quant_api.Artifact"]:
    """Cold-start a zoo model from a packed artifact: (api, qparams, artifact).

    No fp32 weights are materialized and no calibration runs -- the QTensor
    tree loads packed, the plan (calibrated activation exponents included)
    comes from the manifest, and the model is rebuilt from the artifact's
    own serialized ArchConfig and bound to the plan.  With ``mesh`` every
    payload assembles straight onto its owning devices (per-host shard
    files via ``jax.make_array_from_single_device_arrays``); the global
    packed tree never exists on one host."""
    art = quant_api.load_artifact(artifact_dir, mesh=mesh)
    cfg_dict = art.extra.get("arch_config")
    if cfg_dict is None:
        raise ValueError(
            f"artifact at {artifact_dir!r} carries no 'arch_config' metadata; "
            "save it with repro.models.save_servable (or pass extra="
            "{'arch_config': config_to_dict(cfg)} to save_artifact)"
        )
    cfg = config_from_dict(cfg_dict)
    api = build_model(cfg)
    if art.plan is not None:
        api = api.with_plan(art.plan)
    return api, art.params, art

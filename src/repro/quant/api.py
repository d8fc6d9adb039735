"""Calibration-aware PTQ entry points over compiled precision plans.

``quantize_model(params, policy, calib_batches=...)`` is the one call every
consumer (server, dry-run, examples, benchmarks) makes to go from trained
float params to a servable quantized model:

  1. compile the policy against the param tree -> ``QuantPlan``,
  2. replace projection ``w`` leaves with QTensors per the plan
     (``quantize_params``),
  3. optionally run calibration batches through an observing forward pass,
     profile per-site activation ranges, and thread the finalized shared
     exponents into the plan (the paper's profiled static-DFP activation
     mode; un-profiled sites keep dynamic per-row exponents).

The observer uses ``jax.debug.callback`` so it records real runtime values
even when sites live inside ``lax.scan`` block loops (stacked layers share
one site path, hence one exponent -- consistent with the plan table).

``save_artifact`` / ``load_artifact`` make the quantized model a first-class
on-disk artifact: the QTensor tree persists packed (payload + scale table +
format tag, sha256 per payload) alongside the compiled plan with its
calibrated exponents -- quantize once, then cold-start any number of serving
processes from the 4-16x-smaller artifact with fp32 weights never touched.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import dfp
from repro.core.policy import PrecisionPolicy
from repro.quant.formats import quantize_weights
from repro.quant.plan import (
    QuantCtx,
    QuantPlan,
    compile_policy,
    is_projection_site,
    site_subpath,
)
from repro.quant.qtensor import TERNARY_PER_WORD


def _record(store, site: str, max_abs: float, msq: float) -> None:
    """Accumulate one batch's stats into any {site: entry} mapping."""
    e = store.get(site)
    if e is None:
        store[site] = {"max_abs": max_abs, "msq": msq, "count": 1.0}
    else:
        e["max_abs"] = max(e["max_abs"], max_abs)
        e["msq"] += msq
        e["count"] += 1.0


class Observer(dict):
    """Host-side activation-range store: {site: {"max_abs", "msq", "count"}}.

    Populated by ``observe_site`` callbacks during a calibration forward;
    ``exponents()`` finalizes ``max_abs`` into shared 8-bit DFP exponents.
    ``msq``/``count`` mirror ``core.calibration.ObserverState`` so the same
    pass can drive the BN-recompute analogue (``recalibrate_gamma`` needs
    the per-site second moment).
    """

    def record(self, site: str, max_abs: float, msq: float) -> None:
        _record(self, site, max_abs, msq)

    def exponents(self, bits: int = 8, bits_for=None) -> Dict[str, int]:
        """Finalize ranges into shared DFP exponents.  ``bits_for(site)``
        overrides the mantissa width per site (must match the act_bits the
        consumer quantizes with, or the exponent mis-scales)."""
        return {
            site: int(
                dfp.choose_exponent(
                    jnp.float32(e["max_abs"]),
                    bits_for(site) if bits_for is not None else bits,
                )
            )
            for site, e in self.items()
        }


def observe_site(store, site: str, x: jax.Array) -> None:
    """Record one activation batch at ``site`` into a mutable host store.

    Runs via jax.debug.callback so it works identically in eager, jit and
    lax.scan contexts (max/mean accumulation is order-independent).
    """
    xf = x.astype(jnp.float32)
    max_abs = jnp.max(jnp.abs(xf))
    msq = jnp.mean(jnp.square(xf))

    def cb(m, s, _store=store, _site=site):
        _record(_store, _site, float(m), float(s))

    jax.debug.callback(cb, max_abs, msq)


# ---------------------------------------------------------------------------
# Param-tree conversion.
# ---------------------------------------------------------------------------
def _quantizable(prec, kdim: int) -> bool:
    return (
        prec is not None
        and prec.quantized
        and prec.w_bits < 16
        and kdim % prec.group_size == 0
        and kdim % TERNARY_PER_WORD == 0
    )


def _quantize_leaf(w, scales, *, bits, group_size, filter_size, refit_scale,
                   fmt):
    """One projection leaf -> QTensor; stacked leading axes (layers,
    experts) quantize one matrix at a time.  ``lax.map`` bounds the working
    set to a single matrix's f32 temporaries, where vmap would hold them
    for the whole stack at once: several GB per leaf at published widths,
    more than a 16 GB chip holds beside the float model."""
    kw = dict(bits=bits, group_size=group_size, filter_size=filter_size,
              refit_scale=refit_scale, fmt=fmt)
    if w.ndim > 2:
        if scales is None:
            return jax.lax.map(lambda m: _quantize_leaf(m, None, **kw), w)
        return jax.lax.map(lambda a: _quantize_leaf(*a, **kw), (w, scales))
    return quantize_weights(
        w.astype(jnp.float32), bits, group_size, filter_size, refit_scale,
        fmt=fmt, scales=None if scales is None else scales.astype(jnp.float32),
    )


_quantize_stack = jax.jit(
    _quantize_leaf,
    static_argnames=("bits", "group_size", "filter_size", "refit_scale", "fmt"),
)


@jax.jit
def _snap_table(table):
    """Embedding table snapped to the per-row 8-bit DFP grid, in its own
    storage dtype (one fused pass: no f32 copy of the table survives)."""
    from repro.core import calibration

    return calibration.fake_quantize_act(
        table.astype(jnp.float32), 8, per_row=True
    ).astype(table.dtype)


def quantize_params(params, plan: QuantPlan):
    """Walk the param tree; replace projection 'w' leaves with QTensors.

    Stacked leading axes (layers and/or experts) are vmapped over.  The
    embedding table (a gather, not a GEMM) is snapped to the 8-bit DFP grid
    in place (values quantized, storage dtype unchanged).  Precision comes
    from the compiled plan table -- no per-leaf regex resolution.

    Sites carrying trained quantization state (``repro.quant.state``) are
    quantized on their *learned* grid: a ``ttq_scales`` leaf supplies the
    trained Wp/Wn magnitudes and an ``inq_scales`` leaf the last INQ event's
    scale table, threaded into ``quantize_weights(scales=...)`` so the
    artifact is never re-fit from the master weights.  State leaves are
    consumed here -- the output tree holds only servable parameters.
    """
    from repro.quant.state import STATE_KEYS

    def quant_w(w, prec, scales=None):
        return _quantize_stack(
            w, scales, bits=prec.w_bits, group_size=prec.group_size,
            filter_size=prec.filter_size, refit_scale=prec.refit_scale,
            fmt=prec.fmt,
        )

    def walk(node, path):
        if isinstance(node, dict):
            out = {}
            for key, val in node.items():
                sub = site_subpath(path, key)
                if is_projection_site(key, val):
                    prec = plan.resolve(path)
                    if _quantizable(prec, val.shape[-2]):
                        if prec.fmt == "ttq" and "ttq_scales" in node:
                            sc = node["ttq_scales"]
                        else:
                            # |s|: the trained grid is a magnitude (the STE
                            # chains gradients through sign, training may
                            # cross zero) -- same fold as ste.inq_ste
                            sc = node.get("inq_scales")
                            sc = None if sc is None else jnp.abs(sc)
                        out[key] = quant_w(val, prec, scales=sc)
                    else:
                        out[key] = val
                elif key in STATE_KEYS:
                    continue  # consumed above; not a servable parameter
                elif key == "table" and hasattr(val, "ndim"):
                    out[key] = _snap_table(val)
                else:
                    out[key] = walk(val, sub)
            return out
        return node

    return walk(params, "")


# ---------------------------------------------------------------------------
# The one-call PTQ entry point.
# ---------------------------------------------------------------------------
def quantize_model(
    params,
    policy: PrecisionPolicy,
    *,
    mode: str = "ptq",
    backend: str = "auto",
    calib_batches: Optional[Iterable[Any]] = None,
    forward: Optional[Callable[[Any, Any, QuantCtx], Any]] = None,
    act_bits: int = 8,
) -> Tuple[Any, QuantPlan]:
    """Convert float params to QTensors under a compiled plan.

    Returns ``(qparams, plan)``.  With ``calib_batches`` (any iterable of
    model inputs) and ``forward(params, batch, ctx)``, a full-precision
    observing pass profiles activation ranges at every projection site and
    the finalized static exponents ride in the plan; PTQ inference then uses
    static per-site DFP activations where profiled and dynamic per-row
    everywhere else.
    """
    if calib_batches is not None and forward is None:
        raise ValueError("calib_batches requires a forward(params, batch, ctx)")
    plan = compile_policy(policy, params, mode=mode, backend=backend)
    qparams = quantize_params(params, plan)
    if calib_batches is not None:
        obs = Observer()
        ctx = QuantCtx(mode="fp", policy=policy, observer=obs)
        for batch in calib_batches:
            forward(params, batch, ctx)
        # the observer records through jax.debug.callback: on async-dispatch
        # backends the callbacks may still be in flight here -- flush them
        # before finalizing, or the plan silently loses calibrated sites
        jax.effects_barrier()

        def bits_for(site):
            prec = plan.resolve(site)
            # must match the act_bits dense() quantizes this site with
            return prec.act_bits if prec is not None else act_bits

        plan = plan.with_act_exponents(obs.exponents(act_bits, bits_for))
    return qparams, plan


# ---------------------------------------------------------------------------
# Quantized artifacts: packed QTensor tree + plan as the unit of deployment.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Artifact:
    """One loaded quantized artifact: packed params + plan + metadata."""

    params: Any  # param tree with QTensor projection leaves (still packed)
    plan: Optional[QuantPlan]
    extra: Dict[str, Any]  # producer metadata (e.g. the serialized ArchConfig)
    step: int
    path: str  # the verified on-disk step directory


def save_artifact(
    artifact_dir: str,
    params: Any,
    plan: Optional[QuantPlan],
    *,
    extra: Optional[Dict[str, Any]] = None,
    step: int = 0,
    mesh: Any = None,
) -> str:
    """Persist a quantized model as a self-contained on-disk artifact.

    QTensor leaves serialize through the checkpoint codec layer as packed
    payload + scale table + format tag (sha256 per payload, step-atomic
    publish); the compiled plan -- calibrated activation exponents included
    -- rides in the manifest's ``quant_plan`` section.  ``extra`` is free
    producer metadata; pass the serialized ArchConfig
    (``dataclasses.asdict(cfg)`` under key ``"arch_config"``) so serving can
    cold-start without any out-of-band configuration.

    With ``mesh``, payloads write per-host sharded (``payload.shard{k}``,
    per-shard sha256) under the serving-mode sharding rules
    (``repro.parallel.qtensor_shardings``): each host persists only its
    addressable shards, and a mesh-aware ``load_artifact`` reassembles them
    device-by-device.
    """
    from repro.training import checkpoint as ckpt

    shardings = None
    if mesh is not None:
        from repro.parallel.sharding import qtensor_shardings

        shardings = qtensor_shardings(params, mesh, plan)
    meta = dict(extra or {})
    meta.setdefault("kind", "quant_artifact")
    return ckpt.save(
        artifact_dir, step, params, extra=meta, plan=plan, shardings=shardings
    )


def load_artifact(artifact_dir: str, *, mesh: Any = None) -> Artifact:
    """Load the newest intact artifact in ``artifact_dir``.

    Template-free: the param tree (QTensors still packed -- fp32 weights are
    never materialized) and the plan rebuild purely from the verified
    manifest.  Corrupt steps (including a truncated plan JSON) are skipped
    in favor of older intact ones; no intact step raises IOError.

    With ``mesh``, the serving shardings are computed against the
    manifest's abstract tree (``ckpt.tree_shapes``; no payload reads) and
    every payload assembles straight onto its owning devices -- sharded
    payloads via ``jax.make_array_from_single_device_arrays``, so neither
    the global fp32 NOR the global packed tree ever exists on one host.
    """
    from repro.training import checkpoint as ckpt

    # verify once (reads + sha256-hashes every payload), then thread the
    # verified manifest through -- a large artifact is hashed one time per
    # cold start, not once per helper
    step, manifest = ckpt.latest_intact(artifact_dir)
    if step is None:
        raise IOError(f"no intact quantized artifact under {artifact_dir!r}")
    d = ckpt.step_dir(artifact_dir, step)
    plan = ckpt.load_plan(d, manifest=manifest)
    shardings = None
    if mesh is not None:
        from repro.parallel.sharding import qtensor_shardings

        shardings = qtensor_shardings(ckpt.tree_shapes(manifest), mesh, plan)
    return Artifact(
        params=ckpt.restore_tree(d, manifest=manifest, shardings=shardings),
        plan=plan,
        extra=manifest.get("extra", {}),
        step=step,
        path=d,
    )

"""Sharding rules: path-pattern -> PartitionSpec over the production mesh.

Two regimes:

  * mode="train": 2-D FSDP x TP sharding.  Projection weights shard the
    contraction dim over 'data' (ZeRO-3 style, all-gathered per layer inside
    the layer scan, which XLA overlaps with the previous layer's compute)
    and the output dim over 'model' (Megatron pairing: qkv/up N-sharded,
    wo/down K-sharded so no resharding between the paired GEMMs).
    Optimizer state inherits the same specs.

  * mode="serve": pure TP over 'model'; weights replicated over 'data'
    (each data row serves independent requests => zero weight collectives
    per decode step, the right trade for a bandwidth-bound phase).  QTensor
    fields (packed mantissas + scale tables) shard exactly like the dense
    weight they replace; cluster scale tables never straddle shards because
    group_size divides the per-shard K.

Every axis assignment is divisibility-checked against the mesh, falling back
to replication (e.g. 8 KV heads on a 16-wide model axis -> replicated, as
Megatron does).  The MoE expert axis shards over 'model' when divisible
(expert parallelism), else experts stay replicated and the per-expert FFN
dims shard instead.

QTensor leaves are first-class: ``param_spec`` dispatches on the *logical*
(K, N) shape a QTensor carries -- not the packed payload shape, whose K dim
is divided by the words-per-uint32 packing factor (16 for ternary, 8 for
int4 and nf4, 1 for raw-int8 storage: int8 and mx) -- and
``qtensor_shardings`` expands the one logical decision into consistent
per-field specs: the packed payload inherits the weight spec (packing
preserves which dim is which), the scale table follows its cluster
(K/group) axis (mx: the 32-element block axis), and the shared exponent
replicates.  A K assignment is taken only when the mesh axis divides the
logical K *and* the packed K *and* the scale-table K -- otherwise the whole
QTensor falls back together, so payload and scales can never disagree about
their layout.  Everything is derived from the QTensor's own shapes, so a
newly registered format (nf4, mx) shards correctly with no rule changes.
"""
from __future__ import annotations

import contextlib
import re
from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.quantizer import QTensor

# projection name -> (contraction-dim role, output-dim role)
_N_SHARDED = ("wq", "wk", "wv", "up", "gate", "in_proj", "bc_proj", "dt_proj", "lm_head")
_K_SHARDED = ("wo", "down", "out_proj", "x_proj")


def _path_str(path) -> str:
    parts = []
    for e in path:
        if hasattr(e, "key"):
            parts.append(str(e.key))
        elif hasattr(e, "name"):
            parts.append(str(e.name))
        elif hasattr(e, "idx"):
            parts.append(str(e.idx))
    return "/".join(parts)


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1


def _fit(mesh: Mesh, dim: int, axis: Optional[str]) -> Optional[str]:
    """axis if it exists and divides dim, else None (replicate)."""
    if axis is None or axis not in mesh.shape:
        return None
    return axis if dim % mesh.shape[axis] == 0 else None


def _fit_all(mesh: Mesh, dims, axis: Optional[str]) -> Optional[str]:
    """axis if it divides EVERY dim in ``dims`` (a logical dim plus its packed
    and scale-table projections), else None -- the QTensor fields fall back to
    replication together rather than disagreeing about their layout."""
    if axis is None or axis not in mesh.shape:
        return None
    a = mesh.shape[axis]
    return axis if all(d % a == 0 for d in dims) else None


def _proj_spec(path: str, shape, mesh: Mesh, mode: str, k_dims=None) -> P:
    """Spec for a projection leaf ('w', 'packed' or 'scale_m'): the last two
    dims are (K-like, N); leading dims are layer/expert stacks.

    ``k_dims``: extra dims that must also divide for a K-axis assignment to
    hold (a QTensor's packed K/words and scale-table K/group rows)."""
    k_dim, n_dim = shape[-2], shape[-1]
    name_hit = lambda names: any(re.search(rf"(^|/){n}(/|$)", path) for n in names)
    if name_hit(_K_SHARDED):
        tp_on_k = True
    elif name_hit(_N_SHARDED):
        tp_on_k = False
    else:
        tp_on_k = False

    if mode == "serve":
        fsdp = None
    else:
        fsdp = "data"

    k_all = (k_dim,) + tuple(k_dims or ())
    if tp_on_k:
        k_ax = _fit_all(mesh, k_all, "model")
        n_ax = _fit(mesh, n_dim, fsdp)
    else:
        k_ax = _fit_all(mesh, k_all, fsdp)
        n_ax = _fit(mesh, n_dim, "model")

    lead: list = [None] * (len(shape) - 2)
    # expert stacks: shard the expert axis over 'model' when divisible (EP)
    if "experts" in path and len(shape) >= 3:
        e_dim = shape[-3]
        ep = _fit(mesh, e_dim, "model")
        if ep is not None:
            lead[-1] = ep
            # model axis consumed by EP -> drop TP on the inner dims
            if k_ax == "model":
                k_ax = None
            if n_ax == "model":
                n_ax = None
    return P(*lead, k_ax, n_ax)


def _vector_spec(path: str, shape, mesh: Mesh) -> P:
    """1-D-ish params (norm scales, biases, conv, A_log...): replicate."""
    return P(*([None] * len(shape)))


def _qt_logical_shape(qt: QTensor) -> Tuple[int, ...]:
    """Full logical shape of a (possibly stacked) QTensor: the packed
    payload's leading layer/expert stack dims + the logical (K, N)."""
    return tuple(qt.packed.shape[:-2]) + tuple(qt.shape)


def _qt_words_per_k(qt: QTensor) -> int:
    """K rows per packed payload row (16 ternary, 8 int4/nf4, 1 for raw
    int8 storage: int8 and mx) -- derived from the payload shape itself so
    registered formats need no table here."""
    return max(1, qt.k // qt.packed.shape[-2])


def qtensor_spec(path: str, qt: QTensor, mesh: Mesh, mode: str) -> P:
    """Logical-weight spec for a QTensor leaf.

    The decision runs on the shape the QTensor *represents* (stack dims +
    (K, N)), not the packed payload shape, with the extra constraint that a
    K-axis assignment must also divide the packed (K/words) and scale-table
    (K/group) projections of K -- int4 payloads halve K, ternary payloads
    divide it by 16, and the scale table divides it by group_size, so a
    divisibility check against any single field's shape is wrong for the
    other two."""
    shape = _qt_logical_shape(qt)
    k = qt.k
    k_dims = (k // _qt_words_per_k(qt), k // qt.group_size)
    return _proj_spec(path, shape, mesh, mode, k_dims=k_dims)


def qtensor_field_shardings(
    path: str, qt: QTensor, mesh: Mesh, mode: str
) -> QTensor:
    """Expand one logical QTensor spec into consistent per-field shardings.

    Returns a QTensor whose data fields hold NamedShardings (same static
    meta, so it is treedef-compatible with the value tree for device_put /
    jit in_shardings): the packed payload inherits the weight spec verbatim
    (packing preserves dim identity), the scale table follows its cluster
    (K/group) axis, and the shared exponent replicates."""
    spec = qtensor_spec(path, qt, mesh, mode)
    return QTensor(
        packed=NamedSharding(mesh, spec),
        scale_m=NamedSharding(mesh, spec),
        scale_e=NamedSharding(mesh, P()),
        bits=qt.bits, group_size=qt.group_size, shape=tuple(qt.shape),
        fmt=qt.fmt,
    )


def _is_qtensor(leaf) -> bool:
    return isinstance(leaf, QTensor)


def param_spec(path: str, leaf, mesh: Mesh, mode: str) -> P:
    if isinstance(leaf, QTensor):
        return qtensor_spec(path, leaf, mesh, mode)
    shape = leaf.shape
    if re.search(r"(^|/)(table)$", path):  # embedding (V, d): vocab over model
        v_ax = _fit(mesh, shape[0], "model")
        d_ax = _fit(mesh, shape[1], "data") if mode == "train" else None
        return P(v_ax, d_ax)
    if re.search(r"(^|/)(enc_pos|dec_pos)$", path):
        return P(None, None)
    if path.endswith("/w") or path.endswith("/packed") or path.endswith("/scale_m"):
        if len(shape) >= 2:
            return _proj_spec(path, shape, mesh, mode)
    if path.endswith("/scale_e") or leaf.ndim == 0:
        return P()
    return _vector_spec(path, shape, mesh)


def param_shardings(params_shapes: Any, mesh: Mesh, mode: str = "train"):
    """Pytree of NamedSharding matching ``params_shapes`` (from eval_shape).

    QTensor nodes are treated whole: the logical-shape decision is made once
    per site and expanded into per-field shardings, so the packed payload
    and its scale table always agree (flattening them into independent
    leaves let their divisibility checks diverge)."""

    def spec(path, leaf):
        p = _path_str(path)
        if isinstance(leaf, QTensor):
            return qtensor_field_shardings(p, leaf, mesh, mode)
        return NamedSharding(mesh, param_spec(p, leaf, mesh, mode))

    return jax.tree_util.tree_map_with_path(
        spec, params_shapes, is_leaf=_is_qtensor
    )


def qtensor_shardings(
    qparams: Any, mesh: Mesh, plan: Any = None, mode: str = "serve"
):
    """Shardings for a quantized (PTQ) param tree under ``mesh``.

    The serving-side face of ``param_shardings``: QTensor leaves get
    consistent payload/scale-table shardings from their logical shape, plain
    leaves follow the ordinary rules.  ``plan`` (a compiled QuantPlan) is
    accepted so callers can thread per-site layout overrides through one
    place; the built-in rules currently derive everything they need from the
    QTensor itself."""
    del plan  # reserved for per-site layout overrides
    return param_shardings(qparams, mesh, mode)


def opt_shardings(opt_shapes: Any, mesh: Mesh, mode: str = "train"):
    """Optimizer-state shardings: moments inherit the owning param's spec
    (ZeRO: m/v sharded exactly like the weight); per-row exponents drop the
    last axis; the step counter is replicated."""

    def spec(path, leaf):
        p = _path_str(path)
        if p == "step":
            return NamedSharding(mesh, P())
        # paths look like m/<param path>/q | m/<param path>/e | m/<param path>
        parts = p.split("/")
        core = "/".join(parts[1:])
        if core.endswith("/q"):
            base = param_spec(core[:-2], leaf, mesh, mode)
            return NamedSharding(mesh, base)
        if core.endswith("/e"):
            # exponent: same leading spec, last axis (size 1) replicated
            fake = jax.ShapeDtypeStruct(leaf.shape[:-1] + (1,), leaf.dtype)
            base = param_spec(core[:-2], fake, mesh, mode)
            return NamedSharding(mesh, P(*(list(base)[: leaf.ndim - 1] + [None])))
        return NamedSharding(mesh, param_spec(core, leaf, mesh, mode))

    return jax.tree_util.tree_map_with_path(spec, opt_shapes)


# ---------------------------------------------------------------------------
# Activation sharding constraints (perf lever; see EXPERIMENTS.md Sec. Perf)
# ---------------------------------------------------------------------------
# Model code is mesh-agnostic; launchers install the ambient mesh here and
# `constrain` becomes a with_sharding_constraint with divisibility checks.
# Logical axes: "batch" -> (pod, data);  "seq"/"feat"/"expert" -> model.
_ACT_MESH: list = [None]

# Perf iteration C4 toggle (see EXPERIMENTS.md): flash-decoding-style
# sequence sharding for GQA caches whose head count does not divide TP.
KV_SEQ_SHARD: list = [True]


def set_activation_mesh(mesh: Optional[Mesh]) -> None:
    _ACT_MESH[0] = mesh


def kernels_routable() -> bool:
    """May a Pallas kernel run here on whole per-device operands?

    XLA cannot partition a Mosaic kernel, so under a multi-device
    activation mesh a pallas_call runs only inside a shard_map body (traced
    under ``manual_region``, which lifts the mesh); everywhere else callers
    take their XLA path, which shards correctly."""
    mesh = _ACT_MESH[0]
    return mesh is None or mesh.size == 1


@contextlib.contextmanager
def manual_region():
    """Trace a shard_map body: its operands are per-device blocks, so the
    code inside it sees no activation mesh."""
    prev = _ACT_MESH[0]
    _ACT_MESH[0] = None
    try:
        yield
    finally:
        _ACT_MESH[0] = prev


def constrain(x, logical_axes) -> Any:
    """Apply a sharding constraint if an activation mesh is installed.

    logical_axes: tuple like ("batch", "seq", None); axes that do not divide
    the corresponding dim fall back to replicated.
    """
    mesh = _ACT_MESH[0]
    if mesh is None:
        return x
    names = []
    for dim, ax in zip(x.shape, logical_axes):
        if ax == "batch":
            cand = batch_axes(mesh)
            if cand is not None:
                total = 1
                for a in cand:
                    total *= mesh.shape[a]
                cand = cand if dim % total == 0 else None
            names.append(cand)
        elif ax in ("seq", "feat", "expert", "heads"):
            names.append(_fit(mesh, dim, "model"))
        else:
            names.append(None)
    names += [None] * (x.ndim - len(names))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*names)))


# ---------------------------------------------------------------------------
# Data / cache shardings
# ---------------------------------------------------------------------------
def batch_axes(mesh: Mesh):
    """Logical batch axis = all data-parallel mesh axes."""
    names = [n for n in ("pod", "data") if n in mesh.shape]
    return tuple(names) if names else None


def batch_shardings(batch_shapes: Any, mesh: Mesh):
    """Shard the leading (batch) axis of every input over pod+data."""
    baxes = batch_axes(mesh)

    def spec(path, leaf):
        p = _path_str(path)
        shape = leaf.shape
        if p.endswith("positions") and len(shape) == 3:  # (3, B, S)
            return NamedSharding(mesh, P(None, baxes, None))
        if len(shape) == 0:
            return NamedSharding(mesh, P())
        b_dim = shape[0]
        ax = baxes
        if ax is not None:
            total = 1
            for a in ax:
                total *= mesh.shape[a]
            if b_dim % total != 0:
                ax = None
        return NamedSharding(mesh, P(ax, *([None] * (len(shape) - 1))))

    return jax.tree_util.tree_map_with_path(spec, batch_shapes)


def cache_shardings(cache_shapes: Any, mesh: Mesh):
    """KV caches (L, B, S, Kh, hd) and SSM states (L, B, ...): batch over
    pod+data, kv-heads over model when divisible."""
    baxes = batch_axes(mesh)

    def divisible(dim):
        if baxes is None:
            return False
        total = 1
        for a in baxes:
            total *= mesh.shape[a]
        return dim % total == 0

    def spec(path, leaf):
        shape = leaf.shape
        p = _path_str(path)
        if p.endswith("enc_out") and len(shape) == 3:  # (B, T, d)
            return NamedSharding(mesh, P(baxes if divisible(shape[0]) else None, None, None))
        if (p.endswith("ke") or p.endswith("ve")) and len(shape) == 5:
            # exponent planes (L, B, S|S/32, Kh, 1): follow the mantissa
            # buffer on batch + kv-heads, keep seq replicated (tiny leaves;
            # kv_mx's S/32 seq axis rarely divides the data axes anyway)
            bax = baxes if divisible(shape[1]) else None
            kh = _fit(mesh, shape[3], "model")
            return NamedSharding(mesh, P(None, bax, None, kh, None))
        if len(shape) == 5:  # (L, B, S, Kh, hd)
            bax = baxes if divisible(shape[1]) else None
            # batch=1 long-context: shard the sequence over the data axes
            sax = None if bax else (baxes if divisible(shape[2]) else None)
            kh = _fit(mesh, shape[3], "model")
            # GQA caches whose kv-head count does not divide the TP width:
            # shard the SEQUENCE over 'model' (flash-decoding style: scores
            # and PV partials reduce across shards; the cache itself never
            # moves).  Sharding hd instead makes the partitioner all-gather
            # the converted f32 cache -- 1 GiB/step on qwen1.5 x decode_32k
            # (Perf iteration C4).
            s_model = None
            if KV_SEQ_SHARD[0] and kh is None and sax is None:
                s_model = _fit(mesh, shape[2], "model")
            hd = None if (kh or s_model) else _fit(mesh, shape[4], "model")
            return NamedSharding(mesh, P(None, bax, s_model or sax, kh, hd))
        if len(shape) >= 2:
            # stacked ssm states (L, B, ...): feature axis over model if possible
            bax = baxes if divisible(shape[1]) else None
            rest = [None] * (len(shape) - 2)
            if len(shape) >= 3:
                rest[0] = _fit(mesh, shape[2], "model")
            return NamedSharding(mesh, P(None, bax, *rest))
        return NamedSharding(mesh, P(*([None] * len(shape))))

    return jax.tree_util.tree_map_with_path(spec, cache_shapes)

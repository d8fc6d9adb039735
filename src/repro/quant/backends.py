"""Backend registry for the quantized matmul: pluggable execution strategies.

``qmatmul(x, qt)`` is the single entry point for PTQ inference.  It runs a
shared activation-quantization prologue (per-row dynamic DFP exponents, or a
calibrated static per-site exponent when the ``QuantPlan`` carries one) and
then dispatches to a registered backend strategy:

  * ``pallas``   : the real integer pipeline (TPU target; runs in interpret
                   mode on CPU so tests validate the exact kernel
                   semantics).  The kernel itself comes from the *format*
                   registry, so new weight encodings plug in here too.
  * ``xla``      : dequantize-weights -> bf16 dot.  Mathematically identical
                   up to f32 rounding; this is what the distributed (pjit)
                   graph lowers for the dry-run, where collectives/sharding
                   are the object of study.
  * ``xla_int8`` : integer pipeline without Pallas -- per-group batched int8
                   dots with int32 accumulation (2x int8 MXU path, 1 B/elem
                   weight stream).
  * ``ref``      : the pure-jnp oracle (bit-exact integer semantics).
  * ``pallas_ep``: pallas for plain dense sites; MoE expert sites
                   additionally route through ``expert_ffn_ep`` -- the whole
                   expert FFN wrapped in ``shard_map`` over the expert
                   ('model') mesh axis, with the dispatch/combine
                   all-to-alls inside the body, so each device decodes and
                   activation-quantizes only its local expert slices.

XLA cannot partition a Mosaic kernel, so under a multi-device activation
mesh the kernel backends run every dense site as one ``shard_map`` over the
weight's serve layout (``_qdense_sharded``); ``qmatmul`` there raises.
  * ``auto``     : resolves to pallas on TPU, xla otherwise.

Every strategy receives the already-quantized activations ``(xq, xe)`` plus
the QTensor, so registering a new backend is one function -- there is no
string-compare ladder to extend (that lived in ``kernels/ops.py`` before
this registry).

``qdense(x, qt, bias=..., act=...)`` is the whole-site entry point serving
uses: on backends with a registered *fused* strategy
(``register_fused_backend``; built-in: ``pallas``) the quantize prologue,
matmul, exponent scaling, bias and activation run as ONE pallas_call with no
intermediate HBM materialization -- the unfused three-pass composition
(quantize -> matmul -> scale/bias/act) remains the fallback and the ``ref``
backend stays the bit-exact oracle for both.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import dfp
from repro.core.quantizer import QTensor
from repro.kernels._common import activation_fn, interpret_mode, m_bucket, pick_block
from repro.kernels.quantize import quantize_rows
from repro.kernels.ref import qmatmul_ref, quantize_rows_ref
from repro.parallel import sharding as rules
from repro.parallel.sharding import kernels_routable, manual_region

# fn(xq int8 (M, K), xe int32 ((M,1) or scalar), qt, *, block_m, block_n,
#    block_k) -> f32 (M, N), exponents applied.
BackendFn = Callable[..., jax.Array]

_BACKENDS: Dict[str, BackendFn] = {}


def register_backend(name: str, fn: BackendFn, *, overwrite: bool = False) -> None:
    if name in _BACKENDS and not overwrite:
        raise ValueError(f"backend {name!r} already registered")
    _BACKENDS[name] = fn


def get_backend(name: str) -> BackendFn:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered: {sorted(_BACKENDS)}"
        ) from None


def backend_names() -> Tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


# strategies that launch Pallas kernels outside any shard_map
_KERNEL_BACKENDS = ("pallas", "pallas_ep")


def resolve_backend(name: str) -> str:
    """'auto' -> pallas on TPU, xla elsewhere; concrete names pass through.

    Off the TPU, Pallas would only run interpreted, so 'auto' takes the
    XLA float path there; 'pallas' still forces the interpreted kernels."""
    if name == "auto":
        return "xla" if interpret_mode() else "pallas"
    return name


def _needs_shard_map(name: str) -> bool:
    """Does a site on resolved backend ``name`` have to run its kernels
    inside a shard_map (a kernel backend under a multi-device mesh)?"""
    return name in _KERNEL_BACKENDS and not kernels_routable()


# ---------------------------------------------------------------------------
# Shared activation-quantization prologue (the ONE entry point: static or
# dynamic exponents, pallas or jnp -- formerly split across two near-duplicate
# functions, one of which never reached the Pallas kernel even on TPU).
# ---------------------------------------------------------------------------
def quantize_activations(
    x: jax.Array,
    bits: int = 8,
    use_pallas: Optional[bool] = None,
    *,
    exponent=None,
) -> Tuple[jax.Array, jax.Array]:
    """DFP-quantize activations -> (int8 mantissas, int32 exponent(s)).

    With ``exponent`` (a calibrated static per-site DFP exponent from a
    QuantPlan) the mantissas are computed directly against it -- no range
    scan.  Otherwise per-row dynamic exponents, through one of three
    explicit paths:
      * pallas on TPU        (use_pallas defaults to True on TPU),
      * pallas interpret mode (use_pallas=True off-TPU; exact but slow --
        used by tests to validate the kernel semantics),
      * the jnp reference    (use_pallas=False; default off-TPU, and for
        the XLA backends under a multi-device mesh, which XLA partitions).
    """
    if exponent is not None:
        e = jnp.asarray(exponent, jnp.int32)
        return dfp.quantize(x, e, bits), e
    interpret = interpret_mode()
    if use_pallas is None:
        use_pallas = not interpret and kernels_routable()
    if not use_pallas:
        return quantize_rows_ref(x, bits)
    return quantize_rows(x, bits=bits, interpret=interpret)


# ---------------------------------------------------------------------------
# Built-in backend strategies.
# ---------------------------------------------------------------------------
def _xla_backend(xq, xe, qt: QTensor, **_):
    # float-side equivalent: fake-quantized activations x dequant weights
    # (f32 dot output; a bf16-output variant was tried as Perf iteration
    # B3 and had NO effect on collective bytes -- the TP reductions in
    # the MoE cells come from the combine scatter-add, see moe.py B4)
    from repro.quant.formats import dequantize_weights

    xf = dfp.dequantize(xq, xe).astype(jnp.bfloat16)
    w = dequantize_weights(qt).astype(jnp.bfloat16)
    return jax.lax.dot_general(
        xf, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _xla_int8_backend(xq, xe, qt: QTensor, **_):
    # integer pipeline without Pallas: per-group batched int8 dots with
    # int32 accumulation; weights materialize as int8 codes (1 B/elem)
    # instead of a scaled bf16 copy (2 B/elem) -- halves the decode-phase
    # weight stream and uses the 2x int8 MXU path on TPU.
    from repro.quant.formats import decode_codes

    g = qt.group_size
    m = xq.shape[0]
    kg = qt.k // g
    xg = jnp.moveaxis(xq.reshape(m, kg, g), 1, 0)  # (Kg, M, G) int8
    wg = decode_codes(qt).reshape(kg, g, qt.n)  # (Kg, G, N) int8
    part = jax.lax.dot_general(
        xg, wg, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32,
    )  # (Kg, M, N) int32
    scaled = part.astype(jnp.float32) * qt.scale_m.astype(jnp.float32)[:, None, :]
    out = scaled.sum(axis=0)
    return out * dfp.exp2i(qt.scale_e + xe)


def _ref_backend(xq, xe, qt: QTensor, **_):
    return qmatmul_ref(xq, xe, qt)


def _pad_rows_to_bucket(x: jax.Array) -> Tuple[jax.Array, int]:
    """Pad ragged M up to a power-of-two bucket (>= 8).

    Every distinct (M, block) pair is a fresh kernel trace/compile; bucketing
    collapses the ragged serving batch sizes onto a handful of
    specializations (zero rows quantize to zero mantissas, so padded rows
    are inert)."""
    m = x.shape[0]
    pad = m_bucket(m) - m
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x, m


def _pallas_backend(xq, xe, qt: QTensor, *, block_m=128, block_n=128, block_k=512):
    from repro.quant.formats import format_of

    kernel = format_of(qt).kernel
    if kernel is None:
        raise ValueError(
            f"format for bits={qt.bits} has no Pallas kernel registered"
        )
    xq, m = _pad_rows_to_bucket(xq)
    out = kernel(
        xq, qt.packed, qt.scale_m,
        group=qt.group_size, block_m=pick_block(xq.shape[0], block_m),
        block_n=block_n, block_k=block_k, interpret=interpret_mode(),
    )
    out = out[:m]
    return out * dfp.exp2i(qt.scale_e + xe)


register_backend("xla", _xla_backend)
register_backend("xla_int8", _xla_int8_backend)
register_backend("ref", _ref_backend)
register_backend("pallas", _pallas_backend)
# Expert-parallel strategy: plain dense sites run the ordinary pallas path
# (the EP-ness only matters at MoE expert sites, which route through
# expert_ffn_ep below when a mesh is installed); registering it here makes
# "pallas_ep" a first-class backend name a QuantPlan can carry.
register_backend("pallas_ep", _pallas_backend)


# ---------------------------------------------------------------------------
# Fused whole-site strategies: take RAW activations and do prologue + matmul
# + epilogue in one kernel.  Backends without a fused entry fall back to the
# unfused composition inside qdense().
# ---------------------------------------------------------------------------
# fn(x f32/bf16 (M, K), qt, *, bias, act, act_bits, act_exponent, block_m,
#    block_n, block_k) -> f32 (M, N) finished output.
FusedFn = Callable[..., jax.Array]

_FUSED_BACKENDS: Dict[str, FusedFn] = {}


def register_fused_backend(name: str, fn: FusedFn, *, overwrite: bool = False) -> None:
    if name in _FUSED_BACKENDS and not overwrite:
        raise ValueError(f"fused backend {name!r} already registered")
    _FUSED_BACKENDS[name] = fn


def has_fused_backend(name: str) -> bool:
    return name in _FUSED_BACKENDS


def _pallas_fused(
    x, qt: QTensor, *, bias=None, act=None, act_bits=8, act_exponent=None,
    block_m=128, block_n=128, block_k=512,
):
    from repro.quant.formats import format_of

    kernel = format_of(qt).fused_kernel
    if kernel is None:
        raise ValueError(
            f"format {format_of(qt).name!r} has no fused Pallas kernel registered"
        )
    x, m = _pad_rows_to_bucket(x)
    out = kernel(
        x, qt.packed, qt.scale_m, qt.scale_e,
        group=qt.group_size, bias=bias, act=act, act_bits=act_bits,
        act_exponent=None if act_exponent is None else int(act_exponent),
        block_m=pick_block(x.shape[0], block_m), block_n=block_n,
        block_k=block_k, interpret=interpret_mode(),
    )
    return out[:m]


register_fused_backend("pallas", _pallas_fused)
register_fused_backend("pallas_ep", _pallas_fused)


# ---------------------------------------------------------------------------
# The public quantized matmul.
# ---------------------------------------------------------------------------
def qmatmul(
    x: jax.Array,
    qt: QTensor,
    *,
    backend: str = "auto",
    act_bits: int = 8,
    act_exponent=None,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
) -> jax.Array:
    """x [..., K] (float) x QTensor (K, N) -> [..., N] f32.

    Full integer pipeline: 8-bit DFP activations (per-row dynamic exponents,
    or the calibrated static ``act_exponent`` from a QuantPlan), sub-8-bit
    weights, int32 cluster accumulation, one scale multiply per cluster.
    """
    lead = x.shape[:-1]
    xm = x.reshape(-1, x.shape[-1])
    name = resolve_backend(backend)
    if _needs_shard_map(name):
        raise ValueError(
            f"qmatmul on the {name!r} backend under a multi-device mesh: "
            "XLA cannot partition its Pallas kernels.  Route the site "
            "through qdense(..., site=path) (a shard_map over the weight "
            "layout), serve MoE experts with 'pallas_ep' on an expert count "
            "the EP axis divides, or pick 'xla_int8'."
        )
    fn = get_backend(name)
    xq, xe = quantize_activations(xm, act_bits, exponent=act_exponent)
    out = fn(xq, xe, qt, block_m=block_m, block_n=block_n, block_k=block_k)
    return out.reshape(*lead, qt.n)


@functools.partial(jax.jit, static_argnames=("backend", "act_bits"))
def qmatmul_jit(x, qt, backend="auto", act_bits=8):
    return qmatmul(x, qt, backend=backend, act_bits=act_bits)


# ---------------------------------------------------------------------------
# The public quantized dense site (prologue + matmul + epilogue).
# ---------------------------------------------------------------------------
def apply_act(y: jax.Array, act: Optional[str]) -> jax.Array:
    return activation_fn(act)(y)  # same table as the fused kernel epilogue


def _fused_available(name: str, qt: QTensor) -> bool:
    """A fused strategy is usable only if the QTensor's format brought a
    fused kernel (register_format(..., fused_kernel=...)); formats without
    one -- including pre-existing third-party formats -- fall back to the
    unfused composition instead of raising."""
    if name not in _FUSED_BACKENDS:
        return False
    from repro.quant.formats import format_of

    return format_of(qt).fused_kernel is not None


# ---------------------------------------------------------------------------
# Expert-parallel fused FFN: shard_map over the expert ('model') axis.
# ---------------------------------------------------------------------------
def _qdense_stack(x, qt: QTensor, **kw):
    """qdense vmapped over a stacked (E_local, ...) expert axis: each local
    expert's site is one fused pallas_call over its local buffer slice."""
    return jax.vmap(lambda xe, qe: qdense(xe, qe, **kw), in_axes=(0, 0))(x, qt)


def expert_ffn_local(
    experts: Any,  # {"gate": QTensor (E, d, ff), "up": ..., "down": (E, ff, d)}
    x: jax.Array,  # (E, C, d) capacity buffer of the experts held here
    *,
    backend: str,
    site_kwargs: Optional[Dict[str, Dict[str, Any]]] = None,
) -> jax.Array:
    """The expert FFN over experts held whole on this device: the three
    projections as per-expert ``qdense`` sites, gate's silu in its kernel
    epilogue -- the body of ``expert_ffn_ep`` and the one-device path."""
    sites = site_kwargs or {}
    kw = lambda name: dict(backend=backend, **sites.get(name, {}))
    h = _qdense_stack(x, experts["gate"], act="silu", **kw("gate"))
    # h stays f32 into the down projection, exactly like the unfused
    # oracle composition -- casting to the model dtype here would break
    # bit parity with the single-device path on bf16 models
    h = h * _qdense_stack(x, experts["up"], **kw("up"))
    return _qdense_stack(h, experts["down"], **kw("down"))


def ep_divisible(e: int, c: int, mesh, ep_axis: str = "model",
                 cap_axes: Tuple[str, ...] = ()) -> bool:
    """Can (E, C, d) expert buffers run the shard_map EP path on ``mesh``?

    Needs the expert count divisible by the EP axis and the capacity axis
    divisible by every axis it is sharded over (the all-to-alls split E by
    ep on dispatch and C by ep on combine)."""
    if mesh is None or ep_axis not in mesh.shape:
        return False
    ep = mesh.shape[ep_axis]
    cap = ep
    for a in cap_axes:
        cap *= mesh.shape[a]
    return ep > 1 and e % ep == 0 and c % cap == 0


def expert_ffn_ep(
    experts: Any,  # {"gate": QTensor (E, d, ff), "up": ..., "down": (E, ff, d)}
    x: jax.Array,  # (E, C, d) dispatched capacity buffer
    *,
    mesh,
    ep_axis: str = "model",
    cap_axes: Tuple[str, ...] = (),
    backend: str = "pallas_ep",
    site_kwargs: Optional[Dict[str, Dict[str, Any]]] = None,
) -> jax.Array:
    """The whole MoE expert FFN under expert parallelism, as ONE shard_map.

    The token side of the buffer arrives capacity-sharded (C over
    ``cap_axes + (ep_axis,)``, exactly how the dispatch scatter leaves it);
    inside the body an explicit ``all_to_all`` over the expert axis trades
    capacity shards for expert shards, the three projections run the fused
    ``qdense`` path on the LOCAL expert slices only (gate's silu rides in
    its kernel epilogue; h never leaves the shard), and a second
    ``all_to_all`` combines back to capacity sharding.  Each device decodes
    and activation-quantizes only its own experts' slices -- the partitioner
    can no longer replicate the f32 act-quant tensors across the mesh (the
    failure mode of the vmapped qmatmul path, moe.py Perf iteration B7).

    ``site_kwargs``: optional per-site qdense kwargs keyed
    "gate"/"up"/"down" (act_bits / act_exponent / fused from the compiled
    plan) -- per-site so the EP path quantizes each projection exactly like
    the single-device oracle composition does.
    """
    from jax.sharding import PartitionSpec as P

    def body(gq, uq, dq, xs):
        # xs: (E, C_local, d) -- this device's capacity shard of every expert.
        # Dispatch all-to-all: trade the expert axis for the capacity axis so
        # each device holds (E/ep, C_over_cap_axes, d) -- its experts, every
        # token routed to them.
        xl = jax.lax.all_to_all(xs, ep_axis, split_axis=0, concat_axis=1,
                                tiled=True)
        y = expert_ffn_local({"gate": gq, "up": uq, "down": dq}, xl,
                             backend=backend, site_kwargs=site_kwargs)
        # Combine all-to-all: back to capacity sharding for the gather.
        # Cast to the model dtype FIRST -- astype is elementwise, so moving
        # it across the pure data movement is bit-identical, and the combine
        # collective then moves half the bytes on bf16 models (the non-EP
        # combine learned the same lesson as Perf iteration B4, moe.py).
        y = jax.lax.all_to_all(y.astype(xs.dtype), ep_axis, split_axis=1,
                               concat_axis=0, tiled=True)
        return y

    cap = tuple(cap_axes) + (ep_axis,)
    xspec = P(None, cap, None)
    wspec = P(ep_axis)  # leading expert axis of every QTensor field
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(wspec, wspec, wspec, xspec),
        out_specs=xspec,
        check_vma=False,
    )
    with manual_region():  # the body's fused kernels see per-device blocks
        return fn(experts["gate"], experts["up"], experts["down"], x)


def _qdense_sharded(
    xm, qt: QTensor, *, mesh, site: str, bias, act, backend: str,
    act_bits: int, act_exponent, fused: bool, block_m: int, block_n: int,
    block_k: int,
) -> jax.Array:
    """One dense site under a multi-device mesh, as ONE shard_map over the
    weight's serve layout (``parallel/sharding.qtensor_spec`` of ``site``).

    Rows shard over the data axes and every device holds whole rows, so
    each row's dynamic DFP exponent sees its full K, as on one device:
      * N over 'model' (column-parallel) or replicated: each device runs
        the site's kernels on its (K, N/tp) block -- the one-device
        computation, column for column;
      * K over 'model' (row-parallel: wo, down): each device quantizes its
        rows with the ``quantize_rows`` kernel, runs the unfused kernel on
        its K slice (exponents applied), and the f32 partials are summed
        over the axis; bias and activation follow the sum.
    """
    from jax.sharding import PartitionSpec as P

    spec = rules.qtensor_spec(site, qt, mesh, "serve")
    k_ax, n_ax = spec[-2], spec[-1]
    k_div = mesh.shape[k_ax] if k_ax else 1
    n_div = mesh.shape[n_ax] if n_ax else 1
    rows = rules.batch_axes(mesh)
    if rows is not None and xm.shape[0] % math.prod(mesh.shape[a] for a in rows):
        rows = None
    wspec = P(k_ax, n_ax)
    qspec = dataclasses.replace(qt, packed=wspec, scale_m=wspec, scale_e=P())
    local = lambda q: dataclasses.replace(q, shape=(qt.k // k_div, qt.n // n_div))
    kw = dict(backend=backend, act_bits=act_bits, act_exponent=act_exponent,
              block_m=block_m, block_n=block_n, block_k=block_k)

    if k_ax is None:
        def body(x, q, *b):
            return qdense(x, local(q), bias=b[0] if b else None, act=act,
                          fused=fused, **kw)
        out_spec = P(rows, n_ax)
    else:
        def body(x, q, *b):
            q = local(q)
            xq, xe = quantize_activations(x, act_bits, exponent=act_exponent)
            xq = jax.lax.dynamic_slice_in_dim(
                xq, jax.lax.axis_index(k_ax) * q.k, q.k, axis=1
            )
            out = get_backend(backend)(
                xq, xe, q, block_m=block_m, block_n=block_n, block_k=block_k
            )
            out = jax.lax.psum(out, k_ax)
            if b:
                out = out + b[0].astype(jnp.float32)
            return apply_act(out, act)
        out_spec = P(rows, None)

    args, in_specs = [xm, qt], [P(rows, None), qspec]
    if bias is not None:
        args.append(bias)
        in_specs.append(P(n_ax))
    fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=out_spec, check_vma=False)
    with manual_region():  # the body's kernels see per-device blocks
        return fn(*args)


def qdense(
    x: jax.Array,
    qt: QTensor,
    *,
    bias: Optional[jax.Array] = None,
    act: Optional[str] = None,
    backend: str = "auto",
    act_bits: int = 8,
    act_exponent=None,
    fused: bool = True,
    site: Optional[str] = None,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
) -> jax.Array:
    """One quantized dense site: x [..., K] -> f32 [..., N] with the scale
    exponents, ``bias`` and ``act`` ("silu"/"gelu"/"relu") already applied.

    ``site`` is the weight's param path; a kernel backend under a
    multi-device activation mesh needs it to shard the site over the
    weight's serve layout (``_qdense_sharded``).

    On a backend with a registered fused strategy (and ``fused=True``, the
    per-site plan knob) the whole site is ONE kernel launch: activations are
    quantized in-VMEM (per-row dynamic exponents on the first k-step, or the
    plan's calibrated static ``act_exponent`` baked in as a scalar) and the
    ``exp2(scale_e + xe)`` / bias / activation epilogue runs inside the
    resident output tile.  Other backends compose the identical math from
    the unfused pieces, so ``backend="ref"`` remains the bit-exact oracle
    for the fused path.
    """
    lead = x.shape[:-1]
    xm = x.reshape(-1, x.shape[-1])
    name = resolve_backend(backend)
    if _needs_shard_map(name):
        if site is None:
            raise ValueError(
                f"qdense on the {name!r} backend under a multi-device mesh "
                "needs site= (the weight's param path) to find its layout"
            )
        out = _qdense_sharded(
            xm, qt, mesh=rules._ACT_MESH[0], site=site, bias=bias, act=act,
            backend=name, act_bits=act_bits, act_exponent=act_exponent,
            fused=fused, block_m=block_m, block_n=block_n, block_k=block_k,
        )
    elif fused and _fused_available(name, qt):
        out = _FUSED_BACKENDS[name](
            xm, qt, bias=bias, act=act, act_bits=act_bits,
            act_exponent=act_exponent, block_m=block_m, block_n=block_n,
            block_k=block_k,
        )
    else:
        xq, xe = quantize_activations(xm, act_bits, exponent=act_exponent)
        out = get_backend(name)(
            xq, xe, qt, block_m=block_m, block_n=block_n, block_k=block_k
        )
        if bias is not None:
            out = out + bias.astype(jnp.float32)
        out = apply_act(out, act)
    return out.reshape(*lead, qt.n)

"""Unified repro.quant API: compiled plans, registries, calibration-aware PTQ."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import QuantConfig
from repro.core.policy import FULL_PRECISION, LayerPrecision, PrecisionPolicy
from repro.kernels import ref
from repro.models import build_model, make_smoke_batch, quantize_and_plan
from repro.quant import (
    Observer,
    QuantCtx,
    QuantPlan,
    backend_names,
    format_for_bits,
    format_names,
    get_backend,
    get_format,
    qmatmul,
    quantize_activations,
    quantize_model,
    quantize_weights,
    register_backend,
    register_format,
)
from repro.quant import backends as backends_mod
from repro.quant.plan import compile_policy, iter_weight_sites

KEY = jax.random.PRNGKey(0)
PTQ16 = QuantConfig(w_bits=2, group_size=16, mode="ptq", backend="xla")


# ---------------------------------------------------------------------------
# Registries.
# ---------------------------------------------------------------------------
def test_builtin_registries_populated():
    assert {"ternary", "int4", "int8"} <= set(format_names())
    assert {"pallas", "xla", "xla_int8", "ref"} <= set(backend_names())
    for bits in (2, 4, 8):
        assert format_for_bits(bits).bits == bits


def test_registry_duplicate_and_unknown_errors():
    with pytest.raises(ValueError):
        register_format("ternary", bits=2, encode=None, decode=None,
                        weight_codes=None)
    with pytest.raises(ValueError):
        register_backend("xla", lambda *a, **k: None)
    with pytest.raises(KeyError):
        get_format("no_such_format")
    with pytest.raises(ValueError):
        get_backend("no_such_backend")
    with pytest.raises(ValueError):
        qmatmul(jnp.ones((2, 32)), quantize_weights(jnp.ones((32, 8)), 2, 16),
                backend="no_such_backend")


def test_custom_format_plugs_into_qmatmul():
    """A new format flows through quantize_weights + every backend without
    touching dispatch code (the point of the registry)."""
    from repro.core.quantizer import pack4, unpack4
    from repro.quant.formats import _dfp_weight_codes

    name = "int4_dup_for_test"
    try:
        get_format(name)
    except KeyError:
        register_format(
            name, bits=4, encode=pack4, decode=unpack4,
            weight_codes=_dfp_weight_codes(4),
            kernel=format_for_bits(4).kernel,
        )
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    qt = quantize_weights(w, group_size=16, fmt=name)
    assert qt.fmt == name and qt.bits == 4
    want = qmatmul(x, quantize_weights(w, 4, 16), backend="ref")
    for b in ("ref", "xla_int8", "pallas"):
        got = qmatmul(x, qt, backend=b, block_k=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_named_format_flows_through_plan_pipeline():
    """LayerPrecision.fmt selects a registered format through the whole
    quantize_model pipeline (the registry's extension point)."""
    from repro.core.quantizer import pack4, unpack4
    from repro.quant import quantize_params
    from repro.quant.formats import _dfp_weight_codes

    name = "int4_dup_for_test"
    try:
        get_format(name)
    except KeyError:
        register_format(
            name, bits=4, encode=pack4, decode=unpack4,
            weight_codes=_dfp_weight_codes(4),
            kernel=format_for_bits(4).kernel,
        )
    pol = PrecisionPolicy(
        default=LayerPrecision(w_bits=4, group_size=16, fmt=name)
    )
    params = {"proj": {"w": jnp.asarray(
        np.random.default_rng(0).normal(size=(32, 8)), jnp.float32)}}
    plan = pol.compile(params)
    qparams = quantize_params(params, plan)
    qt = qparams["proj"]["w"]
    assert qt.fmt == name and qt.bits == 4
    # and the precision (incl. fmt) survives plan serialization
    assert QuantPlan.from_json(plan.to_json()).resolve("proj").fmt == name


def test_register_format_overwrite_does_not_steal_bits_default():
    """Overwriting a format must not silently re-route fmt="" QTensors of an
    unrelated width, and a name changing width drops its stale default."""
    from repro.core.quantizer import pack4, unpack4
    from repro.quant.formats import _dfp_weight_codes

    name = "bits_probe_for_test"
    kw = dict(encode=pack4, decode=unpack4, weight_codes=_dfp_weight_codes(4))
    register_format(name, bits=4, overwrite=True, **kw)
    assert format_for_bits(4).name == "int4"  # default untouched
    # re-register the same name at a width it can't default either
    register_format(name, bits=8, overwrite=True, **kw)
    assert format_for_bits(8).name == "int8"
    assert format_for_bits(4).name == "int4"  # stale claim dropped, not kept


def test_register_format_overwrite_reassigns_orphaned_bits_default():
    """Regression: re-registering the sole claimant of a width at a NEW
    width must hand the old width's default to a surviving format of that
    width -- pre-fix the default was deleted outright, so format_for_bits
    raised for a width that resolved fine before the re-registration."""
    from repro.core.quantizer import pack4, unpack4
    from repro.quant.formats import _BY_BITS, _FORMATS, _dfp_weight_codes

    kw = dict(encode=pack4, decode=unpack4, weight_codes=_dfp_weight_codes(4))
    a, b = "orphan_probe_a", "orphan_probe_b"
    width = 3  # unclaimed by any built-in
    try:
        register_format(a, bits=width, overwrite=True, **kw)  # claims width 3
        register_format(b, bits=width, overwrite=True, **kw)  # doesn't
        assert format_for_bits(width).name == a
        # branch 1: a CODEC-COMPATIBLE survivor (same encode/decode
        # callables) exists -> the default migrates to it
        register_format(a, bits=5, overwrite=True, **kw)
        assert format_for_bits(width).name == b
        # branch 1b: a survivor with DIFFERENT code semantics must NOT
        # inherit the default -- legacy empty-fmt payloads would silently
        # mis-decode through it (e.g. int4 two's-complement through a LUT);
        # fail closed instead
        from repro.quant.formats import _nf4_decode
        from repro.core.quantizer import pack4u

        c = "orphan_probe_c"
        register_format(
            c, bits=5, overwrite=True,
            encode=pack4u, decode=_nf4_decode, weight_codes=kw["weight_codes"],
        )
        register_format(a, bits=7, overwrite=True, **kw)  # a owned width 5
        with pytest.raises(ValueError):
            format_for_bits(5)  # c survives at width 5 but is incompatible
        # branch 2: no survivor at all -> fail closed (raise), no stale ptr
        register_format(b, bits=6, overwrite=True, **kw)
        with pytest.raises(ValueError):
            format_for_bits(width)
    finally:  # the registry is process-global: leave no probe state behind
        for probe in (a, b, "orphan_probe_c"):
            _FORMATS.pop(probe, None)
        for bits in (3, 5, 6, 7):
            if _BY_BITS.get(bits) in (a, b, "orphan_probe_c"):
                del _BY_BITS[bits]


def test_quantize_weights_stamps_resolved_format_name():
    """Regression: bits-resolved QTensors must be stamped with the resolved
    format NAME, not fmt="" -- an empty stamp re-resolves through the
    mutable _BY_BITS table at decode time, which is ambiguous now that nf4
    coexists with int4 (and mx with int8) at the same width."""
    from repro.quant.formats import format_of

    w = jnp.asarray(np.random.default_rng(0).normal(size=(64, 8)), jnp.float32)
    for bits, want in ((2, "ternary"), (4, "int4"), (8, "int8")):
        qt = quantize_weights(w, bits, 16)
        assert qt.fmt == want
    # legacy empty-fmt artifacts (pre-fix checkpoints) still resolve by
    # bits, and the defaults still point at the built-ins even though nf4
    # and mx are registered at the same widths
    legacy4 = dataclasses.replace(quantize_weights(w, 4, 16), fmt="")
    legacy8 = dataclasses.replace(quantize_weights(w, 8, 16), fmt="")
    assert format_of(legacy4).name == "int4"
    assert format_of(legacy8).name == "int8"


def test_new_formats_registered_without_stealing_defaults():
    """nf4 (bits=4) and mx (bits=8) are first-class registry citizens whose
    bit-widths collide with built-ins -- the registry must keep them
    name-addressed while bits stay with int4/int8."""
    assert {"nf4", "mx"} <= set(format_names())
    assert get_format("nf4").bits == 4 and format_for_bits(4).name == "int4"
    assert get_format("mx").bits == 8 and format_for_bits(8).name == "int8"
    assert get_format("mx").block_size == 32
    for name in ("nf4", "mx"):
        f = get_format(name)
        assert f.kernel is not None and f.fused_kernel is not None


def test_qat_ste_honors_named_format():
    """Regression: the QAT forward must fake-quantize on the NAMED format's
    grid (the one PTQ deploys on), not the bits-default uniform grid --
    silently training against int4's grid while serving nf4's LUT would
    lose the QAT benefit with no error."""
    from repro.core import ste
    from repro.quant.formats import fake_quantize_weights

    w = jnp.asarray(np.random.default_rng(0).normal(size=(64, 8)), jnp.float32)
    got = ste.weights_ste(w, 4, 16, fmt="nf4")
    want = fake_quantize_weights(w, 4, 16, fmt="nf4")
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # the nf4 grid really differs from the bits-4 default grid
    assert not np.array_equal(
        np.asarray(got), np.asarray(fake_quantize_weights(w, 4, 16))
    )
    # and the straight-through gradient is still identity
    g = jax.grad(lambda m: ste.weights_ste(m, 4, 16, fmt="nf4").sum())(w)
    assert np.allclose(np.asarray(g), 1.0)


def test_custom_backend_dispatch():
    calls = []

    def null_backend(xq, xe, qt, **kw):
        calls.append(xq.shape)
        return jnp.zeros((xq.shape[0], qt.n), jnp.float32)

    try:
        register_backend("null_for_test", null_backend)
    except ValueError:
        pass
    qt = quantize_weights(jnp.ones((32, 8)), 2, 16)
    out = qmatmul(jnp.ones((3, 32)), qt, backend="null_for_test")
    assert out.shape == (3, 8) and calls


# ---------------------------------------------------------------------------
# quantize_activations: explicit three-way control flow (was dead logic).
# ---------------------------------------------------------------------------
def test_quantize_activations_ref_path_matches_oracle():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 32)), jnp.float32)
    q, e = quantize_activations(x, use_pallas=False)
    qr, er = ref.quantize_rows_ref(x, 8)
    assert (np.asarray(q) == np.asarray(qr)).all()
    assert (np.asarray(e) == np.asarray(er)).all()


def test_quantize_activations_pallas_interpret_matches_oracle():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(8, 32)), jnp.float32)
    q, e = quantize_activations(x, use_pallas=True)  # off-TPU -> interpret
    qr, er = ref.quantize_rows_ref(x, 8)
    assert (np.asarray(q) == np.asarray(qr)).all()
    assert (np.asarray(e) == np.asarray(er)).all()


def test_quantize_activations_dispatch_three_way(monkeypatch):
    """pallas-on-tpu / pallas-interpret / ref are each reachable and chosen
    by (use_pallas, interpret_mode) exactly."""
    seen = {}

    def fake_quantize_rows(x, *, bits=8, interpret=False, **kw):
        seen["interpret"] = interpret
        return ref.quantize_rows_ref(x, bits)

    monkeypatch.setattr(backends_mod, "quantize_rows", fake_quantize_rows)
    x = jnp.ones((4, 16))

    monkeypatch.setattr(backends_mod, "interpret_mode", lambda: False)
    quantize_activations(x)  # default on TPU -> pallas, compiled
    assert seen.pop("interpret") is False

    monkeypatch.setattr(backends_mod, "interpret_mode", lambda: True)
    quantize_activations(x, use_pallas=True)  # forced pallas off-TPU
    assert seen.pop("interpret") is True

    quantize_activations(x)  # default off-TPU -> ref oracle, no pallas call
    assert "interpret" not in seen


# ---------------------------------------------------------------------------
# Plan compilation: identical resolutions to the legacy per-call resolve.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_plan_matches_legacy_resolve_every_family(arch):
    cfg = configs.get_smoke(arch, PTQ16)
    api = build_model(cfg)
    shapes = jax.eval_shape(lambda: api.init(KEY))
    pol = api.ctx.policy
    plan = pol.compile(shapes)
    assert plan.site_paths, arch
    for path, prec in plan.sites():
        assert prec == pol.resolve(path), path
        assert plan.resolve(path) == pol.resolve(path), path
    # off-table paths fall back to the regex rules (exact legacy semantics)
    for path in ("never/compiled/site", "blocks/99/made_up", "frontend/x"):
        assert plan.resolve(path) == pol.resolve(path)


def test_plan_paper_override_paths():
    params = {
        "embed": {"w": jnp.zeros((32, 16))},
        "blocks": {"attn": {"wq": {"w": jnp.zeros((32, 16))}}},
        "lm_head": {"w": jnp.zeros((32, 16))},
        "router": {"w": jnp.zeros((32, 16))},
    }
    pol = PrecisionPolicy.ternary(group_size=16)
    plan = compile_policy(pol, params)
    assert plan.resolve("embed").w_bits == 8  # C1 analogue
    assert plan.resolve("lm_head").w_bits == 8  # FC analogue
    assert plan.resolve("router").w_bits == 8  # MoE control path
    assert plan.resolve("blocks/attn/wq").w_bits == 2  # default ternary
    assert plan.resolve("blocks/ln/norm").w_bits == FULL_PRECISION  # fallback


def test_plan_first_match_wins_ordering():
    a = LayerPrecision(w_bits=8)
    b = LayerPrecision(w_bits=4)
    pol = PrecisionPolicy(
        default=LayerPrecision(w_bits=2),
        overrides=((r"blocks/x", a), (r"blocks", b)),
    )
    params = {"blocks": {"x": {"w": jnp.zeros((16, 4))},
                         "y": {"w": jnp.zeros((16, 4))}}}
    plan = compile_policy(pol, params)
    assert plan.resolve("blocks/x").w_bits == 8  # first pattern wins
    assert plan.resolve("blocks/y").w_bits == 4  # second catches the rest
    assert [p for p, _ in plan.sites()] == sorted(p for p, _ in plan.sites())


def test_iter_weight_sites_shapes_and_stacked_axes():
    params = {
        "a": {"w": jnp.zeros((5, 32, 16)), "b": jnp.zeros((16,))},  # stacked
        "n": {"scale": jnp.zeros((8,))},
        "c": {"w": jnp.zeros((7,))},  # 1-D 'w' is not a projection site
    }
    sites = dict(iter_weight_sites(params))
    assert set(sites) == {"a"}


def test_plan_compiles_under_eval_shape():
    cfg = configs.get_smoke("qwen3-8b", PTQ16)
    api = build_model(cfg)
    shapes = jax.eval_shape(lambda: api.init(KEY))
    plan_abs = api.ctx.policy.compile(shapes)
    params = api.init(KEY)
    plan_real = api.ctx.policy.compile(params)
    assert plan_abs.site_paths == plan_real.site_paths
    assert plan_abs.site_precisions == plan_real.site_precisions


# ---------------------------------------------------------------------------
# Plan serialization + pytree registration.
# ---------------------------------------------------------------------------
def _example_plan() -> QuantPlan:
    cfg = configs.get_smoke("qwen3-8b", PTQ16)
    api = build_model(cfg)
    shapes = jax.eval_shape(lambda: api.init(KEY))
    plan = api.ctx.policy.compile(shapes, mode="ptq", backend="xla_int8")
    return plan.with_act_exponents({"blocks/attn/wq": -3, "lm_head": 1})


def test_plan_json_roundtrip():
    plan = _example_plan()
    back = QuantPlan.from_json(plan.to_json())
    assert back == plan
    assert back.resolve("blocks/attn/wq") == plan.resolve("blocks/attn/wq")
    assert back.act_exponent("blocks/attn/wq") == -3
    assert back.act_exponent("blocks/mlp/up") is None
    assert back.policy == plan.policy


def test_plan_pytree_roundtrip():
    plan = _example_plan()
    leaves, treedef = jax.tree.flatten(plan)
    assert leaves == []  # all-static: free to close over in jit
    back = jax.tree.unflatten(treedef, leaves)
    assert back == plan
    # and it survives a jit closure without retracing hazards
    @jax.jit
    def f(x):
        prec = plan.resolve("blocks/attn/wq")
        return x * prec.w_bits

    assert float(f(jnp.float32(2.0))) == 4.0


def test_plan_static_act_opt_out():
    plan = _example_plan()
    assert plan.act_exponent("blocks/attn/wq") == -3
    # pin one site to dynamic per-row exponents
    precs = tuple(
        dataclasses.replace(p, static_act=False) if path == "blocks/attn/wq" else p
        for path, p in plan.sites()
    )
    pinned = dataclasses.replace(plan, site_precisions=precs)
    assert pinned.act_exponent("blocks/attn/wq") is None
    assert pinned.act_exponent("lm_head") == 1


# ---------------------------------------------------------------------------
# Calibration-aware PTQ (the paper's profiled static-DFP activation mode).
# ---------------------------------------------------------------------------
def test_observer_collects_sites_and_exponents():
    obs = Observer()
    obs.record("s", 3.0, 1.0)
    obs.record("s", 1.0, 2.0)
    assert obs["s"]["max_abs"] == 3.0 and obs["s"]["count"] == 2.0
    e = obs.exponents()["s"]
    assert 3.0 <= 127 * 2.0 ** e  # static exponent covers the seen range


def test_quantize_model_calibrates_and_serializes():
    cfg = configs.get_smoke("qwen3-8b", PTQ16)
    api = build_model(cfg)
    params = api.init(KEY)
    batches = [make_smoke_batch(jax.random.PRNGKey(i), cfg, 2, 16) for i in (1, 2)]
    qparams, plan = quantize_model(
        params, api.ctx.policy, backend="xla",
        calib_batches=batches,
        forward=lambda p, b, ctx: api.with_ctx(ctx).forward(p, b),
    )
    assert plan.calibrated
    # every compiled projection site was observed by the calibration pass
    assert set(plan.site_paths) <= {p for p, _ in plan.act_exponents}
    # the plan (with exponents) survives serialization
    assert QuantPlan.from_json(plan.to_json()) == plan
    # and quantize_model without calibration leaves exponents empty
    _, plan2 = quantize_model(params, api.ctx.policy)
    assert not plan2.calibrated


def test_static_exponents_match_dynamic_within_dfp_tolerance():
    """PTQ with calibrated static per-site exponents vs dynamic per-row:
    same integer pipeline, agreement within DFP rounding on a zoo model."""
    cfg = configs.get_smoke("qwen3-8b", QuantConfig(
        w_bits=8, group_size=16, mode="ptq", backend="xla"))
    api = build_model(cfg)
    params = api.init(KEY)
    batch = make_smoke_batch(jax.random.PRNGKey(3), cfg, 2, 16)
    qparams, plan, api_static = quantize_and_plan(
        api, params, calib_batches=[batch]
    )
    assert plan.calibrated
    api_dynamic = api.with_plan(plan.with_act_exponents({}))

    out_s = np.asarray(api_static.forward(qparams, batch), np.float32)
    out_d = np.asarray(api_dynamic.forward(qparams, batch), np.float32)
    scale = np.abs(out_d).max() + 1e-9
    # a per-tensor static exponent is coarser than per-row dynamic ones, so
    # agreement is to DFP rounding at the site scale, not bit-exact
    assert np.abs(out_s - out_d).max() / scale < 0.10
    # both agree with the fp forward to PTQ accuracy (sanity)
    out_fp = np.asarray(api.forward(params, batch), np.float32)
    assert np.abs(out_s - out_fp).max() / (np.abs(out_fp).max() + 1e-9) < 0.5


def test_qmatmul_static_exponent_covers_range_exactly():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    qt = quantize_weights(jnp.asarray(rng.normal(size=(64, 16)), jnp.float32), 8, 16)
    # a static exponent at least as large as every row's dynamic exponent
    _, xe = ref.quantize_rows_ref(x, 8)
    e_static = int(np.asarray(xe).max())
    got = qmatmul(x, qt, backend="ref", act_exponent=e_static)
    want = qmatmul(x, qt, backend="ref")
    scale = np.abs(np.asarray(want)).max() + 1e-9
    assert np.abs(np.asarray(got) - np.asarray(want)).max() / scale < 0.02


def test_ptq_serving_on_plan_quantized_params():
    """ServingEngine end-to-end on plan-quantized params (acceptance)."""
    from repro.serving import Request, ServingEngine

    cfg = configs.get_smoke("qwen3-8b", PTQ16)
    api = build_model(cfg)
    params = api.init(KEY)
    batch = make_smoke_batch(jax.random.PRNGKey(5), cfg, 2, 16)
    qparams, plan, api = quantize_and_plan(api, params, calib_batches=[batch])
    assert api.ctx.plan is plan
    eng = ServingEngine(api, qparams, n_slots=2, max_len=16)
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=4))
    done = eng.run()
    assert len(done) == 1 and len(done[0].output) == 4


def test_calibrated_exponents_use_per_site_act_bits():
    """Exponent finalization must use the act_bits each site is quantized
    with (LayerPrecision.act_bits), not one global width -- a 4-bit site's
    exponent from an 8-bit grid would saturate its mantissas 16x early."""
    from repro.core import dfp

    params = {"four": {"w": jnp.zeros((32, 8))}, "eight": {"w": jnp.zeros((32, 8))}}
    pol = PrecisionPolicy(
        default=LayerPrecision(w_bits=8, act_bits=8, group_size=16),
        overrides=((r"^four$", LayerPrecision(w_bits=8, act_bits=4, group_size=16)),),
    )

    def forward(p, batch, ctx):
        from repro.quant import observe_site

        for site in ("four", "eight"):
            observe_site(ctx.observer, site, batch)

    x = jnp.full((4, 32), 100.0)
    _, plan = quantize_model(params, pol, calib_batches=[x], forward=forward)
    e4, e8 = plan.act_exponent("four"), plan.act_exponent("eight")
    assert 100.0 <= dfp.qmax(4) * 2.0 ** e4
    assert 100.0 <= dfp.qmax(8) * 2.0 ** e8
    assert e4 > e8  # fewer mantissa bits -> coarser grid -> larger exponent


def test_quantize_model_requires_forward_for_calibration():
    cfg = configs.get_smoke("qwen3-8b", PTQ16)
    api = build_model(cfg)
    params = jax.eval_shape(lambda: api.init(KEY))
    with pytest.raises(ValueError):
        quantize_model(params, api.ctx.policy, calib_batches=[{}])

"""Quantized artifact lifecycle: packed save/load round-trips, cold-start
serving parity, plan persistence, and corruption fallback."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import QuantConfig, config_from_dict, config_to_dict
from repro.models import (
    build_model,
    load_servable,
    make_smoke_batch,
    quantize_and_plan,
    save_servable,
)
from repro.quant import QTensor, load_artifact, save_artifact
from repro.serving import Request, ServingEngine
from repro.training import checkpoint as ck

KEY = jax.random.PRNGKey(0)

# one representative smoke arch per zoo family
FAMILY_ARCHS = {
    "dense": "qwen3-8b",
    "moe": "grok-1-314b",
    "vlm": "qwen2-vl-72b",
    "hybrid": "zamba2-7b",
    "ssm": "falcon-mamba-7b",
    "encdec": "whisper-base",
}


def _quantized(arch, bits, calib=False):
    cfg = configs.get_smoke(
        arch, QuantConfig(w_bits=bits, group_size=16, mode="ptq", backend="xla")
    )
    api = build_model(cfg)
    params = api.init(KEY)
    batches = None
    if calib:
        batches = [
            make_smoke_batch(jax.random.PRNGKey(100 + i), cfg, batch=2, seq=16)
            for i in range(2)
        ]
    qparams, plan, qapi = quantize_and_plan(api, params, calib_batches=batches)
    return qapi, qparams, plan


def _flat(tree):
    return [
        (ck._path_str(p), l)
        for p, l in jax.tree_util.tree_flatten_with_path(tree)[0]
    ]


def _assert_trees_bit_exact(a, b):
    fa, fb = _flat(a), _flat(b)
    assert [p for p, _ in fa] == [p for p, _ in fb]
    for (path, la), (_, lb) in zip(fa, fb):
        assert np.asarray(la).dtype == np.asarray(lb).dtype, path
        assert np.array_equal(np.asarray(la), np.asarray(lb)), path


# ---------------------------------------------------------------------------
# Round-trip matrix: every zoo family x every built-in format.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", sorted(FAMILY_ARCHS.values()))
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_artifact_roundtrip_family_x_format(arch, bits, tmp_path):
    qapi, qparams, plan = _quantized(arch, bits)
    save_servable(str(tmp_path), qapi, qparams, plan)
    api2, loaded, art = load_servable(str(tmp_path))

    _assert_trees_bit_exact(qparams, loaded)
    # QTensor static metadata survives (bits/group/shape/fmt), still packed
    orig_qt = {p: l for p, l in _flat_qts(qparams)}
    got_qt = {p: l for p, l in _flat_qts(loaded)}
    assert orig_qt.keys() == got_qt.keys() and orig_qt
    for path, qt in got_qt.items():
        ref = orig_qt[path]
        assert (qt.bits, qt.group_size, qt.shape, qt.fmt) == (
            ref.bits, ref.group_size, ref.shape, ref.fmt
        ), path
        assert qt.packed.dtype == ref.packed.dtype
    # plan round-trips byte-identical, config rebuilds exactly
    assert art.plan is not None and art.plan.to_json() == plan.to_json()
    assert api2.cfg == qapi.cfg


def _flat_qts(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda l: isinstance(l, QTensor)
    )
    return [
        (ck._path_str(p), l) for p, l in flat if isinstance(l, QTensor)
    ]


@pytest.mark.parametrize("fmt", ["nf4", "mx"])
def test_artifact_roundtrip_block_formats(fmt, tmp_path):
    """nf4/mx artifacts round-trip packed (payload projections differ per
    format: nf4 packs K/8 uint32 rows like int4, mx stores raw int8 plus a
    K/32 block-scale table) and cold-start decode is bit-identical."""
    cfg = configs.get_smoke(
        "qwen3-8b",
        QuantConfig(
            w_bits=4 if fmt == "nf4" else 8, group_size=16, mode="ptq",
            backend="xla", fmt=fmt,
        ),
    )
    api = build_model(cfg)
    params = api.init(KEY)
    qparams, plan, qapi = quantize_and_plan(api, params)
    fmts = {qt.fmt for _, qt in _flat_qts(qparams)}
    assert fmt in fmts  # default sites actually use the named format

    save_servable(str(tmp_path), qapi, qparams, plan)
    cold_api, cold_params, art = load_servable(str(tmp_path))
    _assert_trees_bit_exact(qparams, cold_params)
    for path, qt in _flat_qts(cold_params):
        ref = dict(_flat_qts(qparams))[path]
        assert (qt.bits, qt.group_size, qt.shape, qt.fmt) == (
            ref.bits, ref.group_size, ref.shape, ref.fmt
        ), path
    assert art.plan.to_json() == plan.to_json()

    tok = jnp.asarray([[3]], jnp.int32)
    l_mem, _ = qapi.decode(qparams, tok, jnp.int32(0), qapi.init_cache(1, 8))
    l_cold, _ = cold_api.decode(
        cold_params, tok, jnp.int32(0), cold_api.init_cache(1, 8)
    )
    assert np.array_equal(np.asarray(l_mem), np.asarray(l_cold))


def test_artifact_roundtrip_bf16_model(tmp_path):
    """Published configs are bfloat16: the unquantized leaves (norm gains,
    the snapped embedding table) must load back as bfloat16, though .npy
    stores that dtype as raw two-byte records."""
    cfg = dataclasses.replace(
        configs.get_smoke(
            "qwen3-8b",
            QuantConfig(w_bits=2, group_size=16, mode="ptq", backend="xla"),
        ),
        dtype="bfloat16",
    )
    api = build_model(cfg)
    qparams, plan, qapi = quantize_and_plan(api, api.init(KEY))
    assert any(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(qparams))
    save_servable(str(tmp_path), qapi, qparams, plan)
    _, loaded, _ = load_servable(str(tmp_path))
    _assert_trees_bit_exact(qparams, loaded)


def test_legacy_empty_fmt_manifest_resolves_by_bits(tmp_path):
    """Pre-fix artifacts stamped fmt="" (bits-resolved QTensors) must keep
    loading and resolving through the bits default -- which registration
    keeps pointed at the built-ins even though nf4/mx now share those
    widths.  Simulates a pre-fix manifest by blanking the stored fmt tags."""
    from repro.quant.formats import format_of

    qapi, qparams, plan = _quantized("qwen3-8b", 4)
    save_servable(str(tmp_path), qapi, qparams, plan)
    d = tmp_path / "step_000000000"
    mpath = d / "manifest.json"
    man = json.loads(mpath.read_text())
    blanked = 0
    for node in man["nodes"].values():
        if node["codec"] == "qtensor" and node["meta"].get("fmt"):
            node["meta"]["fmt"] = ""  # what a pre-fix writer stored
            blanked += 1
    assert blanked  # post-fix writers always stamp a name
    mpath.write_text(json.dumps(man))  # meta is not payload-checksummed

    _, cold_params, _ = load_servable(str(tmp_path))
    legacy = dict(_flat_qts(cold_params))
    assert legacy
    for path, qt in legacy.items():
        assert qt.fmt == ""  # the artifact really is legacy-shaped
        want = {2: "ternary", 4: "int4", 8: "int8"}[qt.bits]
        assert format_of(qt).name == want, path  # bits default, not nf4/mx
        ref = dict(_flat_qts(qparams))[path]
        # bits-resolution decodes the payload identically to the stamped
        # original (leading stacked-layer axes decode per-matrix)
        dec = format_of(qt).decode
        unstack = lambda a: a.reshape((-1,) + a.shape[-2:])
        got = [dec(p, qt.k) for p in unstack(qt.packed)]
        exp = [dec(p, ref.k) for p in unstack(ref.packed)]
        for g, e in zip(got, exp):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(e))
        np.testing.assert_array_equal(
            np.asarray(qt.scale_m), np.asarray(ref.scale_m)
        )


def test_config_dict_roundtrip():
    cfg = configs.get_smoke("qwen3-8b", QuantConfig(w_bits=4, mode="ptq"))
    blob = json.dumps(config_to_dict(cfg))  # must be JSON-safe
    assert config_from_dict(json.loads(blob)) == cfg


# ---------------------------------------------------------------------------
# Cold-start serving parity: artifact tokens == in-memory quantize tokens.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["qwen3-8b", "grok-1-314b"])
def test_cold_start_decode_bit_exact(arch, tmp_path):
    """The decode step served from a loaded artifact is bit-identical to the
    in-memory ``quantize_and_plan`` path (calibrated static exponents
    included)."""
    qapi, qparams, plan = _quantized(arch, 2, calib=True)
    assert plan.calibrated
    save_servable(str(tmp_path), qapi, qparams, plan)
    cold_api, cold_params, _ = load_servable(str(tmp_path))

    tok = jnp.asarray([[3]], jnp.int32)
    l_mem, _ = qapi.decode(qparams, tok, jnp.int32(0), qapi.init_cache(1, 8))
    l_cold, _ = cold_api.decode(
        cold_params, tok, jnp.int32(0), cold_api.init_cache(1, 8)
    )
    assert np.array_equal(np.asarray(l_mem), np.asarray(l_cold))


def test_engine_from_artifact_serves_same_tokens(tmp_path):
    qapi, qparams, plan = _quantized("qwen3-8b", 2, calib=True)
    save_servable(str(tmp_path), qapi, qparams, plan)

    def tokens(eng):
        eng.submit(Request(uid=0, prompt=[5, 9, 2], max_new_tokens=4))
        return eng.run()[0].output

    warm = tokens(ServingEngine(qapi, qparams, n_slots=2, max_len=16))
    cold = tokens(ServingEngine.from_artifact(str(tmp_path), n_slots=2, max_len=16))
    assert warm == cold


def test_artifact_smaller_than_fp32(tmp_path):
    """Packed ternary artifact on disk is >= 4x smaller than the fp32 tree
    (the deployment claim bench_checkpoint measures at larger scale)."""
    cfg = configs.get_smoke(
        "qwen3-8b", QuantConfig(w_bits=2, group_size=16, mode="ptq", backend="xla")
    )
    api = build_model(cfg)
    params = api.init(KEY)
    qparams, plan, qapi = quantize_and_plan(api, params)

    fp_dir, q_dir = tmp_path / "fp", tmp_path / "q"
    ck.save(str(fp_dir), 0, params)
    save_servable(str(q_dir), qapi, qparams, plan)

    # smoke models are embedding-heavy (kept 8-bit-in-fp32 storage), so the
    # projection compression is diluted; 2x on disk here implies >= 4x at
    # real scale where projections dominate -- asserted exactly in
    # benchmarks/bench_checkpoint.py with a projection-dominated config
    assert ck.dir_bytes(str(fp_dir)) / ck.dir_bytes(str(q_dir)) > 2.0


# ---------------------------------------------------------------------------
# Plan persistence + corruption injection.
# ---------------------------------------------------------------------------
def test_truncated_plan_fails_verification_and_falls_back(tmp_path):
    """A corrupt/truncated quant_plan section must invalidate the step (not
    restore as 'unquantized'): restore_latest falls back to the previous
    intact step, load_artifact skips it."""
    qapi, qparams, plan = _quantized("qwen3-8b", 2)
    save_artifact(
        str(tmp_path), qparams, plan,
        extra={"arch_config": config_to_dict(qapi.cfg)}, step=1,
    )
    save_artifact(
        str(tmp_path), qparams, plan,
        extra={"arch_config": config_to_dict(qapi.cfg)}, step=2,
    )
    plan_file = tmp_path / "step_000000002" / ck.PLAN_FILE
    blob = plan_file.read_text()
    plan_file.write_text(blob[: len(blob) // 2])  # truncate mid-JSON

    assert ck.latest_intact_step(str(tmp_path)) == 1
    art = load_artifact(str(tmp_path))
    assert art.step == 1 and art.plan is not None
    assert art.plan.to_json() == plan.to_json()

    template = jax.eval_shape(lambda: qparams)
    step, tree = ck.restore_latest(str(tmp_path), template)
    assert step == 1
    _assert_trees_bit_exact(tree, qparams)


def test_corrupt_packed_payload_falls_back(tmp_path):
    """Bit-rot in a packed QTensor payload is caught by its sha256."""
    qapi, qparams, plan = _quantized("qwen3-8b", 2)
    save_servable(str(tmp_path), qapi, qparams, plan)
    d = tmp_path / "step_000000000"
    victim = sorted(f for f in os.listdir(d) if f.endswith(".npy"))[0]
    with open(d / victim, "r+b") as f:
        f.seek(-1, os.SEEK_END)
        f.write(b"\xff")
    with pytest.raises(IOError):
        load_artifact(str(tmp_path))


def test_plan_json_tamper_detected(tmp_path):
    """A plan whose JSON parses but whose bytes changed (checksum mismatch)
    is rejected -- content integrity, not just well-formedness."""
    qapi, qparams, plan = _quantized("qwen3-8b", 2)
    save_servable(str(tmp_path), qapi, qparams, plan)
    plan_file = tmp_path / "step_000000000" / ck.PLAN_FILE
    tampered = json.loads(plan_file.read_text())
    tampered["mode"] = "qat"
    plan_file.write_text(json.dumps(tampered))
    with pytest.raises(IOError):
        load_artifact(str(tmp_path))


def test_type_corrupt_manifest_falls_back(tmp_path):
    """A manifest that is valid JSON but structurally wrong-typed (null
    array entry) counts as corrupt and falls back, not crashes."""
    tree = {"a": jnp.arange(4.0)}
    ck.save(str(tmp_path), 1, tree)
    ck.save(str(tmp_path), 2, tree)
    mpath = tmp_path / "step_000000002" / "manifest.json"
    m = json.loads(mpath.read_text())
    m["arrays"] = {"a": None}
    mpath.write_text(json.dumps(m))
    assert ck.latest_intact_step(str(tmp_path)) == 1
    step, _ = ck.restore_latest(str(tmp_path), jax.eval_shape(lambda: tree))
    assert step == 1


def test_checkpoint_without_plan_still_restores(tmp_path):
    """Plain (plan-less) checkpoints keep working through the codec layer."""
    tree = {"a": jnp.arange(4.0), "n": {"b": jnp.ones((2, 2), jnp.int32)}}
    ck.save(str(tmp_path), 3, tree)
    d = ck.step_dir(str(tmp_path), 3)
    assert ck.load_plan(d) is None
    got = ck.restore_tree(d)
    _assert_trees_bit_exact(tree, got)


# ---------------------------------------------------------------------------
# MoE calibration satellite: expert sites land in the plan.
# ---------------------------------------------------------------------------
def test_moe_expert_sites_calibrated(tmp_path):
    """The vmapped expert matmuls route through the observer: expert MLP
    sites carry profiled static exponents, and they survive the artifact."""
    qapi, qparams, plan = _quantized("grok-1-314b", 2, calib=True)
    exp_sites = {p for p, _ in plan.act_exponents}
    assert {
        "blocks/moe/experts/gate",
        "blocks/moe/experts/up",
        "blocks/moe/experts/down",
    } <= exp_sites
    # router (a dense() site) is profiled too
    assert any(p.endswith("moe/router") for p in exp_sites)
    save_servable(str(tmp_path), qapi, qparams, plan)
    _, _, art = load_servable(str(tmp_path))
    assert {p for p, _ in art.plan.act_exponents} == exp_sites


def test_trainer_restores_plan(tmp_path):
    """Trainer.maybe_restore is plan-aware: a restarted node resumes with
    the checkpointed precision table, calibrated exponents included."""
    from repro.training import OptConfig, TrainConfig, Trainer
    from repro.training.data import DataConfig, make_batch

    _, _, plan = _quantized("qwen3-8b", 2, calib=True)
    cfg = configs.get_smoke("phi4-mini-3.8b")
    api = build_model(cfg)
    params = api.init(KEY)
    tcfg = TrainConfig(
        opt=OptConfig(lr=1e-4, warmup_steps=0), ckpt_dir=str(tmp_path),
        ckpt_every=2,
    )
    tr = Trainer(api.train_loss, params, tcfg, plan=plan)
    tr.train(lambda i: make_batch(cfg, DataConfig(batch=2, seq=16), i), 2)

    fresh = Trainer(api.train_loss, params, tcfg)  # "new node", no plan
    assert fresh.maybe_restore() == 2
    assert fresh.plan is not None
    assert fresh.plan.to_json() == plan.to_json()

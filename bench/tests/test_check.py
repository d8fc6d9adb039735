"""The correctness check at smoke size on the CPU: a sound run passes, the
control (4-bit activations) reads above the limit, and a run with its
timed path broken underneath comes out not correct."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench import cell as C
from bench import run
from bench.check import control

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = C._load_json(os.path.join(C.BENCH, "peaks.json"))["TPU v5 lite"]
SECONDS = 2.0


@pytest.fixture
def smoke(monkeypatch):
    from repro import configs

    monkeypatch.setattr(configs, "get_config", configs.get_smoke)

    def make(mix):
        cfg = C._load_json(os.path.join(HERE, "smoke-phi4.json"))
        m = C._load_json(os.path.join(HERE, f"smoke-{mix}.json"))
        e2e = [{"name": n, "unit": "x"} for n in
               ("itl_p95_ms", "out_tok_s", "setup_s")]
        return C.Cell(f"smoke-{mix}", 1, cfg, m, e2e, [])

    return make


def _result(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("mix", ["chat", "longdoc"])
def test_sound_run_is_correct(smoke, capsys, mix):
    cell = smoke(mix)
    assert run.run_cell(cell, 2**31 + 9, SECONDS, False, PEAKS,
                        jax.devices()[0]) == 0
    out = _result(capsys)
    assert out["correct"], out
    assert out["check"]["worst_gap"]["value"] <= \
        cell.config["check"]["worst_gap_limit"]
    assert list(out)[-1] == "check"
    assert set(out["metrics"]) >= {"setup_s"}


def test_control_reads_above_the_limit(smoke):
    cell = smoke("chat")
    limit = cell.config["check"]["worst_gap_limit"]
    r = control.readings(cell, 5, SECONDS, jax.devices()[0])
    assert r["program_worst_gap"] <= limit < r["control_worst_gap"], r
    assert r["program_correct"] and not r["control_correct"], r


def test_token_altered_where_produced_is_not_correct(smoke, capsys,
                                                     monkeypatch):
    from repro.serving import engine

    sound = engine.sample

    def altered(key, logits, cfg):
        tok = sound(key, logits, cfg)
        return jnp.where(tok == 0, 1, tok - 1).astype(tok.dtype)

    monkeypatch.setattr(engine, "sample", altered)
    run.run_cell(smoke("chat"), 3, SECONDS, False, PEAKS, jax.devices()[0])
    out = _result(capsys)
    assert not out["correct"], out


def test_cache_left_unwritten_is_not_correct(smoke, capsys, monkeypatch):
    """The decode tick returns its cache as it came: every served token
    after the first attends to a stale cache."""
    from repro.models import transformer

    sound = transformer.decode_step

    def stale(params, token, pos, cfg, ctx, cache):
        logits, _ = sound(params, token, pos, cfg, ctx, cache)
        return logits, cache

    monkeypatch.setattr(transformer, "decode_step", stale)
    run.run_cell(smoke("chat"), 4, SECONDS, False, PEAKS, jax.devices()[0])
    out = _result(capsys)
    assert not out["correct"], out

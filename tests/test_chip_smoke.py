"""chip_smoke.py off the chip: it refuses to run without a TPU, its checks
run end to end at a smoke size, and its pass/fail judgement of the
readings."""
import dataclasses
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402


def test_exits_nonzero_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""  # no result line, nothing served


def test_checks_run_at_smoke_size(monkeypatch):
    """The one-chip path at a smoke size in interpret mode: every request
    finishes, the kernels match their oracles and each control fails its
    check.  Interpret mode emits no tpu_custom_call, so that is the one
    failure left."""
    from repro import configs
    from repro.configs.base import QuantConfig

    monkeypatch.setattr(chip_smoke, "MAX_LEN", 64)
    monkeypatch.setattr(chip_smoke, "PREFILL_CHUNK", 16)
    monkeypatch.setattr(chip_smoke, "DECODE_FILLS", (2, 3, 5, 8, 17, 32, 50, 64))
    monkeypatch.setattr(chip_smoke, "CHUNK_FILLS",
                        (16, 17, 20, 24, 32, 40, 50, 64))
    monkeypatch.setattr(chip_smoke, "DECODE_POSITIONS", tuple(range(8, 16)))
    qc = QuantConfig(w_bits=2, group_size=16, mode="ptq", backend="pallas")
    cfg = dataclasses.replace(
        configs.get_smoke(chip_smoke.ARCH, qc),
        kv_fmt="kv_int8", flash_decode=True, flash_prefill=True,
    )
    r = chip_smoke.serve_check(
        cfg, slots=8, max_len=64, prompt_lens=(8, 16, 24), new_tokens=4,
        chunk=16, n_requests=8,
    )
    assert r["finished"] == r["submitted"] == 8
    assert len(r["sites"]) == 15  # 7 block sites x 2 shapes + lm_head
    assert chip_smoke.failures(r) == [
        "an engine program holds no tpu_custom_call"
    ]


def _diff(max_rel=0.0, **over):
    return {"max_abs": max_rel, "max_rel": max_rel, "finite": True, **over}


def _readings(**over):
    r = {
        "finished": 8, "submitted": 8, "statuses": ["finished"] * 8,
        "left_in_engine": 0, "health_events": {"quarantined": 0},
        "kernels_prefill": 9, "kernels_decode": 9,
        "sites": {"blocks/mlp/gate@8": _diff(),
                  "lm_head@8": _diff(argmax_equal=True)},
        "site_control": _diff(1e-2),
        "attention": {"decode": _diff(), "prefill": _diff()},
        "attention_control": {"decode": _diff(1.0), "prefill": _diff(1.0)},
        "model": {"prefill": _diff(), "decode": _diff()},
        "model_control": {"prefill": _diff(0.1), "decode": _diff(0.1)},
    }
    r.update(over)
    return r


@pytest.mark.parametrize("over,expect", [
    ({}, None),
    ({"finished": 7, "statuses": ["finished"] * 7 + ["expired"]},
     "not finished"),
    ({"left_in_engine": 1}, "not finished"),
    ({"health_events": {"quarantined": 1}}, "quarantines"),
    ({"kernels_decode": 0}, "tpu_custom_call"),
    ({"model": {"prefill": _diff(), "decode": _diff(0.5)}}, "model decode"),
    ({"sites": {"lm_head@8": _diff(argmax_equal=False)}}, "argmax"),
    ({"sites": {"blocks/mlp/gate@8": _diff(1e-3)}}, "blocks/mlp/gate@8"),
    ({"sites": {"blocks/mlp/gate@8": _diff(finite=False)}}, "gate@8"),
    ({"site_control": _diff(1e-5)}, "site control"),
    ({"attention": {"decode": _diff(), "prefill": _diff(0.5)}},
     "attention prefill"),
    ({"attention_control": {"decode": _diff(1e-3), "prefill": _diff(1.0)}},
     "attention decode control"),
    ({"model_control": {"prefill": _diff(1e-3), "decode": _diff(0.1)}},
     "model prefill control"),
], ids=["pass", "expired", "left", "quarantine", "no_kernel", "rel",
        "argmax", "site", "nonfinite", "site_control", "attention",
        "attention_control", "model_control"])
def test_failures_judge_the_readings(over, expect):
    bad = chip_smoke.failures(_readings(**over))
    if expect is None:
        assert bad == []
    else:
        assert len(bad) == 1 and expect in bad[0]


def _four_chip_readings(**over):
    r = {
        "mesh_statuses": ["finished"] * 4,
        "one_device_statuses": ["finished"] * 4,
        "mesh_prefill_kernels": 10, "one_device_prefill_kernels": 11,
        "mesh_prefill_all_to_all": 22,
        "sites": {"blocks/attn/wo": _diff(), "experts": _diff()},
        "model": {"prefill": _diff(argmax_equal=True),
                  "decode": _diff(argmax_equal=True)},
    }
    r.update(over)
    return r


@pytest.mark.parametrize("over,expect", [
    ({}, None),
    ({"mesh_statuses": ["finished"] * 3 + ["failed"]}, "mesh: requests"),
    ({"one_device_prefill_kernels": 0}, "one_device: the prefill"),
    ({"mesh_prefill_all_to_all": 0}, "all-to-alls"),
    ({"sites": {"experts": _diff(1e-3)}}, "mesh experts"),
    ({"model": {"prefill": _diff(0.5, argmax_equal=True)}}, "mesh prefill"),
], ids=["pass", "unfinished", "no_kernel", "no_all_to_all", "site", "model"])
def test_four_chip_failures_judge_the_readings(over, expect):
    bad = chip_smoke.four_chip_failures(_four_chip_readings(**over))
    if expect is None:
        assert bad == []
    else:
        assert len(bad) == 1 and expect in bad[0]

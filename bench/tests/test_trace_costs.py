"""Trace reduction on a synthetic trace; kernel counts against hand-worked
shapes; the chip and peaks checks of ``run.main``."""
import os

import pytest

from bench import costs, run
from bench import trace as T
from bench.costs import transformer as M
from bench.loadgen import Dispatch, Window
from bench.readings import Readings, nearest_rank
from bench.cell import metric_reader, _load_json, BENCH

CONFIG = _load_json(os.path.join(BENCH, "configs", "phi4-mini-ternary-kv8.json"))
PEAKS = _load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
MS = 1e6  # ns per ms


def _synthetic():
    """Host spans and device events, in ns:
    wait 0-10 ms; generate step 10-40 ms with its tick 12-32 (a qmm
    kernel 12-22, a flash kernel 22-30, a fusion 30-32); prefill step
    40-70 ms with its program 45-65 (qmm 45-60, flash 60-65)."""
    E = T.Event
    ops = [E("%while.3 = () while()", 12 * MS, 32 * MS, T.PARENT),
           E("%ternary_matmul_fused.1 = f32[] custom-call()", 12 * MS,
             22 * MS, "fused_qmm_call"),
           E("%closed_call.2 = f32[] custom-call()", 22 * MS, 30 * MS,
             "flash_attend"),
           E("%add_fusion.3 = f32[] fusion()", 30 * MS, 32 * MS),
           E("%ternary_matmul_fused.4 = f32[] custom-call()", 45 * MS,
             60 * MS, "fused_qmm_call"),
           E("%closed_call.5 = f32[] custom-call()", 60 * MS, 65 * MS,
             "flash_attend")]
    modules = [E("jit__tick_fn", 12 * MS, 32 * MS),
               E("jit__lambda", 45 * MS, 65 * MS)]
    spans = [E("wait_arrival", 0, 10 * MS), E("step", 10 * MS, 40 * MS),
             E("step", 40 * MS, 70 * MS)]
    return T.Reduced(ops, modules, spans)


def _readings(red):
    recs = []
    win = Window(0.0, 1.0, recs, [
        Dispatch("generate", 0.1, 0.2, [100, 200]),
        Dispatch("prefill", 0.3, 0.4, [256], 0, 256)], 0.0)
    mix = {"engine": {"prefill_chunk": 256}}
    return Readings(win, red, CONFIG, mix, PEAKS)


def test_busy_idle_and_kernel_sums():
    r = _readings(_synthetic())
    assert r.span == (0, 70 * MS)
    assert r.busy == [(12 * MS, 32 * MS), (45 * MS, 65 * MS)]
    assert r.busy_s() == pytest.approx(0.040)
    assert r.kernel_seconds("fused_qmm_call") == pytest.approx(0.025)
    assert r.kernel_seconds("flash_attend") == pytest.approx(0.013)
    # serve: the 10 ms wait_arrival span is left out: 40 busy of 60
    assert metric_reader("device_idle_share.serve")(r) == pytest.approx(100 / 3)
    assert metric_reader("device_idle_share.offline")(r) == pytest.approx(300 / 7)
    # host time: (30 - 20) and (30 - 20) ms of the two step spans
    assert metric_reader("host_ms_per_dispatch.serve")(r) == pytest.approx(10.0)
    assert metric_reader("decode_tick_ms.serve")(r) == pytest.approx(20.0)
    assert metric_reader("prefill_chunk_ms.serve")(r) == pytest.approx(20.0)
    bd = T.breakdown(r.red)
    assert bd["device_ops"][0] == ["fused_qmm_call", pytest.approx(0.025)]
    # idle 0-12 ms (in the wait), 32-45 (the generate step's host tail)
    # and 65-70 (the prefill step's), longest first
    assert bd["idle_gaps"] == [["step", pytest.approx(0.013)],
                               ["wait_arrival", pytest.approx(0.012)],
                               ["step", pytest.approx(0.005)]]


def test_roofline_is_least_time_over_kernel_time():
    r = _readings(_synthetic())
    least = sum(c["qmm_least_s"] for c in r.counts)
    assert metric_reader("qmm_roofline.serve")(r) == pytest.approx(
        100 * least / 0.025)


def test_kernel_class_names():
    """Names as a v5e trace gives them (HLO instruction text)."""
    cc = "%{} = f32[256,3072]{{1,0}} custom-call(bf16[256,3072] %fusion.37)"
    assert T.kernel_class(cc.format("ternary_matmul_fused.49")) == \
        "fused_qmm_call"
    assert T.kernel_class(cc.format("int8_matmul_fused")) == "fused_qmm_call"
    assert T.kernel_class(cc.format("closed_call.13")) == "flash_attend"
    assert T.kernel_class(
        "%convert_fusion.2 = f32[256] fusion(f32[256] %ternary_matmul_fused.49)"
    ) == ""
    assert T.kernel_class('%custom-call.2 = s8[32] custom-call(), '
                          'custom_call_target="AllocateBuffer"') == ""
    assert T.base_name("%fusion.12 = f32[8] fusion(x)") == "fusion"


def test_qmm_counts_by_hand():
    # ternary 3072 x 8192 at M = 32, clusters of 16
    ops, nbytes = costs.qmm(32, 3072, 8192, "ternary", 16)
    assert ops == 2 * 32 * 3072 * 8192
    weights = 3072 * 8192 // 4 + (3072 // 16) * 8192
    assert nbytes == weights + 32 * (3072 + 8192) * 2
    t, bound = costs.least_seconds(ops, nbytes, PEAKS["int8_ops"],
                                   PEAKS["hbm_bytes_per_s"])
    assert bound == "bytes" and t == pytest.approx(nbytes / 819e9)


def test_flash_decode_counts_by_hand():
    # one decode row over a 4096-token kv_int8 cache filled to 1000:
    # the read stops at the fill level, whatever the allocation
    ops, nbytes = costs.attention([1000], [1000], 24, 8, 128, "kv_int8")
    assert ops == 4 * 24 * 128 * 1000
    assert nbytes == 1000 * 2 * 8 * (128 + 1) + 2 * 24 * 128 * 2


def test_dispatch_counts_prefill_projects_one_head_row():
    c = M.dispatch(CONFIG, PEAKS, "prefill", [256], 0, 256)
    per_layer = sum(2 * k * n for p, k, n, *_ in M.sites(CONFIG)
                    if p != "lm_head")
    head = 2 * 3072 * 200192
    attn = 4 * 24 * 128 * sum(range(1, 257))
    assert c["ops"] == pytest.approx(256 * 32 * per_layer + head + 32 * attn)


def test_nearest_rank():
    assert nearest_rank(list(range(1, 11)), 90) == 9
    assert nearest_rank([5.0], 95) == 5.0


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("dev", [_Dev("cpu", "cpu"),
                                 _Dev("tpu", "TPU v99 unknown")])
def test_main_refuses_without_a_known_chip(monkeypatch, capsys, dev):
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    rc = run.main(["--workload", "phi4-chat", "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""

"""The program's spans and scopes read from a synthetic trace: ``extend``'s
new fields, device time by scope, idle time by engine span, and each
reading on hand-worked numbers; the existing readers read the same with
the new fields present."""
import dataclasses

import pytest

from bench import program_trace as P
from bench import trace as T
from bench.cell import metric_reader
from bench.tests.test_trace_costs import MS, _readings, _synthetic

TICK = "jit(_tick_fn)/while/body"
# op name (as trace.reduce keeps it) -> its op_name path
PATHS = {
    "%ternary_matmul_fused.1 = f32[] custom-call()":
        f"{TICK}/qdense/wq/jit(ternary_matmul_fused)/fused_qmm_ternary",
    "%closed_call.2 = f32[] custom-call()":
        f"{TICK}/attention/jit(flash_attend)/flash_attend/pallas_call",
    "%add_fusion.3 = f32[] fusion()": f"{TICK}/kv_write/select_n",
    "%ternary_matmul_fused.4 = f32[] custom-call()":
        "jit(_prefill_fn)/while/body/qdense/down/fused_qmm_ternary",
}


def _hlo(module, lines):
    body = "\n".join(f'  {text}, metadata={{op_name="{path}" '
                     'stack_frame_id=3}' for text, path in lines)
    return f"HloModule {module}, is_scheduled=true\n\nENTRY %main {{\n{body}\n}}\n"


# the optimized HLO of the programs that ran: the tick, and the prefill
# at two shapes, of which the synthetic trace ran the second
TEXTS = [
    _hlo("jit__tick_fn", [(k, v) for k, v in PATHS.items() if "tick" in v]
         + [("ROOT %while.3 = () while()", f"{TICK}/while")]),
    _hlo("jit__lambda", [("%ternary_matmul_fused.4 = f32[8] custom-call()",
                          "jit(_prefill_fn)/qdense/up/fused_qmm_ternary")]),
    _hlo("jit__lambda", [(k, v) for k, v in PATHS.items() if "prefill" in v]),
]
EXISTING = ("device_idle_share.serve", "device_idle_share.offline",
            "host_ms_per_dispatch.serve", "decode_tick_ms.serve",
            "prefill_chunk_ms.serve", "prefill_chunk_ms.offline",
            "qmm_roofline.serve", "qmm_roofline.offline",
            "attn_roofline.serve", "attn_roofline.offline",
            "step_mfu.serve", "mfu.offline")


@dataclasses.dataclass
class _Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Line:
    name: str
    events: list


@dataclasses.dataclass
class _Plane:
    name: str
    lines: list


def _span(name, a, b, **args):
    return _Ev(name, a * MS, (b - a) * MS, list(args.items()))


# the engine's spans inside the load generator's two steps (10-40, 40-70 ms)
PROGRAM = [
    _span("engine.step", 10, 40),
    _span("engine.schedule", 10, 11),
    _span("engine.generate", 11, 13, tick=7, rows=12, slots=16, fill=3000,
          capacity=65536),
    _span("engine.sync", 13, 33),
    _span("engine.commit", 33, 36),
    _span("engine.step", 40, 70),
    _span("engine.schedule", 40, 44),
    _span("engine.admit", 41, 43, uid=5, slot=3, queue_wait_ms=12.5),
    _span("engine.prefill", 44, 46, uid=5, start=0, size=256, fill=256,
          capacity=4096),
    _span("engine.sync", 46, 66),
    _span("engine.commit", 66, 68),
]


def _planes():
    return [_Plane("/host:CPU", [_Line("python", PROGRAM + [
        _span("step", 10, 40), _span("jit_compile", 0, 1)])])]


def _extended():
    return P.extend(_synthetic(), _planes(), TEXTS)


def test_extend_adds_scopes_and_spans_and_keeps_the_rest():
    base, red = _synthetic(), _extended()
    assert isinstance(red, T.Reduced)
    assert [(o.name, o.start, o.end, o.kind) for o in red.ops] == \
        [(o.name, o.start, o.end, o.kind) for o in base.ops]
    assert red.modules == base.modules and red.spans == base.spans
    want = {**PATHS, "%while.3 = () while()": f"{TICK}/while"}
    assert [o.scope for o in red.ops] == [want.get(o.name, "") for o in base.ops]
    assert [s.name for s in red.program] == [e.name for e in PROGRAM]
    gen = red.program[2]
    assert gen.args == {"tick": 7, "rows": 12, "slots": 16, "fill": 3000,
                        "capacity": 65536}
    assert (gen.start, gen.end) == (11 * MS, 13 * MS)


def test_existing_readers_read_the_same_with_the_new_fields():
    plain, extended = _readings(_synthetic()), _readings(_extended())
    for name in EXISTING:
        assert metric_reader(name)(extended) == metric_reader(name)(plain), name
    assert T.breakdown(extended.red) == T.breakdown(plain.red)
    assert [k for _, k in T.classify_modules(extended.red)] == \
        [k for _, k in T.classify_modules(plain.red)]
    assert T.window(extended.red) == T.window(plain.red)


def test_hlo_paths_of_an_optimized_module():
    module, instrs = P.hlo_paths(TEXTS[0] + (
        '  %tuple_fusion.7 = (s8[2,4]{1,0}, s8[2]{0}) fusion(%p), kind=kLoop, '
        'metadata={op_name="kv_write/select_n"}\n'
        '  %param.1 = f32[8]{0} parameter(0)\n'))
    assert module == "jit__tick_fn"
    assert instrs["add_fusion.3"] == ("f32[]", f"{TICK}/kv_write/select_n")
    assert instrs["tuple_fusion.7"] == ("(s8[2,4]{1,0}, s8[2]{0})",
                                       "kv_write/select_n")
    assert instrs["param.1"] == ("f32[8]{0}", "")


def test_extend_without_texts_leaves_every_op_unscoped():
    red = P.extend(_synthetic(), _planes())
    assert [o.scope for o in red.ops] == [""] * len(red.ops)
    assert set(P.scope_seconds(red)) == {"unscoped"}


@pytest.mark.parametrize("path,want", [
    (f"{TICK}/qdense/wq/jit(ternary_matmul_fused)/fused_qmm_ternary",
     "qdense/wq"),
    ("qdense/lm_head/dot_general", "qdense/lm_head"),
    ("kv_write/jit(_where)/select_n", "kv_write"),
    (f"{TICK}/attention/flash_attend/pallas_call", "attention"),
    ("jit(_tick_fn)/guardrail/reduce_max", "guardrail"),
    ("jit(_first_token)/sample/argmax", "sample"),
    ("moe_dispatch/qdense/router/dot_general", "qdense/router"),
    ("moe_dispatch/scatter-add", "moe_dispatch"),
    (f"{TICK}/rms_norm/mul", "unscoped"),
    ("", "unscoped"),
])
def test_layer_of_a_path(path, want):
    assert P.layer(path) == want


def test_device_time_by_scope():
    got = P.scope_seconds(_extended())
    # the while parent is left out; each kernel and fusion by its scope,
    # the prefill's flash call (no path) as unscoped
    assert got == {"qdense/wq": pytest.approx(0.010),
                   "qdense/down": pytest.approx(0.015),
                   "attention": pytest.approx(0.008),
                   "unscoped": pytest.approx(0.005),
                   "kv_write": pytest.approx(0.002)}
    assert list(got)[0] == "qdense/down"  # longest first


def test_idle_by_innermost_span():
    # idle 0-12, 32-45 and 65-70 ms of the window 0-70
    got = P.idle_by_span(_extended())
    want = {"outside_engine": 10, "engine.step": 6, "engine.commit": 5,
            "engine.schedule": 3, "engine.admit": 2, "engine.sync": 2,
            "engine.generate": 1, "engine.prefill": 1}
    assert got == {k: pytest.approx(v * 1e-3) for k, v in want.items()}
    assert sum(got.values()) == pytest.approx(0.030)


def test_readings_by_hand():
    red = _extended()
    # each step 30 ms, less its 20 ms sync
    assert P.engine_host_ms(red) == pytest.approx(10.0)
    assert P.decode_rows_share(red) == pytest.approx(75.0)
    assert P.kv_use_share(red) == pytest.approx(100 * 3000 / 65536)
    assert P.kv_use_share(red, ("engine.generate", "engine.prefill")) == \
        pytest.approx(100 * 3256 / 69632)
    # the tick program's kv_write fusion, 30-32 ms
    assert P.kv_write_ms(red) == pytest.approx(2.0)
    assert P.spans(red, "engine.admit")[0].args["queue_wait_ms"] == 12.5


def test_readings_absent_from_a_trace_without_them():
    red = _synthetic()  # names only: no spans, no scopes, no insert program
    plain = P.Reduced(red.ops, red.modules, red.spans)
    for read in (P.engine_host_ms, P.decode_rows_share, P.kv_use_share,
                 P.kv_write_ms, P.insert_ms):
        assert read(plain) is None
    assert P.idle_by_span(plain) == {"outside_engine": pytest.approx(0.030)}
    assert metric_reader("insert_ms.serve")(_readings(red)) is None


def test_insert_ms_reads_the_named_insert_programs():
    red = _synthetic()
    red.modules += [T.Event("jit__insert_fn", 66 * MS, 67 * MS),
                    T.Event("jit__insert_fn", 68 * MS, 71 * MS),
                    T.Event("jit__insert_fn", 80 * MS, 81 * MS)]  # after
    red.modules.sort(key=lambda m: m.start)
    assert P.insert_ms(red) == pytest.approx(2.0)
    assert metric_reader("insert_ms.serve")(_readings(red)) == \
        pytest.approx(2.0)


def test_program_texts_of_a_smoke_engine(monkeypatch):
    """Every program the engine dispatches, compiled, with the layer
    scopes in its instructions' op_name paths."""
    import os

    from repro import configs
    from bench import cell as C, run

    here = os.path.dirname(os.path.abspath(__file__))
    monkeypatch.setattr(configs, "get_config", configs.get_smoke)
    config = C._load_json(os.path.join(here, "smoke-phi4.json"))
    mix = C._load_json(os.path.join(here, "smoke-chat.json"))
    eng = run.build_engine(config, mix, 7)
    texts = P.program_texts(eng)
    modules = [P.hlo_paths(t)[0] for t in texts]
    # the tick, the prefill at 16, 1, 2, 4 and 8 tokens, the insert
    assert modules == ["jit__tick_fn"] + ["jit__prefill_fn"] * 5 + \
        ["jit__insert_fn"]
    for text in texts[:2]:
        found = {P.layer(op) for _, op in P.hlo_paths(text)[1].values()}
        assert {"qdense/wq", "qdense/down", "qdense/lm_head", "kv_write",
                "attention"} <= found

"""The staged engine's profiler spans and its device ops' scopes.

A ``StagedEngine`` serves a few requests of a smoke model under
``jax.profiler`` on the CPU; the trace's ``engine.*`` host spans must say
what each step dispatched, with the counts the engine kept, and the
programs' HLO must carry the layer scopes the trace reduction reads
(``docs/SERVING.md``, "Tracing")."""
import glob
import re
import tempfile

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro import configs
from repro.kernels.flash_prefill import live_kv_steps
from repro.models import build_model, kv_cache
from repro.serving import Request, SchedulerConfig, StagedEngine, chunk_plan

CHUNK, MAX_LEN, SLOTS = 8, 256, 2
# prompts across the chunk boundary; three requests on two slots, so one
# waits for a slot while the others generate; the long one reaches the
# cache's second flash tile (128 tokens)
LENS, MAX_NEW = (5, 150, 8), (4, 3, 5)
DISPATCH = ("engine.prefill", "engine.generate")


@pytest.fixture(scope="module")
def engine():
    api = build_model(configs.get_smoke("phi4-mini-3.8b"))
    return StagedEngine(api, api.init(jax.random.PRNGKey(0)), n_slots=SLOTS,
                        max_len=MAX_LEN,
                        sched=SchedulerConfig(prefill_chunk=CHUNK))


@pytest.fixture(scope="module")
def served(engine):
    """Serve every request under the profiler, then step once more with
    nothing to do; returns the requests, what each step dispatched (from
    the engine's counts and the requests' progress) and the spans."""
    eng = engine
    reqs = [Request(uid=100 + i, prompt=[(7 * j + i) % 50 + 1 for j in range(n)],
                    max_new_tokens=m)
            for i, (n, m) in enumerate(zip(LENS, MAX_NEW))]
    for r in reqs:
        eng.submit(r, strict=True)
    steps = []
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    try:
        while eng._has_work():
            before = dict(eng.counts)
            out0 = [len(r.output) for r in reqs]
            eng.step()
            # (tokens gained, tokens its cache row held after the step)
            gained = [(len(r.output) - n, len(r.prompt) + len(r.output) - 1)
                      for r, n in zip(reqs, out0) if len(r.output) > n]
            if eng.counts["generate_ticks"] != before["generate_ticks"]:
                kind = "generate"
            elif eng.counts["prefill_chunks"] != before["prefill_chunks"]:
                kind = "prefill"
            else:
                kind = None
            steps.append((kind, gained))
        with jax.profiler.TraceAnnotation("idle_probe"):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine.") or ev.name == "idle_probe":
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns, dict(ev.stats)))
    spans.sort(key=lambda s: s[1])
    return reqs, steps, spans


def _named(spans, *names):
    return [s for s in spans if s[0] in names]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_one_dispatch_span_inside_each_dispatching_step(served):
    _, steps, spans = served
    step_spans = _named(spans, "engine.step")
    assert len(step_spans) == len(steps) + 1  # the idle step too
    dispatches = _named(spans, *DISPATCH)
    for d in dispatches:
        assert sum(_inside(d, s) for s in step_spans) == 1
    for (kind, _), s in zip(steps, step_spans):
        held = [d[0] for d in dispatches if _inside(d, s)]
        assert held == ([] if kind is None else [f"engine.{kind}"])
        # the schedule runs in every step, before the dispatch
        assert len([x for x in _named(spans, "engine.schedule")
                    if _inside(x, s)]) == 1


def test_generate_spans_count_ticks_rows_and_fill(served, engine):
    _, steps, spans = served
    gen = _named(spans, "engine.generate")
    assert len(gen) == engine.counts["generate_ticks"]
    ticks = [g for kind, g in steps if kind == "generate"]
    assert sum(s[3]["rows"] for s in gen) == sum(n for g in ticks for n, _ in g)
    for s, g in zip(gen, ticks):
        args = s[3]
        assert args["rows"] == len(g)
        assert args["slots"] == SLOTS
        assert args["capacity"] == SLOTS * MAX_LEN
        assert args["fill"] == sum(held for _, held in g)
    assert [s[3]["tick"] for s in gen] == sorted(s[3]["tick"] for s in gen)


def test_dispatch_spans_count_live_kv_tiles(served, engine):
    """``kv_tiles`` / ``kv_tiles_grid`` are the flash grid's live and total
    KV steps for the rows a dispatch runs: every slot at its position in a
    tick (idle and reserved slots at 0), the chunk at its start in a
    prefill."""
    _, steps, spans = served
    cfg = engine.api.cfg
    group, fmt = cfg.n_heads // cfg.n_kv_heads, kv_cache.resolve_kv_fmt(cfg)

    def counted(q_start, valid, s):
        live, grid = live_kv_steps(q_start, valid, 2**30, s=s, g=group,
                                   t=MAX_LEN, fmt=fmt)
        return {"kv_tiles": live, "kv_tiles_grid": grid}

    def read(args):
        return {k: args[k] for k in ("kv_tiles", "kv_tiles_grid")}

    ticks = [g for kind, g in steps if kind == "generate"]
    gen = _named(spans, "engine.generate")
    for span, g in zip(gen, ticks):
        pos = [held - 1 for _, held in g] + [0] * (SLOTS - len(g))
        assert read(span[3]) == counted(pos, [p + 1 for p in pos], 1)
    pre = [s[3] for s in _named(spans, "engine.prefill")]
    for a in pre:
        assert read(a) == counted([a["start"]], [a["start"] + a["size"]],
                                  a["size"])
    for a in [s[3] for s in gen] + pre:
        assert 0 < a["kv_tiles"] <= a["kv_tiles_grid"]
    # the long request's rows past token 128 run both tiles, the others one
    assert {a["kv_tiles"] for a in (s[3] for s in gen)} >= {SLOTS, SLOTS + 1}
    assert any(a["kv_tiles"] < a["kv_tiles_grid"] for a in pre)


def test_prefill_spans_follow_chunk_plan_and_carry_uid(served):
    reqs, _, spans = served
    for r in reqs:
        mine = [s for s in _named(spans, "engine.prefill", "engine.admit",
                                  "engine.insert") if s[3]["uid"] == r.uid]
        names = [s[0] for s in mine]
        plan = chunk_plan(len(r.prompt), CHUNK)
        assert names == (["engine.admit"] + ["engine.prefill"] * len(plan)
                         + ["engine.insert"])
        starts = [sum(plan[:i]) for i in range(len(plan))]
        pre = [s[3] for s in mine if s[0] == "engine.prefill"]
        assert [(a["start"], a["size"]) for a in pre] == list(zip(starts, plan))
        assert all(a["fill"] == a["start"] + a["size"] for a in pre)
        assert all(a["capacity"] == MAX_LEN for a in pre)
        assert 0 <= mine[-1][3]["slot"] < SLOTS
        assert mine[0][3]["queue_wait_ms"] >= 0
    # every span that names a request names one of these
    uids = {s[3]["uid"] for s in spans if "uid" in s[3]}
    assert uids == {r.uid for r in reqs}


def test_idle_step_emits_no_dispatch_span(served):
    _, _, spans = served
    (probe,) = _named(spans, "idle_probe")
    inside = [s[0] for s in spans if _inside(s, probe) and s is not probe]
    assert inside == ["engine.step", "engine.schedule"]


def _scopes(hlo: str) -> set:
    """Every scope an op_name of ``hlo`` passes through, as paths of
    one or two components (``kv_write``, ``qdense/wq``)."""
    out = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo):
        parts = name.split("/")
        out.update(parts)
        out.update("/".join(p) for p in zip(parts, parts[1:]))
    return out


def test_programs_carry_the_layer_scopes(engine):
    eng = engine
    tokens, pos = eng._device_operands()
    tick = eng._decode_step.lower(
        eng.params, tokens, pos, eng.cache, eng.key, jnp.int32(-1),
        jnp.float32(0.0)).as_text(dialect="hlo", debug_info=True)
    prefill = eng._prefill_step.lower(
        eng.params, jnp.zeros((1, CHUNK), jnp.int32), jnp.int32(0),
        eng.api.init_cache(1, MAX_LEN)).as_text(dialect="hlo", debug_info=True)
    want = {"qdense/wq", "qdense/down", "qdense/lm_head", "kv_write",
            "attention"}
    assert want <= _scopes(prefill)
    assert want | {"sample", "guardrail"} <= _scopes(tick)
    assert "jit(_tick_fn)/" in tick and "jit(_prefill_fn)/" in prefill

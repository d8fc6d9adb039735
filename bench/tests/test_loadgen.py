"""The load generator reads each dispatch from counts and the requests'
progress alone; here the engine's own state, read as each stage is
dispatched, says what it was, and the two have to agree."""
import os

import pytest

from bench import cell as C
from bench import run, traffic
from bench.loadgen import LoadGen

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("mix", ["chat", "longdoc"])
def test_dispatches_match_the_engine(monkeypatch, mix):
    from repro import configs

    monkeypatch.setattr(configs, "get_config", configs.get_smoke)
    config = C._load_json(os.path.join(HERE, "smoke-phi4.json"))
    m = C._load_json(os.path.join(HERE, f"smoke-{mix}.json"))
    eng = run.build_engine(config, m, 7)
    truth = []
    prefill, generate = eng._prefill_dispatch, eng._generate_dispatch

    def prefill_dispatch():
        truth.append(("prefill",) + tuple(eng._pf.next_chunk()))
        return prefill()

    def generate_dispatch():
        reserved = eng._pf.slot if eng._pf is not None else None
        truth.append(("generate", sorted(
            int(eng.slot_pos[s]) + 1 for s, r in enumerate(eng.slot_req)
            if r is not None and s != reserved)))
        return generate()

    monkeypatch.setattr(eng, "_prefill_dispatch", prefill_dispatch)
    monkeypatch.setattr(eng, "_generate_dispatch", generate_dispatch)
    specs = traffic.generate(m, 11, 3.0, config["vocab_size"])
    gen = LoadGen(eng)
    if m["loop"] == "closed":
        specs = specs[run.fill_slots(gen, specs):]
    win = gen.run(specs, 3.0, loop=m["loop"])
    seen = []
    for d in win.dispatches:
        if d.kind == "generate":
            seen.append(("generate", sorted(d.valid)))
        else:
            seen.append(("prefill", d.start, d.size))
    assert {k[0] for k in seen} == {"generate", "prefill"}
    assert seen == truth

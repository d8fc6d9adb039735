"""Serving engine: decode/forward parity, continuous batching, PTQ serving."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import QuantConfig
from repro.models import build_model, quantize_model_params
from repro.serving import Request, SamplerConfig, ServingEngine
from repro.serving.sampler import sample

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ["qwen3-8b", "gemma3-12b", "falcon-mamba-7b", "zamba2-7b"])
def test_engine_greedy_matches_full_forward(arch):
    cfg = configs.get_smoke(arch)
    api = build_model(cfg)
    params = api.init(KEY)
    prompt = [5, 9, 2, 7, 11]
    eng = ServingEngine(api, params, n_slots=2, max_len=16)
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=1))
    out = eng.run()[0].output[0]
    logits = api.forward(params, {"tokens": jnp.asarray([prompt])})
    ref = int(jnp.argmax(logits[0, -1]))
    assert out == ref


def test_engine_multi_token_matches_sequential_forward():
    """3 greedy tokens from the engine == 3 rounds of full re-forward."""
    cfg = configs.get_smoke("qwen3-8b")
    api = build_model(cfg)
    params = api.init(KEY)
    prompt = [3, 1, 4]
    eng = ServingEngine(api, params, n_slots=1, max_len=16)
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=3))
    got = eng.run()[0].output

    seq = list(prompt)
    want = []
    for _ in range(3):
        logits = api.forward(params, {"tokens": jnp.asarray([seq])})
        t = int(jnp.argmax(logits[0, -1]))
        want.append(t)
        seq.append(t)
    assert got == want


def test_continuous_batching_isolation():
    """Requests admitted mid-flight do not perturb running slots."""
    cfg = configs.get_smoke("qwen3-8b")
    api = build_model(cfg)
    params = api.init(KEY)

    solo = ServingEngine(api, params, n_slots=1, max_len=32)
    solo.submit(Request(uid=0, prompt=[7, 7, 3], max_new_tokens=4))
    want = solo.run()[0].output

    eng = ServingEngine(api, params, n_slots=3, max_len=32)
    eng.submit(Request(uid=0, prompt=[7, 7, 3], max_new_tokens=4))
    eng.step()
    eng.submit(Request(uid=1, prompt=[1, 2], max_new_tokens=4))
    eng.submit(Request(uid=2, prompt=[9], max_new_tokens=2))
    done = {r.uid: r.output for r in eng.run()}
    assert done[0] == want


def test_slot_reuse_after_finish():
    cfg = configs.get_smoke("qwen3-8b")
    api = build_model(cfg)
    params = api.init(KEY)
    eng = ServingEngine(api, params, n_slots=1, max_len=16)
    for i in range(3):
        eng.submit(Request(uid=i, prompt=[i + 1, 2], max_new_tokens=2))
    done = eng.run()
    assert sorted(r.uid for r in done) == [0, 1, 2]
    assert all(len(r.output) == 2 for r in done)


def test_ptq_serving_pipeline():
    cfg = configs.get_smoke(
        "qwen3-8b", QuantConfig(w_bits=2, group_size=16, mode="ptq", backend="xla")
    )
    api = build_model(cfg)
    params = api.init(KEY)
    qparams = quantize_model_params(params, api.ctx.policy)
    eng = ServingEngine(api, qparams, n_slots=2, max_len=16)
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=4))
    done = eng.run()
    assert len(done) == 1 and len(done[0].output) == 4


def test_eos_stops_generation():
    cfg = configs.get_smoke("qwen3-8b")
    api = build_model(cfg)
    params = api.init(KEY)
    # find the greedy first token, then use it as "eos"
    eng = ServingEngine(api, params, n_slots=1, max_len=16)
    eng.submit(Request(uid=0, prompt=[5, 6], max_new_tokens=8))
    first = eng.run()[0].output[0]
    eng2 = ServingEngine(api, params, n_slots=1, max_len=16)
    eng2.submit(Request(uid=0, prompt=[5, 6], max_new_tokens=8, eos_id=first))
    out = eng2.run()[0].output
    assert out == [first]


def test_submit_rejects_overlong_prompt():
    """A prompt that cannot fit max_len comes back ``rejected`` with a
    reason (one bad client must not take the serve loop down), never queued
    to silently finish done=True with truncated/empty output.
    ``strict=True`` restores the loud raise-at-submit behavior."""
    cfg = configs.get_smoke("qwen3-8b")
    api = build_model(cfg)
    params = api.init(KEY)
    eng = ServingEngine(api, params, n_slots=1, max_len=8)
    for uid, prompt in ((0, list(range(8))), (1, list(range(20)))):
        r = eng.submit(Request(uid=uid, prompt=prompt, max_new_tokens=2))
        assert r.status == "rejected" and "max_len" in r.reason
        assert not r.done and len(eng.queue) == 0
    r = eng.submit(Request(uid=2, prompt=[], max_new_tokens=2))
    assert r.status == "rejected" and "empty" in r.reason
    # strict mode: the original raise-on-malformed contract
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(Request(uid=3, prompt=list(range(8)), max_new_tokens=2),
                   strict=True)
    assert eng.stats()["health"]["events"]["rejected"] == 3
    eng.submit(Request(uid=4, prompt=list(range(7)), max_new_tokens=1))  # fits
    assert len(eng.run()) == 1


def test_queue_depth_and_admission_ticks():
    """The request queue is a deque reporting depth + per-request admission
    tick through stats()."""
    from collections import deque

    cfg = configs.get_smoke("qwen3-8b")
    api = build_model(cfg)
    params = api.init(KEY)
    eng = ServingEngine(api, params, n_slots=1, max_len=16)
    assert isinstance(eng.queue, deque)
    for i in range(3):
        eng.submit(Request(uid=i, prompt=[i + 1, 2], max_new_tokens=1))
    s0 = eng.stats()
    assert s0["queued"] == 3 and s0["tick"] == 0
    eng.step()
    s1 = eng.stats()
    assert s1["queued"] == 2  # one admitted into the single slot
    assert s1["admitted_tick"] == [0]  # admitted before the first tick ran
    assert s1["tick"] == 1
    done = eng.run()
    # FIFO admission order survives the deque swap, and later requests
    # record later admission ticks
    ticks = [r.admitted_tick for r in sorted(done, key=lambda r: r.uid)]
    assert ticks == sorted(ticks) and ticks[0] == 0
    assert eng.stats()["queued"] == 0


def test_lockstep_run_budget_reports_leftover():
    """run(max_ticks) expiry must not silently abandon work: every
    submitted request is accounted for in finished + leftover()."""
    cfg = configs.get_smoke("qwen3-8b")
    api = build_model(cfg)
    params = api.init(KEY)
    eng = ServingEngine(api, params, n_slots=1, max_len=32)
    for i in range(3):
        eng.submit(Request(uid=i, prompt=[1, 2, 3, 4], max_new_tokens=8))
    done = eng.run(max_ticks=2)
    left = eng.leftover()
    assert done == [] and len(left["in_flight"]) == 1 and len(left["queued"]) == 2
    assert all(not r.done for r in left["in_flight"] + left["queued"])
    drained = eng.drain()
    assert {r.uid for r in drained["in_flight"] + drained["queued"]} == {0, 1, 2}
    assert eng.leftover() == {"in_flight": [], "queued": []}
    assert eng.stats()["active"] == 0 and eng.stats()["queued"] == 0


def test_ssm_slot_reuse_no_stale_state():
    """Recurrent state is NOT masked by cache positions the way stale KV
    rows are: a reused slot must be cleared on admission, or the previous
    occupant's SSM state leaks into the new request's tokens."""
    cfg = configs.get_smoke("falcon-mamba-7b")
    api = build_model(cfg)
    params = api.init(KEY)
    probe = [5, 9, 2]

    fresh = ServingEngine(api, params, n_slots=1, max_len=16)
    fresh.submit(Request(uid=0, prompt=list(probe), max_new_tokens=3))
    want = fresh.run()[0].output

    eng = ServingEngine(api, params, n_slots=1, max_len=16)
    eng.submit(Request(uid=0, prompt=[13, 8, 8, 8, 1], max_new_tokens=4))
    eng.submit(Request(uid=1, prompt=list(probe), max_new_tokens=3))
    done = {r.uid: r.output for r in eng.run()}
    assert done[1] == want


def test_sampler_modes():
    logits = jnp.asarray([[0.0, 5.0, 1.0, -2.0]])
    assert int(sample(KEY, logits, SamplerConfig(temperature=0.0))[0]) == 1
    t = sample(KEY, logits, SamplerConfig(temperature=1.0, top_k=2))
    assert int(t[0]) in (1, 2)
    counts = set()
    for i in range(20):
        counts.add(int(sample(jax.random.PRNGKey(i), logits, SamplerConfig(temperature=5.0))[0]))
    assert len(counts) > 1  # high temperature actually samples


def test_int8_kv_cache_greedy_parity():
    """DFP-quantized KV cache (beyond-paper) preserves greedy decode."""
    import dataclasses

    cfg = configs.get_smoke("qwen3-8b")
    api = build_model(cfg)
    params = api.init(KEY)
    api8 = build_model(dataclasses.replace(cfg, kv_bits=8))

    prompt = jnp.asarray([[5, 9, 2, 7, 11, 3]])
    l_ref, c_ref = api.prefill(params, {"tokens": prompt}, api.init_cache(1, 16))
    l_q, c_q = api8.prefill(params, {"tokens": prompt}, api8.init_cache(1, 16))
    assert c_q["k"].dtype == jnp.int8 and "ke" in c_q
    t1 = jnp.argmax(l_ref[:, -1:], -1).astype(jnp.int32)
    t2 = jnp.argmax(l_q[:, -1:], -1).astype(jnp.int32)
    assert int(t1[0, 0]) == int(t2[0, 0])
    for i in range(3):
        l_ref, c_ref = api.decode(params, t1, jnp.int32(6 + i), c_ref)
        l_q, c_q = api8.decode(params, t2, jnp.int32(6 + i), c_q)
        t1 = jnp.argmax(l_ref[:, -1:], -1).astype(jnp.int32)
        t2 = jnp.argmax(l_q[:, -1:], -1).astype(jnp.int32)
        assert int(t1[0, 0]) == int(t2[0, 0])


@pytest.mark.parametrize(
    "limits,fails",
    [([], True), (["--deadline-ms", "60000"], False)],
    ids=["no_limits", "admission_limit"],
)
def test_serve_cli_exit_code_counts_unfinished(monkeypatch, limits, fails):
    """serve.py fails when a request does not finish -- here every prompt
    is rejected for not fitting max_len -- unless chaos or an admission
    limit was asked for, where lost requests are expected."""
    import sys

    from repro.launch import mesh, serve

    # keep this process's jax config as it was: no persistent cache
    monkeypatch.setattr(mesh, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "phi4-mini-3.8b", "--smoke", "--requests", "2",
        "--max-len", "6", *limits,
    ])
    if fails:
        with pytest.raises(SystemExit, match="2 of 2 requests did not finish"):
            serve.main()
    else:
        serve.main()

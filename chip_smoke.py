"""Bring-up check on a TPU: serve Phi-4-mini through the Pallas integer pipeline.

One process, one chip, random weights from a seed, no downloads:

  1. build phi4-mini-3.8b at its published width and depth (32 layers),
  2. PTQ through ``quantize_and_plan``: ternary weights, cluster size 16,
     the ``pallas`` backend (fused quantize/matmul/epilogue kernels),
  3. serve 8 seeded requests through ``StagedEngine`` over a kv_int8 cache
     with the flash kernels on for decode and chunked prefill,
  4. hold the kernels to their oracles on the same chip and inputs.

Two renditions of the quantized model that differ only in f32 rounding
disagree by 3e-2 to 5e-2 of the largest logit: the 8-bit dynamic
fixed-point activation quantization at every dense site turns rounding
differences into one-step mantissa flips.  So the verdict compares where a
flip cannot arise, each check with a control that must fail it:

  * sites: every dense site of layer 0 and the lm_head, the fused kernel
    against the ``ref`` oracle on the same activations, at the decode
    (8-row) and prefill-chunk (256-row) shapes.  Same integers; only the
    f32 sum over clusters differs.  Control: the oracle at 7-bit
    activations.
  * attention: ``flash_attend`` against the XLA attention at HIGHEST
    precision over one kv_int8 cache, 8 rows at ragged fill levels, one
    decode row and one 256-row chunk each.  Control: the oracle one
    position early (a fill-level or causal off-by-one).
  * model: the served path against the ``ref`` backend with the same flash
    kernel (dense sites differ, attention does not), one prefill chunk and
    one decode step over 8 slots at ragged positions.  Control: the same
    at 7-bit activations.

The served path against the ``ref`` backend with flash off, the flips
included, is printed as a reading (``vs_ref_flash_off``).  It also fails
unless JAX's first device is a TPU, every request finishes, no request is
quarantined and the engine's own compiled programs hold Pallas kernels
(``tpu_custom_call``).  The last line of standard output is one JSON
object naming the device.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # sharded MoE artifact on a 2x2 host

The numbers it prints are bring-up readings, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
ARCH = "phi4-mini-3.8b"
SLOTS, MAX_LEN, NEW_TOKENS, PREFILL_CHUNK = 8, 1024, 32, 256
# prompt lengths are drawn from 128..512 in steps of 64, so the staged
# engine's power-of-two chunk plan compiles three prefill shapes (256, 128,
# 64) instead of up to nine
PROMPT_LENS = tuple(range(128, 513, 64))
# ragged cache fill levels of the attention check: short rows, where an
# off-by-one moves the output by a large share, up to the full cache
DECODE_FILLS = (2, 3, 17, 128, 333, 512, 777, 1024)
CHUNK_FILLS = (256, 257, 300, 384, 512, 640, 900, 1024)
# ragged decode positions of the model check, inside a 256-token prefill
DECODE_POSITIONS = tuple(range(128, 256, 16))
CONTROL_ACT_BITS = 7
# --four-chips: one grok-1 layer (of 64) at published width, int4 weights,
# served on the users' sharded layout (serve.py --mesh dp=2,ep=2
# --backend pallas_ep); see CHANGES.md for the bytes
MOE_ARCH, MOE_LAYERS, FOUR_CHIP_MESH = "grok-1-314b", 1, "dp=2,ep=2"

# Limits: the largest |difference| over the reference's largest |value|.
# Sites: the kernel and the oracle sum the same int32 cluster partials in
# f32 in another order; 512 terms put that near 1e-6, and a one-bit
# coarser activation moves an output by about 1e-2.
SITE_RTOL = 1e-4
# Attention: f32 softmax in another order, and a bf16 pass through the MXU
# (2**-8 per product) should Mosaic take one for an f32 dot.
ATTN_RTOL = 1e-2
# Model: the dense sites' f32 order flips an activation mantissa only
# where a value sits within an ulp of a rounding boundary, so flips are
# rare; the flash-off rendition, with one flip in tens, reads 3e-2 to 5e-2.
MODEL_RTOL = 1e-2


def _compile_seconds():
    """Running total of the seconds JAX spends lowering and compiling,
    read from JAX's own monitoring events."""
    import jax

    total = [0.0]

    def listen(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            total[0] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: total[0]


def rel_diff(got, want, argmax: bool = False) -> dict:
    """Largest |got - want|, alone and over want's largest |value|; with
    ``argmax``, whether every row's argmax agrees."""
    import numpy as np

    got = np.asarray(got, np.float32).reshape(-1, got.shape[-1])
    want = np.asarray(want, np.float32).reshape(-1, want.shape[-1])
    max_abs = float(np.max(np.abs(got - want)))
    out = {
        "max_abs": max_abs,
        "max_rel": max_abs / float(np.max(np.abs(want))),
        "finite": bool(np.isfinite(got).all()),
    }
    if argmax:
        out["argmax_equal"] = bool((got.argmax(-1) == want.argmax(-1)).all())
    return out


def _layer0(qt):
    """Layer 0 of a layer-stacked QTensor (one shared exponent per matrix,
    so a stacked one has a leading axis on it); a plain one passes."""
    import jax

    return jax.tree.map(lambda a: a[0], qt) if qt.scale_e.ndim else qt


def _qtensor_sites(params):
    """(site path, QTensor) for every quantized projection; the path is the
    one ``dense()`` is called with (the param path less its ``w``)."""
    import jax

    from repro.core.quantizer import QTensor

    leaves, _ = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, QTensor)
    )
    return [("/".join(k.key for k in path[:-1]), leaf)
            for path, leaf in leaves if isinstance(leaf, QTensor)]


def site_parity(qparams, rows, backend="pallas", seed=SEED):
    """Every dense site of layer 0 and the lm_head (the lm_head at the
    first of ``rows`` only: prefill projects one row per request): the
    kernel backend against the ``ref`` oracle on the same seeded
    activations.  Returns ({"<site>@<rows>": diff}, control diff)."""
    import jax
    import jax.numpy as jnp

    from repro.quant.backends import qdense

    dense = jax.jit(qdense, static_argnames=("backend", "act", "act_bits"))
    key = jax.random.PRNGKey(seed)
    out, control = {}, None
    for path, qt in _qtensor_sites(qparams):
        if "experts" in path:
            continue
        qt = _layer0(qt)
        act = "silu" if path.endswith("gate") else None
        lm_head = path.endswith("lm_head")
        for m in rows[:1] if lm_head else rows:
            key, sub = jax.random.split(key)
            x = jax.random.normal(sub, (m, qt.k), jnp.bfloat16)
            want = dense(x, qt, backend="ref", act=act)
            got = dense(x, qt, backend=backend, act=act)
            out[f"{path}@{m}"] = rel_diff(got, want, argmax=lm_head)
            if control is None:
                low = dense(x, qt, backend="ref", act=act,
                            act_bits=CONTROL_ACT_BITS)
                control = rel_diff(low, want)
    return out, control


def attention_parity(cfg, seed=SEED) -> tuple:
    """``flash_attend`` against the XLA attention at HIGHEST precision over
    one seeded cache in the config's KV format: SLOTS rows at ragged fill
    levels, a decode row and a prefill chunk each.  Returns ({"decode",
    "prefill"} diffs, the same for the oracle one position early)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_prefill import flash_attend
    from repro.models import attention, kv_cache

    fmt = kv_cache.resolve_kv_fmt(cfg)
    kh, hd = cfg.n_kv_heads, cfg.hd()
    g = cfg.n_heads // kh
    kk, kv, kq = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (SLOTS, MAX_LEN, kh, hd)
    cache, _ = kv_cache.write(
        fmt, kv_cache.init_cache(cfg, (SLOTS,), MAX_LEN),
        jax.random.normal(kk, shape, jnp.bfloat16),
        jax.random.normal(kv, shape, jnp.bfloat16), jnp.int32(0),
    )
    view = kv_cache.attend_view(fmt, cache)
    flash = jax.jit(flash_attend, static_argnames="fmt")

    @jax.jit
    def oracle(q, view, q_start, valid):
        ck, cv, kscale, vscale = view
        q_pos = q_start[:, None] + jnp.arange(q.shape[1])
        bias = attention._mask_bias(
            q_pos, jnp.arange(MAX_LEN), True, None, valid
        )[:, None, None]
        with jax.default_matmul_precision("highest"):
            return attention._attend_dense(q, ck, cv, bias, kscale, vscale)

    out, control = {}, {}
    for name, s, fills in (("decode", 1, DECODE_FILLS),
                           ("prefill", PREFILL_CHUNK, CHUNK_FILLS)):
        q = jax.random.normal(
            jax.random.fold_in(kq, s), (SLOTS, s, kh, g, hd), jnp.float32
        )
        valid = jnp.asarray(fills, jnp.int32)
        start = valid - s
        got = flash(
            q, cache["k"], cache["v"], cache.get("ke"), cache.get("ve"),
            start[:, None], valid[:, None],
            jnp.full((1, 1), 2**30, jnp.int32), fmt=fmt,
        )
        want = oracle(q, view, start, valid)
        out[name] = rel_diff(got, want)
        control[name] = rel_diff(oracle(q, view, start - 1, valid - 1), want)
    return out, control


def model_parity(cfg, plan, qapi, qparams, seed=SEED) -> dict:
    """The served path's logits against ref-backend renditions of the same
    quantized model: one prefill chunk of one request and one decode step
    over SLOTS rows at ragged positions (their cache prefilled by the
    served path, so every rendition decodes from the same state)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import build_model
    from repro.quant.plan import QuantCtx

    rng = np.random.default_rng(seed + 1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, PREFILL_CHUNK)), jnp.int32)
    rows = jnp.asarray(
        rng.integers(0, cfg.vocab, (SLOTS, PREFILL_CHUNK)), jnp.int32
    )
    nxt = jnp.asarray(rng.integers(0, cfg.vocab, (SLOTS, 1)), jnp.int32)
    pos = jnp.asarray(DECODE_POSITIONS, jnp.int32)
    zero = jnp.int32(0)
    _, cache = jax.jit(qapi.prefill_chunk)(
        qparams, rows, zero, qapi.init_cache(SLOTS, MAX_LEN)
    )
    empty = qapi.init_cache(1, MAX_LEN)

    def logits(api):
        prefill, _ = jax.jit(api.prefill_chunk)(qparams, toks, zero, empty)
        decode, _ = jax.jit(api.decode)(qparams, nxt, pos, cache)
        return {"prefill": prefill, "decode": decode}

    def ref(flash, act_bits=None):
        precs = plan.site_precisions
        if act_bits is not None:
            precs = tuple(dataclasses.replace(p, act_bits=act_bits)
                          for p in precs)
        p = dataclasses.replace(plan, backend="ref", site_precisions=precs)
        c = dataclasses.replace(cfg, flash_decode=flash, flash_prefill=flash)
        return build_model(c, QuantCtx.for_plan(p))

    got = logits(qapi)
    out = {}
    for name, api in (("model", ref(True)),
                      ("model_control", ref(True, CONTROL_ACT_BITS)),
                      ("vs_ref_flash_off", ref(False))):
        want = logits(api)
        out[name] = {k: rel_diff(got[k], want[k], argmax=True) for k in got}
    return out


def serve_check(cfg, *, slots, max_len, prompt_lens, new_tokens, chunk,
                n_requests, seed=SEED) -> dict:
    """Quantize ``cfg`` on boot, serve seeded requests through the staged
    engine twice (compiling, then timed), count the kernels in the
    engine's compiled programs, and run the parity checks.  Returns the
    readings; raises nothing on a bad reading (``failures`` judges)."""
    import jax
    import numpy as np

    from repro.models import build_model, init_and_quantize
    from repro.serving import Request, SchedulerConfig, StagedEngine

    out = {}
    compile_s = _compile_seconds()
    t0 = time.perf_counter()
    qparams, plan, qapi, out["float_bytes"] = init_and_quantize(
        build_model(cfg), jax.random.PRNGKey(seed)
    )
    qparams = jax.block_until_ready(qparams)
    out["quantized_bytes"] = sum(x.nbytes for x in jax.tree.leaves(qparams))
    out["boot_s"] = time.perf_counter() - t0
    print(f"booted: {out['float_bytes']} float bytes -> "
          f"{out['quantized_bytes']} quantized in {out['boot_s']:.1f}s",
          flush=True)

    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, cfg.vocab, int(rng.choice(prompt_lens))).tolist()
        for _ in range(n_requests)
    ]
    eng = StagedEngine(
        qapi, qparams, n_slots=slots, max_len=max_len,
        sched=SchedulerConfig(prefill_chunk=chunk),
    )

    def run(uid0):
        reqs = [
            eng.submit(Request(uid=uid0 + i, prompt=p, max_new_tokens=new_tokens))
            for i, p in enumerate(prompts)
        ]
        t = time.perf_counter()
        eng.run()
        return reqs, time.perf_counter() - t

    # the first pass compiles every shape the second pass meets; the second
    # is timed with nothing left to compile
    c0 = compile_s()
    _, out["first_pass_s"] = run(0)
    c1 = compile_s()
    print(f"first pass (compiling): {out['first_pass_s']:.1f}s", flush=True)
    reqs, out["serve_s"] = run(n_requests)
    out["compile_s_in_timed_pass"] = compile_s() - c1
    out["serve_compile_s"] = c1 - c0
    out["statuses"] = [r.status for r in reqs]
    out["finished"] = sum(r.status == "finished" for r in reqs)
    out["submitted"] = len(reqs)
    out["tokens"] = sum(len(r.output) for r in reqs)
    out["health_events"] = eng.stats()["health"]["events"]
    left = eng.leftover()
    out["left_in_engine"] = len(left["in_flight"]) + len(left["queued"])
    programs = eng.compiled_programs()
    out["kernels_decode"] = programs["decode"].as_text().count("tpu_custom_call")
    out["kernels_prefill"] = programs["prefill"].as_text().count("tpu_custom_call")
    del eng, programs

    out["sites"], out["site_control"] = site_parity(qparams, (slots, chunk))
    out["attention"], out["attention_control"] = attention_parity(cfg)
    out.update(model_parity(cfg, plan, qapi, qparams))
    out["compile_s"] = compile_s()
    return out


def _judge(bad, name, diff, limit):
    """Append to ``bad`` if ``diff`` is off its limit (or, for logits of
    the same inputs, names another argmax)."""
    if not (diff["finite"] and diff["max_rel"] <= limit):
        bad.append(f"{name}: {diff['max_rel']} of the reference's largest "
                   f"value, limit {limit}: {diff}")
    if diff.get("argmax_equal") is False and name.startswith("lm_head"):
        bad.append(f"{name}: argmax differs: {diff}")


def _judge_control(bad, name, control, limit):
    """Append to ``bad`` if a rendition the check exists to tell apart
    reads within its limit."""
    if not control["max_rel"] > limit:
        bad.append(f"{name} control reads {control['max_rel']}, within the "
                   f"limit {limit}: the check cannot tell it apart")


def failures(r: dict) -> list:
    bad = []
    if r["finished"] != r["submitted"] or r["left_in_engine"]:
        bad.append(f"requests not finished: {r['statuses']}, "
                   f"{r['left_in_engine']} left in the engine")
    if r["health_events"]["quarantined"]:
        bad.append(f"quarantines: {r['health_events']}")
    if not (r["kernels_prefill"] and r["kernels_decode"]):
        bad.append("an engine program holds no tpu_custom_call")
    for name, diff in r["sites"].items():
        _judge(bad, name, diff, SITE_RTOL)
    _judge_control(bad, "site", r["site_control"], SITE_RTOL)
    for name, diff in r["attention"].items():
        _judge(bad, f"attention {name}", diff, ATTN_RTOL)
        _judge_control(bad, f"attention {name}",
                       r["attention_control"][name], ATTN_RTOL)
    for name, diff in r["model"].items():
        _judge(bad, f"model {name}", diff, MODEL_RTOL)
        _judge_control(bad, f"model {name}", r["model_control"][name],
                       MODEL_RTOL)
    return bad


def one_chip(dev) -> dict:
    from repro import configs
    from repro.configs.base import QuantConfig

    qc = QuantConfig(w_bits=2, group_size=16, mode="ptq", backend="pallas")
    cfg = dataclasses.replace(
        configs.get_config(ARCH, qc),
        kv_fmt="kv_int8", flash_decode=True, flash_prefill=True,
    )
    r = serve_check(
        cfg, slots=SLOTS, max_len=MAX_LEN, prompt_lens=PROMPT_LENS,
        new_tokens=NEW_TOKENS, chunk=PREFILL_CHUNK, n_requests=SLOTS,
    )
    r["peak_bytes_in_use"] = dev.memory_stats()["peak_bytes_in_use"]
    return r


def moe_config():
    from repro import configs
    from repro.configs.base import QuantConfig

    qc = QuantConfig(w_bits=4, group_size=16, mode="ptq", backend="pallas_ep")
    return dataclasses.replace(
        configs.get_config(MOE_ARCH, qc), n_layers=MOE_LAYERS
    )


def _expert_parity_inputs(params, cfg, seed=SEED):
    """Layer 0's expert weights and a seeded (E, 8, d) dispatch buffer."""
    import jax
    import jax.numpy as jnp

    experts = {n: _layer0(w["w"]) for n, w in
               params["blocks"]["moe"]["experts"].items()}
    x = jax.random.normal(jax.random.PRNGKey(seed + 2),
                          (cfg.n_experts, 8, cfg.d_model), jnp.bfloat16)
    return experts, x


def four_chips(cfg, mesh_spec=FOUR_CHIP_MESH, n_requests=4, prompt_len=64,
               new_tokens=8, max_len=128) -> dict:
    """The sharded MoE serving path against the same artifact on one chip.

    Quantizes ``cfg`` on boot, saves it as a per-host sharded artifact
    under the serving mesh, and loads it twice: on the mesh (dense sites
    in a shard_map over their weight layout, the EP expert FFN in its
    shard_map) and on one device (the same kernels, whole).  Each side
    serves the same seeded requests, runs one prefill chunk and one decode
    step, and runs every dense site of layer 0 and the expert FFN on the
    same seeded inputs.  Returns both sides' readings and differences."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import parse_mesh_spec
    from repro.models import build_model, init_and_quantize, load_servable, save_servable
    from repro.parallel import sharding as rules
    from repro.quant.backends import expert_ffn_ep, expert_ffn_local, qdense
    from repro.serving import Request, SchedulerConfig, StagedEngine

    mesh = parse_mesh_spec(mesh_spec)
    out = {"arch": cfg.name, "n_layers": cfg.n_layers, "mesh": dict(mesh.shape)}
    t0 = time.perf_counter()
    qparams, plan, qapi, out["float_bytes"] = init_and_quantize(
        build_model(cfg), jax.random.PRNGKey(SEED)
    )
    out["quantized_bytes"] = sum(x.nbytes for x in jax.tree.leaves(qparams))
    out["boot_s"] = time.perf_counter() - t0
    print(f"booted in {out['boot_s']:.1f}s", flush=True)

    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab, prompt_len).tolist()
               for _ in range(n_requests)]
    toks = jnp.asarray(prompts[:2], jnp.int32)
    backend = plan.backend
    with tempfile.TemporaryDirectory() as d:
        save_servable(d, qapi, qparams, plan, mesh=mesh)
        del qparams
        readings = {}
        for name, m in (("mesh", mesh), ("one_device", None)):
            api, params, _ = load_servable(d, mesh=m)
            eng = StagedEngine(
                api, params, n_slots=n_requests, max_len=max_len, mesh=m,
                sched=SchedulerConfig(prefill_chunk=prompt_len),
            )
            reqs = [eng.submit(Request(uid=i, prompt=p,
                                       max_new_tokens=new_tokens))
                    for i, p in enumerate(prompts)]
            eng.run()
            kernels = eng.compiled_programs()["prefill"].as_text()
            del eng
            cache = api.init_cache(toks.shape[0], max_len)
            if m is not None:
                cache = jax.device_put(cache, rules.cache_shardings(
                    jax.eval_shape(lambda: cache), m))
            experts, xe = _expert_parity_inputs(params, cfg)
            rules.set_activation_mesh(m)
            try:
                logits, cache = jax.jit(api.prefill_chunk)(
                    params, toks, jnp.int32(0), cache)
                step, _ = jax.jit(api.decode)(
                    params, jnp.asarray([[1], [2]], jnp.int32),
                    jnp.int32(prompt_len), cache)
                sites = {}
                for path, qt in _qtensor_sites(params):
                    if "experts" in path:
                        continue
                    qt = _layer0(qt)
                    x = jax.random.normal(jax.random.PRNGKey(len(sites)),
                                          (8, qt.k), jnp.bfloat16)
                    sites[path] = np.asarray(jax.jit(functools.partial(
                        qdense, backend=backend, site=path))(x, qt))
                if m is None:  # in the model dtype, as the EP combine is
                    ffn = lambda w, x: expert_ffn_local(
                        w, x, backend=backend).astype(x.dtype)
                else:
                    ffn = functools.partial(expert_ffn_ep, mesh=m,
                                            cap_axes=("data",),
                                            backend=backend)
                sites["experts"] = np.asarray(jax.jit(ffn)(experts, xe))
            finally:
                rules.set_activation_mesh(None)
            readings[name] = {
                "tokens": [r.output for r in reqs],
                "statuses": [r.status for r in reqs],
                "prefill": np.asarray(logits), "decode": np.asarray(step),
                "sites": sites,
                "kernels": kernels.count("tpu_custom_call"),
                "all_to_all": kernels.count("all-to-all"),
            }
            del params, cache, experts
    for name, r in readings.items():
        out[f"{name}_statuses"] = r["statuses"]
        out[f"{name}_tokens"] = r["tokens"]
        out[f"{name}_prefill_kernels"] = r["kernels"]
        out[f"{name}_prefill_all_to_all"] = r["all_to_all"]
    a, b = readings["mesh"], readings["one_device"]
    out["tokens_equal"] = a["tokens"] == b["tokens"]
    out["sites"] = {k: rel_diff(a["sites"][k], b["sites"][k])
                    for k in b["sites"]}
    out["model"] = {k: rel_diff(a[k], b[k], argmax=True)
                    for k in ("prefill", "decode")}
    return out


def four_chip_failures(r: dict) -> list:
    bad = []
    for name in ("mesh", "one_device"):
        if any(s != "finished" for s in r[f"{name}_statuses"]):
            bad.append(f"{name}: requests not finished: {r[f'{name}_statuses']}")
        if not r[f"{name}_prefill_kernels"]:
            bad.append(f"{name}: the prefill program holds no tpu_custom_call")
    if not r["mesh_prefill_all_to_all"]:
        bad.append("mesh: the expert FFN did not run the EP all-to-alls")
    for name, diff in r["sites"].items():
        _judge(bad, f"mesh {name}", diff, SITE_RTOL)
    for name, diff in r["model"].items():
        _judge(bad, f"mesh {name}", diff, MODEL_RTOL)
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve a sharded MoE artifact on a 4-chip host and "
                         "compare it with the same artifact on one chip")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    from repro.launch.mesh import enable_compile_cache

    print(f"device_kind: {dev.device_kind}  devices: {jax.device_count()}")
    print(f"compile cache: {enable_compile_cache()}")
    if args.four_chips:
        r = four_chips(moe_config())
        bad = four_chip_failures(r)
    else:
        r = one_chip(dev)
        bad = failures(r)
    for key, val in r.items():
        print(f"{key}: {val}")
    for b in bad:
        print(f"FAIL: {b}", file=sys.stderr)
    if bad:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count(),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

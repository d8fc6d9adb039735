"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up draws the cell's packed weights from the seed on the device, builds
the staged engine, warms every program the cell's traffic uses (the
decode tick, ``insert``, each power-of-two prefill chunk up to the chunk
size, first-token sampling).  For an open loop it then serves the
arrivals due before the window, so that the window opens on an engine in
its steady state; for a closed loop it fills every slot.  The window then
drives ``StagedEngine.step`` for ``--seconds``.  With
``--trace 1`` the window runs under the profiler and the per-layer
metrics are read from the trace; otherwise the end-to-end metrics are
printed.  Either way the served tokens are then checked against the plain
reference (``bench/check``).  The last line of standard output is one JSON
object; the numbers compared, each beside its limit, are the last lines
of standard error and the result's last key.

Exits non-zero, printing no result, when JAX's first device is not a TPU,
when its ``device_kind`` is not in ``bench/peaks.json``, or when there
are fewer chips than the cell asks for.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# one fixed directory inside the checkout: its path is part of the key
# under which a later run finds the compiled programs
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def _prepare_env():
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    # no eviction: every program of the cell stays for the next run
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


class CompileMeter:
    """Compilations and their seconds, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.n, self.s = 0, 0.0

        def listen(event, duration, **_):
            if event.startswith("/jax/core/compile/"):
                self.n += 1
                self.s += duration

        jax.monitoring.register_event_duration_secs_listener(listen)


def build_config(config: dict):
    """The program's ArchConfig for a configuration file, refusing one
    whose sizes the program's architecture does not have."""
    import dataclasses

    from repro import configs
    from repro.configs.base import QuantConfig

    q = config["quant"]
    qc = QuantConfig(w_bits=q["w_bits"], act_bits=q["act_bits"],
                     group_size=q["group_size"], mode="ptq",
                     backend=q["backend"])
    cfg = dataclasses.replace(
        configs.get_config(config["arch"], qc), kv_fmt=config["kv_fmt"],
        flash_decode=config["flash_decode"],
        flash_prefill=config["flash_prefill"])
    want = {"d_model": "hidden_size", "d_ff": "intermediate_size",
            "n_heads": "num_attention_heads",
            "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
            "n_layers": "num_hidden_layers", "vocab": "vocab_size",
            "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
            "tie_embeddings": "tie_word_embeddings", "dtype": "torch_dtype",
            "vocab_pad_to": "vocab_pad_to"}
    bad = {a: (getattr(cfg, a), config[b]) for a, b in want.items()
           if getattr(cfg, a) != config[b]}
    # the program's architectures rotate every dimension, unscaled
    for key, program in (("partial_rotary_factor", 1.0), ("rope_scaling", None)):
        if config[key] != program or cfg.mrope:
            bad[key] = (program, config[key])
    if bad:
        raise ValueError(f"{config['name']}: the program's {config['arch']} "
                         f"differs from the file (program, file): {bad}")
    return cfg


def build_engine(config: dict, mix: dict, seed: int):
    import jax

    from bench import weights
    from repro.models import build_model
    from repro.serving import SchedulerConfig, StagedEngine

    api = build_model(build_config(config))
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    plan = api.ctx.policy.compile(shapes, mode="ptq",
                                  backend=config["quant"]["backend"])
    params = weights.make_params(api, plan, seed)
    e = mix["engine"]
    eng = StagedEngine(api.with_plan(plan), params, n_slots=e["slots"],
                       max_len=e["max_len"],
                       sched=SchedulerConfig(prefill_chunk=e["prefill_chunk"]))
    return eng


def warm_up(eng, vocab: int, seed: int) -> None:
    """One request whose prompt is cut into every power-of-two chunk up to
    the chunk size, with two output tokens: compiles (or loads) each
    prefill shape, first-token sampling, insert and the decode tick."""
    import numpy as np

    from repro.serving import Request

    chunk = eng.sched.prefill_chunk
    rng = np.random.default_rng(seed ^ 0x5EED)
    req = Request(uid=-1, prompt=rng.integers(0, vocab, 2 * chunk - 1).tolist(),
                  max_new_tokens=2)
    eng.submit(req, strict=True)
    eng.run()
    if req.status != "finished":
        raise RuntimeError(f"warm-up request ended {req.status}: {req.reason}")


def fill_slots(gen, specs) -> int:
    """Closed loop: submit the job's first requests until every slot is
    generating; returns how many were submitted."""
    from bench.loadgen import clock

    eng, i = gen.eng, 0
    while sum(1 for r in gen.live.values() if r.token_t) < eng.n_slots:
        while not eng.queue and i < len(specs):
            gen.submit(specs[i], clock())
            i += 1
        gen.step()
    return i


def end_to_end_counts(win, loop: str):
    """(attempted, failed) requests of the window."""
    bad = [r for r in win.records
           if r.req.terminal and r.req.status != "finished"]
    if loop == "open":
        due = [r for r in win.records if win.t0 <= r.due_t < win.t1]
        missing = [r for r in due if not r.token_t and r not in bad]
        return len(due), len(bad) + len(missing)
    return sum(r.due_t < win.t1 for r in win.records), len(bad)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _prepare_env()

    from bench import cell as C

    cell = C.load(args.workload)
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        peak_table = json.load(f)

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"bench: JAX's first device is {dev.platform}, not a TPU; "
              "nothing was run", file=sys.stderr)
        return 2
    if dev.device_kind not in peak_table:
        print(f"bench: no peaks for device_kind {dev.device_kind!r} in "
              "bench/peaks.json; nothing was run", file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"bench: the cell asks for {cell.chips} chips, JAX sees "
              f"{len(devs)}; nothing was run", file=sys.stderr)
        return 2
    return run_cell(cell, args.seed, args.seconds, bool(args.trace),
                    peak_table[dev.device_kind], dev)


def serve_window(cell, seed: int, seconds: float, traced: bool, dev) -> dict:
    """Set up and serve one window of ``cell``; returns the window, the
    set-up seconds, what compiled inside the window, the device's peak
    memory and (traced) the reduced trace.  The engine is gone on return."""
    import jax

    from bench import traffic
    from bench import trace as T
    from bench.loadgen import LoadGen
    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()
    meter = CompileMeter()
    config, mix = cell.config, cell.mix
    phases = {"start": time.monotonic() - T_START}
    eng = build_engine(config, mix, seed)
    jax.block_until_ready(eng.params)
    phases["weights"] = time.monotonic() - T_START
    vocab = config["vocab_size"]
    warm_up(eng, vocab, seed)
    phases["warm_up"] = time.monotonic() - T_START
    specs = traffic.generate(mix, seed, seconds, vocab)
    gen = LoadGen(eng)
    if mix["loop"] == "closed":
        specs = specs[fill_slots(gen, specs):]

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    c0, closed = [], []

    def on_open():
        phases["served_before_window"] = time.monotonic() - T_START
        print(f"set-up (s since start): {phases}; compiles {meter.n} "
              f"({meter.s:.1f} s)", file=sys.stderr, flush=True)
        if traced:
            jax.profiler.start_trace(trace_dir)
        c0.extend((meter.n, meter.s))

    def on_close():
        closed.append((meter.n - c0[0], meter.s - c0[1]))
        if traced:
            jax.profiler.stop_trace()

    try:
        win = gen.run(specs, seconds, loop=mix["loop"], on_open=on_open,
                      on_close=on_close)
        red = T.reduce(T.xplane_path(trace_dir)) if traced else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = win.t0 - T_START
    stats = dev.memory_stats() or {}
    # the program's state goes before anything else runs
    gen.eng = eng = None
    del gen
    gc.collect()
    return {"win": win, "setup_s": setup_s, "compiles": closed[0],
            "peak": stats.get("peak_bytes_in_use"), "red": red}


def run_cell(cell, seed: int, seconds: float, traced: bool, peaks: dict,
             dev) -> int:
    """Set up, measure, check and print one run of ``cell``."""
    import jax

    from bench import check
    from bench import trace as T
    from bench.cell import metric_reader
    from bench.readings import Readings

    config, mix = cell.config, cell.mix
    s = serve_window(cell, seed, seconds, traced, dev)
    win, red = s["win"], s["red"]
    r = Readings(win, red, config, mix, peaks, s["setup_s"])
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = metric_reader(m["name"])(r)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    attempted, failed = end_to_end_counts(win, mix["loop"])

    t_ref = time.monotonic()
    picked = check.sample(win.records, seed, mix["check_requests"])
    ref = check.reference(config, seed)
    gaps = check.served_gaps(
        ref, picked, check.padded_length(picked, mix["engine"]["max_len"]))
    ref_s = time.monotonic() - t_ref
    compiles, compile_s = s["compiles"]
    numbers = {
        "worst_gap": (float(gaps.max()) if len(gaps) else None,
                      config["check"]["worst_gap_limit"]),
        "requests_failed": (failed, 0),
        "window_compiles": (compiles, 0),
    }
    correct = check.judge(numbers)

    due = [x for x in win.records if win.t0 <= x.due_t < win.t1]
    info = {"requests_due_or_started": attempted,
            "ttft_ms_due_in_window": sorted(
                (x.token_t[0] - x.due_t) * 1e3 for x in due if x.token_t),
            "requests_in_window": len(win.records),
            "dispatches": len(win.dispatches),
            "drain_s": win.drain_s, "window_compile_s": compile_s,
            "checked_requests": len(picked), "checked_tokens": len(gaps),
            "reference_s": ref_s, "setup_s": s["setup_s"]}
    if traced:
        count = lambda xs: {k: xs.count(k) for k in sorted(set(xs))}
        info["programs"] = count([k for _, k in r.modules])
        info["window_dispatches"] = count([d.kind for d in r.dispatches])
    print(json.dumps(info), file=sys.stderr)
    for name, (v, lim) in numbers.items():
        print(f"check {name} {v} limit {lim}", file=sys.stderr)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": s["peak"]}
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = r.busy_s()
        device["window_s"] = r.window_s()
        out["breakdown"] = T.breakdown(red)
    out["check"] = {k: {"value": v, "limit": lim}
                    for k, (v, lim) in numbers.items()}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

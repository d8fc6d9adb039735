"""Production serving launcher (PTQ integer pipeline + continuous batching).

Two boot modes:

  * quantize-on-boot: build the model, quantize through the unified
    ``repro.quant`` API (optional calibration batches profile static
    per-site activation exponents), and optionally persist the result as a
    packed artifact (``--save-artifact DIR``).
  * cold start (``--artifact DIR``): load a previously saved artifact --
    packed QTensors + compiled plan + serialized ArchConfig -- and serve
    directly.  No fp32 weights are materialized and no calibration runs;
    the 4-16x-smaller artifact is the unit of deployment.

With ``--mesh dp=2,ep=2`` the whole pipeline runs sharded: the artifact's
per-host shard files assemble straight onto their owning devices, the
engine's decode step runs under NamedSharding, and MoE expert sites
dispatch through the shard_map expert-parallel fused qdense when the plan
carries the "pallas_ep" backend.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
      --bits 2 --group-size 16 --requests 8 [--calibrate 4] \
      [--save-artifact DIR] [--plan-json p.json]
  PYTHONPATH=src python -m repro.launch.serve --artifact DIR --requests 8 \
      [--mesh dp=2,ep=2]

Serving runs the staged engine by default (prefill / insert / generate
stages, chunked prefill, SLO percentiles in the run report); ``--engine
lockstep`` selects the shared-tick oracle, ``--prefill-chunk`` and
``--policy {decode,prefill}`` tune the staged scheduler.  Fault tolerance:
``--deadline-ms / --max-queue / --ttft-slo-ms`` gate admission,
``--tpot-slo-ms`` arms overload degradation, ``--retries`` budgets
quarantine retries, and ``--chaos "rate=0.01,kinds=nan_logits|kv_corrupt"``
injects seeded faults to demonstrate containment.  See docs/SERVING.md.
"""
from __future__ import annotations

import argparse
import sys
import time

# --mesh on a CPU-pinned run (JAX_PLATFORMS=cpu): force the host platform
# device count BEFORE the first jax initialization -- mirrors dryrun.py, but
# only when the operator did not set XLA_FLAGS themselves.
from repro.launch.mesh import parse_mesh_spec, preinit_mesh_flag

preinit_mesh_flag(sys.argv)

import jax
import numpy as np

from repro import configs
from repro.configs.base import QuantConfig
from repro.models import (
    build_model,
    init_and_quantize,
    load_servable,
    make_smoke_batch,
    save_servable,
)
from repro.serving import (
    AdmissionConfig,
    FaultInjector,
    HealthConfig,
    Request,
    SamplerConfig,
    SchedulerConfig,
    ServingEngine,
    StagedEngine,
)


def tree_mb(tree) -> float:
    return sum(l.nbytes for l in jax.tree.leaves(tree)) / 1e6


def boot_from_artifact(artifact_dir: str, mesh=None):
    """Cold start: (api, qparams, plan) from a packed on-disk artifact."""
    t0 = time.time()
    api, qparams, art = load_servable(artifact_dir, mesh=mesh)
    plan = art.plan
    plan_str = (
        f"plan: {len(plan.site_paths)} sites, "
        f"{len(plan.act_exponents)} calibrated"
        if plan is not None else "plan: none (unquantized artifact)"
    )
    mesh_str = (
        "" if mesh is None
        else f" onto mesh {dict(mesh.shape)} (per-host shards assembled)"
    )
    print(
        f"arch={api.cfg.name} cold-started from {art.path} in "
        f"{time.time() - t0:.2f}s: {tree_mb(qparams):.1f} MB packed, "
        f"{plan_str} (fp32 never materialized){mesh_str}"
    )
    return api, qparams, plan


def boot_quantize(args, mesh=None):
    """Quantize-on-boot: init fp params, PTQ (optionally calibrated)."""
    qc = QuantConfig(w_bits=args.bits, group_size=args.group_size,
                     mode="ptq", backend=args.backend,
                     fmt=getattr(args, "fmt", None))
    cfg = (configs.get_smoke if args.smoke else configs.get_config)(args.arch, qc)
    api = build_model(cfg)
    calib = None
    if args.calibrate:
        calib = [
            make_smoke_batch(jax.random.PRNGKey(100 + i), cfg, batch=2, seq=16)
            for i in range(args.calibrate)
        ]
    qparams, plan, api, fp_bytes = init_and_quantize(
        api, jax.random.PRNGKey(0), calib_batches=calib
    )
    fp_mb, q_mb = fp_bytes / 1e6, tree_mb(qparams)
    print(f"arch={cfg.name} weights {fp_mb:.1f} MB -> {q_mb:.1f} MB "
          f"({fp_mb / q_mb:.1f}x)  plan: {len(plan.site_paths)} sites, "
          f"{len(plan.act_exponents)} calibrated")
    if args.save_artifact:
        out = save_servable(args.save_artifact, api, qparams, plan, mesh=mesh)
        shard_str = " (per-host shards)" if mesh is not None else ""
        print(f"saved packed artifact to {out}{shard_str} "
              f"(serve it with --artifact {args.save_artifact})")
    if args.plan_json:
        with open(args.plan_json, "w") as f:
            f.write(plan.to_json())
        print(f"wrote QuantPlan to {args.plan_json}")
    return api, qparams, plan


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=configs.ARCH_IDS)
    ap.add_argument("--artifact", default=None, metavar="DIR",
                    help="cold-start from a packed quantized artifact "
                         "(replaces --arch/--calibrate: no fp32, no requant)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--bits", type=int, default=2, choices=[2, 4, 8])
    ap.add_argument("--fmt", default=None, metavar="NAME",
                    help="registered weight format by name (e.g. nf4, mx); "
                         "overrides the --bits ladder for default sites")
    ap.add_argument("--group-size", type=int, default=16)
    ap.add_argument("--kv-fmt", default=None, metavar="NAME",
                    choices=["kv_bf16", "kv_int8", "kv_mx"],
                    help="registered KV-cache format (models/kv_cache.py); "
                         "overrides the config (and its kv_bits back-compat)")
    ap.add_argument("--flash-decode", action="store_true",
                    help="route single-token decode through the fused "
                         "Pallas flash kernel (reads the packed cache; "
                         "interpreted off-TPU); says nothing about "
                         "prefill -- see --flash-prefill")
    ap.add_argument("--flash-prefill", action="store_true",
                    help="route chunked-prefill cache attends (and the "
                         "in-chunk tail) through the fused Pallas flash "
                         "kernel -- one pass over the packed cache per "
                         "chunk, which is what moves TTFT; independent "
                         "of --flash-decode")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--engine", default="staged",
                    choices=["lockstep", "staged"],
                    help="staged (default): prefill/insert/generate stages "
                         "with chunked prefill; lockstep: the shared-tick "
                         "oracle (prefill and decode in one graph)")
    ap.add_argument("--prefill-chunk", type=int, default=32, metavar="N",
                    help="staged engine: max prompt tokens one prefill "
                         "dispatch may consume")
    ap.add_argument("--policy", default="decode",
                    choices=["decode", "prefill"],
                    help="staged engine stage arbitration: decode-priority "
                         "(inter-token latency) vs prefill-priority (TTFT)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--calibrate", type=int, default=0, metavar="N",
                    help="profile N batches for static activation exponents")
    ap.add_argument("--save-artifact", default=None, metavar="DIR",
                    help="persist the quantized model as a packed artifact")
    ap.add_argument("--plan-json", default=None,
                    help="write the compiled QuantPlan to this path")
    ap.add_argument("--mesh", default=None, metavar="SPEC",
                    help="serve sharded, e.g. 'dp=2,ep=2' (dp->data, "
                         "ep/tp->model); cold starts assemble per-host "
                         "shard files straight onto their devices")
    ap.add_argument("--backend", default=QuantConfig.backend,
                    choices=["xla", "xla_int8", "pallas", "pallas_ep",
                             "ref", "auto"],
                    help="qmatmul backend the compiled plan carries "
                         "(auto: the Pallas integer pipeline on a TPU; "
                         "pallas_ep routes MoE expert sites through the "
                         "shard_map fused path under --mesh)")
    # fault tolerance: deadlines, load shedding, overload SLOs, chaos
    ap.add_argument("--deadline-ms", type=float, default=None, metavar="MS",
                    help="default per-request deadline; past it a request "
                         "is expired wherever it is (queued or in flight)")
    ap.add_argument("--max-queue", type=int, default=None, metavar="N",
                    help="shed submissions once the queue holds N requests")
    ap.add_argument("--ttft-slo-ms", type=float, default=None, metavar="MS",
                    help="shed submissions whose estimated TTFT exceeds MS")
    ap.add_argument("--tpot-slo-ms", type=float, default=None, metavar="MS",
                    help="enter overload mode (smaller prefill chunks, "
                         "decode-priority) when recent TPOT p95 exceeds MS")
    ap.add_argument("--retries", type=int, default=1, metavar="N",
                    help="retry budget for fault-quarantined requests "
                         "(re-queued with exponential backoff)")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="inject faults, e.g. 'rate=0.01,kinds=nan_logits|"
                         "kv_corrupt|stall_tick,seed=0' -- seeded and "
                         "deterministic; see repro/serving/faults.py")
    args = ap.parse_args()
    if bool(args.artifact) == bool(args.arch):
        ap.error("exactly one of --arch or --artifact is required")

    from repro.launch.mesh import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()} (persistent; "
          "cold-start compiles replay from disk)")
    mesh = parse_mesh_spec(args.mesh) if args.mesh else None
    if args.artifact:
        api, qparams, plan = boot_from_artifact(args.artifact, mesh=mesh)
    else:
        api, qparams, plan = boot_quantize(args, mesh=mesh)
    if args.kv_fmt or args.flash_decode or args.flash_prefill:
        # rebind the api closures to the overridden cache config; weights
        # and the compiled plan are untouched (the KV format is a pure
        # serving-time choice).  --flash-decode and --flash-prefill are
        # INDEPENDENT: one gates S == 1 ticks, the other chunked-prefill
        # cache attends -- neither implies the other.
        import dataclasses

        cfg2 = dataclasses.replace(
            api.cfg,
            kv_fmt=args.kv_fmt or api.cfg.kv_fmt,
            flash_decode=args.flash_decode or api.cfg.flash_decode,
            flash_prefill=args.flash_prefill or api.cfg.flash_prefill,
        )
        api = build_model(cfg2, api.ctx)
    from repro.models import kv_cache as kv_fmt_lib

    # the startup banner always states both flash knobs: "on for decode,
    # off for prefill" is a valid -- and previously invisible -- state
    # the flash kernel needs a whole cache per device; a sharded cache
    # attends through XLA (parallel/sharding.kernels_routable)
    flash_off = (mesh is not None and mesh.size > 1
                 and (api.cfg.flash_decode or api.cfg.flash_prefill))
    print(f"kv cache: fmt={kv_fmt_lib.resolve_kv_fmt(api.cfg)} "
          f"flash_decode={api.cfg.flash_decode} "
          f"flash_prefill={api.cfg.flash_prefill}"
          + (" (not under this mesh: XLA attends over the sharded cache)"
             if flash_off else ""))
    cfg = api.cfg

    faults = FaultInjector.from_spec(args.chaos) if args.chaos else None
    if faults is not None:
        print(f"chaos: rate={faults.rate} kinds={'|'.join(faults.kinds)}")
    eng_kw = dict(n_slots=args.slots, max_len=args.max_len,
                  sampler=SamplerConfig(temperature=args.temperature),
                  mesh=mesh,
                  admission=AdmissionConfig(
                      max_queue=args.max_queue,
                      ttft_slo_ms=args.ttft_slo_ms,
                      deadline_ms=args.deadline_ms),
                  health=HealthConfig(overload_tpot_ms=args.tpot_slo_ms),
                  faults=faults)
    if args.engine == "staged":
        eng = StagedEngine(api, qparams, sched=SchedulerConfig(
            prefill_chunk=args.prefill_chunk, policy=args.policy), **eng_kw)
        print(f"engine=staged policy={args.policy} "
              f"prefill_chunk={args.prefill_chunk}")
    else:
        eng = ServingEngine(api, qparams, **eng_kw)
        print("engine=lockstep (shared-tick oracle)")
    rng = np.random.default_rng(0)
    not_admitted = []
    for i in range(args.requests):
        r = eng.submit(Request(
            uid=i, prompt=rng.integers(0, cfg.vocab, 6).tolist(),
            max_new_tokens=8, max_retries=args.retries,
        ))
        if r.status != "queued":
            not_admitted.append(r)
    t0 = time.time()
    done = eng.run()
    dt = time.time() - t0
    finished = [r for r in done if r.status == "finished"]
    toks = sum(len(r.output) for r in finished)
    print(f"{len(finished)} finished / {toks} tokens in {dt:.1f}s "
          f"({toks / dt:.1f} tok/s)")
    health = eng.stats()["health"]
    ev = health["events"]
    if not_admitted or any(ev[k] for k in
                           ("expired", "failed", "quarantined", "retried")):
        print(f"  fault tolerance: shed={ev['shed']} rejected={ev['rejected']} "
              f"expired={ev['expired']} quarantined={ev['quarantined']} "
              f"retried={ev['retried']} failed={ev['failed']}")
        for r in not_admitted[:4]:
            print(f"    req {r.uid} {r.status}: {r.reason}")
    print(f"  ticks={health['ticks']} slow={health['slow_ticks']} "
          f"hung={health['hung_ticks']} "
          f"tick_ewma={health['tick_ms_ewma']:.1f}ms "
          f"overload_entered={health['overload_entered']}")
    if health["faults"]:
        print(f"  chaos injected: {health['faults']}")
    left = eng.leftover()
    if left["in_flight"] or left["queued"]:
        print(f"UNFINISHED: {len(left['in_flight'])} in flight, "
              f"{len(left['queued'])} queued (tick budget expired; "
              "drain() returns them)")
    lat = eng.stats()["latency"]
    for name in ("queue_wait", "ttft", "tpot"):
        p = lat[name]
        if p is not None:
            print(f"  {name:10s} p50={p['p50'] * 1e3:7.1f}ms "
                  f"p95={p['p95'] * 1e3:7.1f}ms p99={p['p99'] * 1e3:7.1f}ms "
                  f"(n={p['n']})")
    for r in done[:4]:
        print(f"  req {r.uid}: {r.output}")
    # shed, expired or failed requests are the point of a chaos or
    # admission-limited run; anywhere else a request that did not finish
    # is a failed run
    lost = (len([r for r in done + not_admitted if r.status != "finished"])
            + len(left["in_flight"]) + len(left["queued"]))
    limits = (args.deadline_ms, args.max_queue, args.ttft_slo_ms)
    if lost and args.chaos is None and all(v is None for v in limits):
        sys.exit(f"{lost} of {args.requests} requests did not finish")


if __name__ == "__main__":
    main()

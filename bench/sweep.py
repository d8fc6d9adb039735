"""Find an open-loop cell's knee on the chip, once, when the cell is
defined.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 0.2 0.3 0.5 [--write]

One process draws the weights and warms the engine once.  At each rate it
serves the mix's arrivals due before the window (``warm_s`` of them, as
the benchmark's own runs do), so each window opens on the engine's steady
state at that rate, then one window; what is left is aborted before the
next rate.  A rate is sustained when the window closes with at most
``BACKLOG`` requests waiting for a slot, or no more than it opened with.
The knee is the highest sustained rate below the first rate that is not;
the cell runs at 0.8 of it.  One JSON line per rate, then the traffic file
with ``knee_per_s`` and ``rate_per_s`` set (written in place with
``--write``).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402

BACKLOG = 2
SHARE = 0.8


def one_rate(eng, mix, rate, seed, seconds):
    from bench import traffic
    from bench.loadgen import LoadGen

    m = dict(mix, rate_per_s=rate)
    specs = traffic.generate(m, seed, seconds, eng.api.cfg.vocab)
    gen = LoadGen(eng)
    state = {}

    def at(key):
        def note():
            state[key] = (len(eng.queue),
                          sum(1 for r in gen.live.values() if r.token_t))
        return note

    win = gen.run(specs, seconds, loop="open", on_open=at("open"),
                  on_close=at("close"), drain_s=0.0)
    eng.drain()
    due = [r for r in win.records if win.t0 <= r.due_t < win.t1]
    ttft = sorted(r.token_t[0] - r.due_t for r in due if r.token_t)
    (wait0, gen0), (wait1, gen1) = state["open"], state["close"]
    return {"rate_per_s": rate, "requests_due_in_window": len(due),
            "first_tokens_in_window": len(ttft),
            "waiting_at_open": wait0, "waiting_at_close": wait1,
            "generating_at_open": gen0, "generating_at_close": gen1,
            "ttft_median_s": statistics.median(ttft) if ttft else None,
            "ttft_max_s": ttft[-1] if ttft else None,
            "sustained": wait1 <= max(BACKLOG, wait0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)
    run._prepare_env()
    from bench import cell as C

    cell = C.load(args.workload)
    if cell.mix["loop"] != "open":
        print("sweep: only an open-loop cell has a knee", file=sys.stderr)
        return 2
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU; nothing was run", file=sys.stderr)
        return 2
    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()
    eng = run.build_engine(cell.config, cell.mix, args.seed)
    run.warm_up(eng, cell.config["vocab_size"], args.seed)
    knee = None
    for rate in sorted(args.rates):
        r = one_rate(eng, cell.mix, rate, args.seed, args.seconds)
        print(json.dumps(r), flush=True)
        if not r["sustained"]:
            break
        knee = rate
    if knee is None:
        print("sweep: no rate was sustained", file=sys.stderr)
        return 1
    mix = dict(cell.mix, knee_per_s=knee, rate_per_s=round(SHARE * knee, 4))
    text = json.dumps(mix, indent=2) + "\n"
    print(text)
    if args.write:
        with open(os.path.join(C.BENCH, "traffic",
                               cell_traffic(args.workload)), "w") as f:
            f.write(text)
    return 0


def cell_traffic(workload: str) -> str:
    from bench import cell as C

    bench = C._load_json(os.path.join(C.ROOT, "BENCHMARK.json"))
    w = next(w for w in bench["workloads"] if w["name"] == workload)
    return f"{w['traffic']}.json"


if __name__ == "__main__":
    sys.exit(main())

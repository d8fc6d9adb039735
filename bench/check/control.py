"""The readings a cell's limit is set from, on the chip.

    python3 bench/check/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, one window of the cell at its own load; then, over the same
sample of served requests, the program's widest gap (the lower reading)
and the control's: the reference computed with 4-bit activations -- the
precision below the configuration's 8 -- put in the program's place,
its first choice at each position read under the 8-bit reference (the
upper reading), each judged against the configuration's limit as a run
is (``check.judge``): the control has to come out not correct.  4 bits is
the step below the configuration's 8-bit activations, as int4 is below
int8.  One JSON line per seed on standard output.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402  (bench/run.py: the environment and the window)

CONTROL_ACT_BITS = 4


def readings(cell, seed: int, seconds: float, dev) -> dict:
    from bench import check

    s = run.serve_window(cell, seed, seconds, False, dev)
    mix, config = cell.mix, cell.config
    picked = check.sample(s["win"].records, seed, mix["check_requests"])
    t_pad = check.padded_length(picked, mix["engine"]["max_len"])
    ref = check.reference(config, seed)
    low = check.reference(config, seed, act_bits=CONTROL_ACT_BITS)
    t = time.monotonic()
    program = check.served_gaps(ref, picked, t_pad)
    control = check.control_gaps(ref, low, picked, t_pad)
    limit = config["check"]["worst_gap_limit"]
    return {"seed": seed, "tokens": int(len(program)),
            "program_correct": check.judge(
                {"worst_gap": (float(program.max()), limit)}),
            "control_correct": check.judge(
                {"worst_gap": (float(control.max()), limit)}),
            "requests": len(picked),
            "program_worst_gap": float(program.max()),
            "program_p99_gap": float(sorted(program)[int(0.99 * (len(program) - 1))]),
            "control_worst_gap": float(control.max()),
            "control_median_gap": float(sorted(control)[len(control) // 2]),
            "reference_s": time.monotonic() - t}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    run._prepare_env()
    from bench import cell as C

    cell = C.load(args.workload)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("control: no TPU; nothing was run", file=sys.stderr)
        return 2
    for seed in args.seeds:
        print(json.dumps(readings(cell, seed, args.seconds, dev)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The correctness check's control: each module's plain reference,
computed one precision below the one the configuration states, put in
the program's place on the timed path.  It must come out not correct.

    python3 bench/control.py --workload qwen3.batch --seeds 1,2,3 \\
        --seconds 10

For each seed, in this one process that holds the cell's chips, a whole
run of the cell (`harness.run` with `control`): set-up, a window of
`--seconds` at the cell's own load, and after it the comparison of the
sampled jobs, once with the program's outputs and once with the
control's in their place (`harness.control_samples`), each through the
same `check_samples` and `verdict`.  One JSON line per seed.  Each
module file names its control precision (`CONTROL`): float8 e4m3 for
`lm-forward`'s bfloat16.  Exits 1 if a control came out correct or the
program did not.  The benchmark's own runs never run it.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    harness.use_checkout_cache(ROOT)

    ok = True
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = harness.run(args.workload, seed, args.seconds, False,
                          root=ROOT, control=True)
        ok &= out["correct"] and not out["control_correct"]
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"],
                          "control_correct": out["control_correct"],
                          "control_checks": out["control_checks"],
                          "attempted": out["attempted"],
                          "device": out["device"]}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

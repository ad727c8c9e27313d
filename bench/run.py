#!/usr/bin/env python3
"""The benchmark's entry: one run of one cell of `BENCHMARK.json`.

    python3 bench/run.py --workload qwen3.batch --seed 7 --seconds 30 \\
        --trace 0

It runs on the machine it is started on and holds the cell's chips in
this one process.  Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.  Its last line of stdout is
one JSON object: `correct`, `attempted`, `failed`, `metrics` (the cell's
end-to-end metrics, or with `--trace 1` its per-layer metrics), `device`,
with `--trace 1` a `breakdown`, and last `checks`, each number compared
with the reference beside its limit (also the last lines of stderr).

JAX's persistent compilation cache is `.jax_cache/` at the root of the
checkout, whatever the environment says, so that only a cell's first run
in a checkout compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    harness.use_checkout_cache(ROOT)
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), root=ROOT, t_start=T_START)
    except (harness.NoChip, harness.SpecError) as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

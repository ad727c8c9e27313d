#!/usr/bin/env python3
"""Find the knee of a cell's open-loop tenant: the highest rate whose
completions keep up with its arrivals and whose backlog does not grow.

    python3 bench/knee.py --workload qwen3.interactive --tenant live \\
        --rates 1,2,3,4 --seconds 20 --seed 5

In one process that holds the cell's chips: the cell's set-up once, then
one window per rate (the other tenants as the traffic file has them),
each drained before the next.  One JSON line per rate: arrivals, jobs
completed in the window, the backlog (jobs sent and not done) at half the
window and at its end, and the latency p50/p95 of the jobs due in it.
The knee is the last rate that completed at least 95% of its arrivals in
the window with a backlog at the end no larger than at half-time plus
one; the cell's rate is then written into its traffic file by hand, at
about four fifths of it.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def backlog(jobs, tenant: str, t: float) -> int:
    return sum(1 for j in jobs if j.tenant == tenant and j.sent <= t
               and not (j.done <= t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--tenant", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated jobs per second, in order")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    harness.use_checkout_cache(ROOT)

    p = harness.prepare(args.workload, args.seed)
    knee = None
    try:
        for rate in [float(r) for r in args.rates.split(",")]:
            d = harness.Driver(p.daemon, p.cell.traffic, p.pools, args.seed,
                               sample_sizes={})
            t0 = time.perf_counter()
            t1 = d.window(t0, args.seconds, rates={args.tenant: rate})
            d.drain(t1 + harness.DRAIN_S)
            mine = [j for j in d.jobs if j.tenant == args.tenant]
            lat = [j.latency_ms for j in mine]
            done = sum(1 for j in mine if j.error is None and j.done <= t1)
            half, end = (backlog(d.jobs, args.tenant, t0 + args.seconds / 2),
                         backlog(d.jobs, args.tenant, t1))
            others = {t["name"]: sum(len(j.items) for j in d.jobs
                                     if j.tenant == t["name"]
                                     and j.error is None and j.done <= t1)
                      for t in p.cell.traffic["tenants"]
                      if t["name"] != args.tenant}
            keeps_up = done >= 0.95 * len(mine) and end <= half + 1
            if keeps_up:
                knee = rate
            print(json.dumps({
                "rate": rate, "arrivals": len(mine), "done_in_window": done,
                "backlog_half": half, "backlog_end": end,
                "p50_ms": harness.percentile(lat, 50),
                "p95_ms": harness.percentile(lat, 95),
                "failed": sum(1 for j in mine if j.error is not None),
                "other_chunks_done": others, "keeps_up": keeps_up}),
                flush=True)
    finally:
        p.daemon.shutdown()
    print(json.dumps({"workload": args.workload, "tenant": args.tenant,
                      "knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Shared benchmark helpers.  Every benchmark prints CSV rows:
    name,us_per_call,derived
where `derived` is a benchmark-specific figure of merit (speedup, ratio,
utilization, ...).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def row(name: str, us_per_call: float, derived) -> str:
    line = f"{name},{us_per_call:.3f},{derived}"
    print(line, flush=True)
    return line


def write_bench(out: str, pr: int, bench: str, metrics: dict,
                gates: dict | None = None) -> None:
    """Write the standard `BENCH_<pr>.json` artifact.

    One schema across every benchmark so the perf trajectory stays
    machine-readable PR over PR:

        {"pr": N, "bench": "<name>",
         "metrics": {...measurements...},
         "gates": {...bounds and pass/fail...}}

    `out` falsy (CI smoke runs pass `--out ''`) writes nothing.
    """
    if not out:
        return
    payload = {"pr": pr, "bench": bench, "metrics": metrics,
               "gates": gates or {}}
    with open(out, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")
    print(f"wrote {out}", flush=True)


def timeit(fn, *args, warmup: int = 1, iters: int = 5) -> float:
    """Median wall seconds per call."""
    for _ in range(warmup):
        fn(*args)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def run_subprocess(code: str, device_count: int | None = None,
                   timeout: int = 1200) -> str:
    """Run python code in a clean subprocess on the CPU (optionally with N
    fake host devices) and return stdout.  Benchmarks needing multiple
    devices use this so the parent keeps its 1-device view.  The child is
    held to the CPU because a parent that has imported JAX may hold the
    accelerator, and a chip belongs to one process at a time."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if device_count:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={device_count}")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"subprocess failed:\n{out.stderr[-4000:]}")
    return out.stdout

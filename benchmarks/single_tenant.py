"""Paper Fig. 19-21 analogue: single-tenant scaling with replication and
varying exposed parallelism.  A CPU-only tool: its live part runs on
fake host devices, and its times are CPU times, not device times.

Two layers of evidence (this container has ONE physical core, so concurrent
slot execution timeshares it — live wall-clock cannot show parallel
speedup):
  1. LIVE (subprocess, 4 host devices, shell host4_s4): correctness +
     scheduling behaviour when one tenant exposes 1..8 chunks; measures
     per-chunk service latency and verifies all slots get used.
  2. CALIBRATED SIM: per-chunk latency measured live feeds the cost model;
     the simulator then reports the scaling curve the policy achieves on
     hardware where slots are truly parallel (the paper's Fig 20/21 shape:
     linear until #slots, then time-multiplexing plateau).
"""
from __future__ import annotations

import json

from benchmarks.common import row, run_subprocess
from repro.core import ImplAlt, ModuleDescriptor, PolicyConfig, Registry, \
    SimJob, simulate

_LIVE = r"""
import json, time
import numpy as np
from repro.core import Daemon, Shell, default_registry, uniform_shell

shell = Shell(uniform_shell("host4_s4", (1, 4), 4))
reg = default_registry()
d = Daemon(shell, reg)
re = np.zeros((256, 256), np.float32)
# warm the module on every slot
h = d.submit("warm", "mandelbrot", [(re, re)] * 8)
h.future.result(600)
out = {}
for n_req in (1, 2, 3, 4, 6, 8):
    t0 = time.perf_counter()
    h = d.submit("u0", "mandelbrot", [(re, re)] * n_req)
    h.future.result(600)
    out[n_req] = time.perf_counter() - t0
slots_used = len({r[0] for r in
                  [(k[0],) for k in d._placements.keys()]})
out["slots_used"] = slots_used
d.shutdown()
print("RESULT::" + json.dumps(out))
"""


def main() -> list[str]:
    rows = []
    out = run_subprocess(_LIVE, device_count=4)
    res = json.loads([l for l in out.splitlines()
                      if l.startswith("RESULT::")][0][8:])
    slots_used = res.pop("slots_used")
    per_chunk = res["1"]
    for n_req, t in sorted(res.items(), key=lambda kv: int(kv[0])):
        rows.append(row(f"fig20/live/{n_req}_requests", t * 1e6,
                        f"rel={t / res['1']:.2f}"))
    rows.append(row("fig20/live/slots_used", 0.0, slots_used))

    # calibrated simulation: FIXED frame of work exposed at varying
    # parallelism on 4 truly-parallel slots (paper Fig 20/21 semantics)
    frame_ms = per_chunk * 1e3          # live-calibrated frame cost
    overhead = frame_ms * 0.04
    base = None
    for n_req in (1, 2, 3, 4, 6, 8, 12):
        reg = Registry()
        reg.register_module(ModuleDescriptor(
            name="mandelbrot", entrypoint="x:y",
            impls=(ImplAlt("x1", 1, frame_ms / n_req + overhead),)))
        r = simulate(reg, 4, [SimJob(0.0, "u0", "mandelbrot", n_req)],
                     PolicyConfig(reconfig_penalty_ms=overhead))
        base = base or r.makespan
        rows.append(row(f"fig21/sim/{n_req}_chunks",
                        r.makespan * 1e3,
                        f"frame_rel={r.makespan / base:.2f}"))
    return rows


if __name__ == "__main__":
    main()

"""Paper Table 3 analogue: decoupled compilation vs per-slot recompilation.

FOS claim: compile a module ONCE against the slot interface; relocation to
other congruent slots is (nearly) free via bitstream manipulation.  Standard
flow: compile the module separately for *each* region.

FOS-JAX measurement (subprocess with 8 host devices, shell host8_s4):
  - xilinx-flow analogue: place the module on slots 0..2 with JAX's
    caches cleared before each placement -> 3 full compiles;
  - FOS analogue: first compile (against the congruence class), then
    relocations to slots 1..2 with the in-process caches warm.
The persistent compilation cache is off throughout, so every cold compile
is cold.  A CPU-only tool: the times are XLA CPU compile times, not chip
compile times.
Derived figure = speedup of the FOS flow for 3 regions (paper: 1.74-2.34x).
"""
from __future__ import annotations

from benchmarks.common import row, run_subprocess

_CODE = r"""
import time, json
import jax
jax.config.update("jax_enable_compilation_cache", False)
from repro.core import Shell, uniform_shell
from repro.core.module import AccelModule
from repro.core import zoo

shell = Shell(uniform_shell("host8_s4", (1, 8), 4))
results = {}

# --- standard-flow analogue: independent compile per region (cold caches) ---
t_cold = []
for i in range(3):
    jax.clear_caches()
    mod = AccelModule(f"mandel_cold_{i}", zoo.build_mandelbrot, [1])
    t0 = time.perf_counter()
    mod.place(shell.slots[i], 1)
    t_cold.append(time.perf_counter() - t0)

# --- FOS flow: compile once, relocate to congruent slots (warm cache) ------
jax.clear_caches()
mod = AccelModule("mandel_fos", zoo.build_mandelbrot, [1])
t0 = time.perf_counter(); mod.place(shell.slots[0], 1)
t_first = time.perf_counter() - t0
t_reloc = []
for i in (1, 2):
    t0 = time.perf_counter(); mod.place(shell.slots[i], 1)
    t_reloc.append(time.perf_counter() - t0)

results = {
    "xilinx_total": sum(t_cold),
    "fos_total": t_first + sum(t_reloc),
    "first_compile": t_first,
    "reloc_mean": sum(t_reloc) / len(t_reloc),
}
print("RESULT::" + json.dumps(results))
"""


def main() -> list[str]:
    out = run_subprocess(_CODE, device_count=8)
    import json
    res = json.loads([l for l in out.splitlines()
                      if l.startswith("RESULT::")][0][8:])
    speedup = res["xilinx_total"] / res["fos_total"]
    rows = [
        row("table3/xilinx_flow_3regions", res["xilinx_total"] * 1e6,
            "3 independent compiles"),
        row("table3/fos_flow_3regions", res["fos_total"] * 1e6,
            f"speedup={speedup:.2f}x"),
        row("table3/first_compile", res["first_compile"] * 1e6, "cold"),
        row("table3/relocation", res["reloc_mean"] * 1e6,
            f"vs_cold={res['first_compile'] / max(res['reloc_mean'], 1e-9):.1f}x"),
    ]
    return rows


if __name__ == "__main__":
    main()

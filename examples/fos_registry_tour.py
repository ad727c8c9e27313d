"""Tour of the FOS logical-hardware abstraction (paper Listings 1-5).

Shows the JSON descriptors for shells and accelerators, decoupled
compilation against a slot interface, relocation to a congruent slot,
slot merging for a bigger implementation alternative, and the generic
driver invoking a module purely from its descriptor.

    PYTHONPATH=src python examples/fos_registry_tour.py
"""
import json
import sys
import time

sys.path.insert(0, "src")

import numpy as np                                            # noqa: E402

from repro.core import Shell, default_registry, uniform_shell  # noqa: E402
from repro.core.module import AccelModule, run_placement       # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def main():
    enable_compile_cache()
    reg = default_registry()

    print("== shell descriptor (paper Listing 1) ==")
    print(json.dumps(reg.shell("pod256_s4").to_json(), indent=2)[:400])

    print("\n== accelerator descriptor (paper Listing 2) ==")
    print(json.dumps(reg.module("mandelbrot").to_json(), indent=2))

    # single-device shell for the live part
    shell = Shell(uniform_shell("host1_s1", (1, 1), 1))
    desc = reg.module("mandelbrot")
    mod = AccelModule("mandelbrot", desc.load_builder(), desc.footprints)

    print("\n== decoupled compilation against the slot interface ==")
    t0 = time.perf_counter()
    pl = mod.place(shell.slots[0], 1)
    print(f"first compile: {(time.perf_counter() - t0) * 1e3:.1f} ms "
          f"(program {pl.compile_time_s * 1e3:.1f} ms)")

    t0 = time.perf_counter()
    pl2 = mod.place(shell.slots[0], 1)
    print(f"relocation (congruent slot): "
          f"{(time.perf_counter() - t0) * 1e3:.1f} ms "
          f"(program {pl2.compile_time_s * 1e3:.1f} ms)")

    print("\n== generic driver invocation (paper Listings 4/5) ==")
    rng = np.random.default_rng(0)
    re = rng.uniform(-2, 1, (256, 256)).astype(np.float32)
    im = rng.uniform(-1.5, 1.5, (256, 256)).astype(np.float32)
    out = run_placement(pl2, re, im)
    print(f"mandelbrot tile -> {np.asarray(out).shape}, "
          f"mean escape iter {float(np.asarray(out).mean()):.1f}")

    print("\n== module I/O signature (the ADR-map analogue) ==")
    prog = mod.program(shell.slots[0], 1)
    print(json.dumps(prog.signature(), indent=2)[:400])


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Record the small TPU trace of the daemon that `test_program_spans.py`
reads.

    python3 bench/tests/record_fos_trace.py <out_dir>

On one chip: a one-slot daemon serves one `mandelbrot` job to warm up,
then, inside a `bench.window` host span, three one-chunk jobs one after
another, the load generator in a `bench.submit` span while it submits and
in a `bench.wait` span while it waits for each result, with a 20 ms
`bench.wait` between jobs.  The daemon's own `fos.*` spans land in the
trace beside them.  The trace's `.xplane.pb` lands under `<out_dir>`; copy
it to `bench/tests/data/tpu_daemon.xplane.pb`.
"""
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]


def main(out: str) -> None:
    import jax
    import numpy as np
    from bench import trace
    from repro.core import Daemon, Shell, default_registry, uniform_shell

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_fos_trace: no TPU")
    d = Daemon(Shell(uniform_shell("host1_s1", (1, 1), 1)),
               default_registry())
    rng = np.random.default_rng(0)
    chunk = (rng.uniform(-2, 1, (256, 256)).astype(np.float32),
             rng.uniform(-1.5, 1.5, (256, 256)).astype(np.float32))
    try:
        d.submit("warm", "mandelbrot", [chunk]).future.result(timeout=600)
        trace.start(Path(out))
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.submit"):
                    h = d.submit("alice", "mandelbrot", [chunk])
                with jax.profiler.TraceAnnotation("bench.wait"):
                    h.future.result(timeout=600)
                    time.sleep(0.02)
        trace.stop()
    finally:
        d.shutdown()
    print(sorted(str(p) for p in Path(out).rglob("*.xplane.pb")))


if __name__ == "__main__":
    main(sys.argv[1])

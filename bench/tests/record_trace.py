#!/usr/bin/env python3
"""Record the small TPU trace that `test_trace.py` reads.

    python3 bench/tests/record_trace.py <out_dir>

On one chip: one execution of `prog_a` before the window, then inside a
`bench.window` host span a 5 ms `bench.wait` span, and three executions
of `prog_a` (a bf16 matmul) and two of `prog_b` (an elementwise pass),
each followed by a 20 ms `bench.wait` span.  The trace's `.xplane.pb`
lands under `<out_dir>`; copy it to `bench/tests/data/tpu_small.xplane.pb`.
"""
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp
    from bench import trace

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: no TPU")

    def prog_a(x):
        return x @ x

    def prog_b(x):
        return jnp.sin(x) * 2.0

    a, b = jax.jit(prog_a), jax.jit(prog_b)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    a(x).block_until_ready()
    b(x).block_until_ready()
    trace.start(Path(out))
    a(x).block_until_ready()
    with jax.profiler.TraceAnnotation("bench.window"):
        # the trace's device clock runs a millisecond or two off the
        # host's: keep the executions that far inside the window
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(0.005)
        for f in (a, b, a, b, a):
            with jax.profiler.TraceAnnotation("bench.submit"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.02)
    trace.stop()
    print(sorted(str(p) for p in Path(out).rglob("*.xplane.pb")))


if __name__ == "__main__":
    main(sys.argv[1])

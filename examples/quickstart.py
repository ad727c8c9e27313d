"""Quickstart: train a reduced model for a few steps, then serve it.

    PYTHONPATH=src python examples/quickstart.py [--arch qwen3-14b]

Every assigned architecture works (reduced configs run on CPU).
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro import configs                                    # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import ServeRun, serve               # noqa: E402
from repro.launch.train import TrainRun, train               # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b",
                    choices=configs.ARCH_IDS)
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()
    enable_compile_cache()

    print(f"== training {args.arch} (reduced) for {args.steps} steps ==")
    hist = train(TrainRun(arch=args.arch, steps=args.steps, global_batch=8,
                          seq_len=32, lr=3e-3, log_every=5))
    first, last = hist["loss"][0][1], hist["loss"][-1][1]
    print(f"loss {first:.3f} -> {last:.3f} "
          f"({hist['steps_per_sec']:.2f} steps/s)")

    print(f"== serving {args.arch} (reduced): prefill + 16 tokens ==")
    serve(ServeRun(arch=args.arch, batch=2, prompt_len=16,
                   max_new_tokens=16))


if __name__ == "__main__":
    main()

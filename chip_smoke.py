#!/usr/bin/env python3
"""Bring-up check: the FOS daemon serving on TPU chips, end to end.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the four-chip phase, and only it

One chip: a 1x1 shell and a `Daemon` with the default registry and a
preemptive policy serve three tenants through `Daemon.submit`:
`lm-forward` (granite-3-8b at its published widths, 16 layers, bf16,
8 x 512 tokens per chunk), `mandelbrot` and `sobel`.  Every output is
checked against a plain reference: NumPy for the image modules, and for
`lm-forward` the same weights run through `stack.forward` jitted directly,
outside the daemon.  Per module it prints the seconds compiling its
program (`compile_s`) and compiling and running the init that generates its
random weights on the chip (`init_s`: a stand-in for a checkpoint load,
which is not measured), first-chunk and warm per-chunk latency, the device
bytes in use once its phase ended, and the largest error against the
reference; and once, the process's peak device bytes.

Four chips: a fabric of two shells of two 1-chip slots runs chunks on
every chip, a cross-shell steal and one footprint-2 `lm-forward` on a
merged slot, and compares each chunk with the same chunk computed on one
chip.  It prints the devices that hold each chunk's output.  Programs that
span two chips are compiled outside the persistent compilation cache
(`AccelModule.place`).

The last line of stdout is one JSON object naming the device; it is
printed only when every phase passed.  Without a TPU the script exits
non-zero before serving anything.  Everything runs in this one process:
a chip belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

CHUNK_TIMEOUT_S = 900.0
# AccelModule's default weights key: the daemon's modules are built from it
WEIGHTS_KEY = 0


# -- plain references ---------------------------------------------------------


def mandelbrot_ref(re: np.ndarray, im: np.ndarray,
                   iters: int = 256) -> np.ndarray:
    """Escape-time counts, the module's iteration in NumPy float32."""
    zr = np.zeros_like(re)
    zi = np.zeros_like(im)
    count = np.zeros(re.shape, np.int32)
    two = np.float32(2.0)
    for _ in range(iters):
        zr2 = zr * zr - zi * zi + re
        zi2 = two * zr * zi + im
        inside = zr2 * zr2 + zi2 * zi2 < np.float32(4.0)
        zr = np.where(inside, zr2, zr)
        zi = np.where(inside, zi2, zi)
        count += inside
    return count


def sobel_ref(img: np.ndarray) -> np.ndarray:
    """Gradient magnitude of the 3x3 Sobel stencil, zero padding."""
    p = np.pad(img.astype(np.float64), 1)
    h, w = img.shape

    def at(dy, dx):
        return p[1 + dy:1 + dy + h, 1 + dx:1 + dx + w]

    gx = (at(-1, 1) - at(-1, -1)) + 2 * (at(0, 1) - at(0, -1)) \
        + (at(1, 1) - at(1, -1))
    gy = (at(1, -1) - at(-1, -1)) + 2 * (at(1, 0) - at(-1, 0)) \
        + (at(1, 1) - at(-1, 1))
    return np.sqrt(gx * gx + gy * gy)


def lm_reference(cfg, token_chunks: list, device) -> list[np.ndarray]:
    """Last-position logits of each chunk: the weights the daemon's module
    builds (same init, same key), run through `stack.forward` jitted
    directly on `device`.  Call it after the daemon released its copy:
    one chip holds one set of these weights."""
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.models import api, stack

    init = jax.jit(lambda k: api.init_params(cfg, k),
                   out_shardings=SingleDeviceSharding(device))
    params = init(jax.device_put(jax.random.PRNGKey(WEIGHTS_KEY), device))

    @jax.jit
    def forward(p, tokens):
        h, _ = stack.forward(p, cfg, {"tokens": tokens})
        return stack.unembed(p, cfg, h[:, -1:])[:, 0]

    outs = [np.asarray(forward(params, jax.device_put(t, device)))
            for t in token_chunks]
    del params
    return outs


# -- checks: each returns the error it measured, or fails the run -------------


def _fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check_mandelbrot(got, want) -> float:
    """Share of pixels whose escape count differs.  The iteration is
    chaotic at the set's boundary: a last-bit difference in one step (an
    FMA contraction, another operation order) moves the escape step of a
    boundary pixel and of no other, so a small share may differ; more than
    1% means the computation itself is wrong."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        _fail(f"mandelbrot shape {got.shape} != {want.shape}")
    frac = float(np.mean(got != want))
    if frac > 0.01:
        _fail(f"mandelbrot: {frac:.4%} of pixels differ (limit 1%)")
    return frac


# bf16 keeps 8 significant bits: unit roundoff 2**-8
_BF16_EPS = 2.0 ** -8


def check_sobel(got, want) -> float:
    """Largest absolute error.  The TPU's default precision may round the
    convolution's float32 operands to bf16 (relative 2**-8); with pixels in
    [0, 1) and sum |k| = 8 per direction, each gradient moves by at most
    8 * 2**-8 and the magnitude by sqrt(2) times that (0.044).  A wrong
    stencil or a lower precision (fp8: 2**-4) breaks it."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        _fail(f"sobel: shape {got.shape} or non-finite values")
    err = float(np.max(np.abs(got - want)))
    if err > np.sqrt(2) * 8 * _BF16_EPS:
        _fail(f"sobel: max |err| {err} > {np.sqrt(2) * 8 * _BF16_EPS}")
    return err


def check_lm(got, want, vocab: int) -> float:
    """Largest |error| relative to the largest |logit|.  Both sides run
    the same program on the same weights and chip kind; only another
    fusion or accumulation order separates them, which moves single bf16
    roundings (2**-8).  Allowing five of them (2e-2 of the logit scale)
    passes that and fails a program computing in fp8 (2**-4) or on other
    weights."""
    got = np.asarray(got, np.float64)[:, :vocab]
    want = np.asarray(want, np.float64)[:, :vocab]
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        _fail(f"lm-forward: shape {got.shape} or non-finite logits")
    rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    if rel > 5 * _BF16_EPS:
        _fail(f"lm-forward: max rel err {rel} > {5 * _BF16_EPS}")
    return rel


# -- serving helpers ----------------------------------------------------------


def _inputs(seed: int, vocab: int, tok_shape: tuple) -> dict:
    rng = np.random.default_rng(seed)
    re, im = np.meshgrid(np.linspace(-2.0, 1.0, 256, dtype=np.float32),
                         np.linspace(-1.5, 1.5, 256, dtype=np.float32))
    return {
        "mandelbrot": [(re, im)],
        "sobel": [(rng.random((1024, 1024)).astype(np.float32),)],
        # two chunks, drawn from the full vocabulary
        "lm-forward": [(rng.integers(0, vocab, tok_shape).astype(np.int32),)
                       for _ in range(2)],
    }


def _wait(handle) -> list:
    import jax
    outs = handle.future.result(timeout=CHUNK_TIMEOUT_S)
    return jax.block_until_ready(outs)


def _timed_chunk(daemon, module: str, chunk: tuple) -> tuple[float, object]:
    """Client-side latency of one single-chunk job: submit to result, the
    result waited on with `block_until_ready`."""
    t0 = time.perf_counter()
    out = _wait(daemon.submit("probe", module, [chunk]))[0]
    return time.perf_counter() - t0, out


def _device_bytes(device, key: str):
    """`bytes_in_use` now, or `peak_bytes_in_use` over the process."""
    return (device.memory_stats() or {}).get(key)


_IMAGE_REFS = {"mandelbrot": mandelbrot_ref, "sobel": sobel_ref}
_IMAGE_CHECKS = {"mandelbrot": check_mandelbrot, "sobel": check_sobel}


def run_one_chip(device, reg, lm_cfg, tok_shape, seed: int = 0) -> None:
    """Serve the three tenants on a 1x1 shell over `device`."""
    from repro.core import Daemon, PolicyConfig, Shell, uniform_shell

    spec = uniform_shell("chip1_s1", (1, 1), 1)
    reg.register_shell(spec)
    inputs = _inputs(seed, lm_cfg.vocab, tok_shape)
    daemon = Daemon(Shell(spec, [device]), reg,
                    PolicyConfig(preemptive=True))
    report: dict = {}
    lm_outs: list = []              # (chunk index, logits)
    try:
        # each module alone: the first chunk pays compile and weight init,
        # the next three are warm
        for module in ("lm-forward", "mandelbrot", "sobel"):
            chunk = inputs[module][0]
            first, out = _timed_chunk(daemon, module, chunk)
            outs = [out]
            warm = []
            for _ in range(3):
                t, out = _timed_chunk(daemon, module, chunk)
                warm.append(t)
                outs.append(out)
            placed = daemon.metrics["modules"][module]
            report[module] = {
                "compile_s": placed["compile_s"], "init_s": placed["init_s"],
                "first_chunk_s": first, "warm_chunk_s": warm,
                "bytes_in_use_after": _device_bytes(device, "bytes_in_use")}
            if module == "lm-forward":
                lm_outs += [(0, np.asarray(o)) for o in outs]
            else:
                want = _IMAGE_REFS[module](*chunk)
                report[module]["max_err"] = max(
                    _IMAGE_CHECKS[module](o, want) for o in outs)
            print(f"phase alone/{module}: {json.dumps(report[module])}",
                  flush=True)
        # three tenants at once under the preemptive policy
        handles = {
            "lm-forward": daemon.submit("carol", "lm-forward",
                                        inputs["lm-forward"], priority=3),
            "mandelbrot": daemon.submit("alice", "mandelbrot",
                                        inputs["mandelbrot"] * 2),
            "sobel": daemon.submit("bob", "sobel", inputs["sobel"] * 2),
        }
        mixed = {m: _wait(h) for m, h in handles.items()}
        lm_outs += [(i, np.asarray(o))
                    for i, o in enumerate(mixed["lm-forward"])]
        for module, check in _IMAGE_CHECKS.items():
            want = _IMAGE_REFS[module](*inputs[module][0])
            err = max(check(o, want) for o in mixed[module])
            report[module]["max_err"] = max(report[module]["max_err"], err)
        print(f"phase mixed: stats {json.dumps(daemon.stats)}", flush=True)
    finally:
        daemon.shutdown()
    del daemon, handles, mixed
    gc.collect()
    refs = lm_reference(lm_cfg, [c[0] for c in inputs["lm-forward"]],
                        device)
    report["lm-forward"]["max_err"] = max(
        check_lm(o, refs[i], lm_cfg.vocab) for i, o in lm_outs)
    for module in ("lm-forward", "mandelbrot", "sobel"):
        print(f"module {module}: {json.dumps(report[module])}", flush=True)
    # the whole process: the daemon's phases and the reference after them
    print(f"process peak_bytes_in_use: "
          f"{_device_bytes(device, 'peak_bytes_in_use')}", flush=True)


def run_four_chips(devices, reg, lm_cfg, tok_shape, seed: int = 0) -> None:
    """Two shells of two 1-chip slots: chunks on every chip, a cross-shell
    steal, and one footprint-2 `lm-forward` on a merged slot; then the
    same chunks on one chip."""
    import jax
    from repro.core import Daemon, PolicyConfig, Shell, uniform_shell

    shells = {}
    for i, name in enumerate(("shellA", "shellB")):
        spec = uniform_shell(name, (1, 2), 2)
        reg.register_shell(spec)
        shells[name] = Shell(spec, devices[2 * i:2 * i + 2])
    # lm-forward only in its two-slot form here: a merged slot
    lm = reg.module("lm-forward")
    reg.register_module(dataclasses.replace(lm, impls=(lm.impl_for(2),)))
    inputs = _inputs(seed, lm_cfg.vocab, tok_shape)
    daemon = Daemon(shells, reg, PolicyConfig(preemptive=True))
    # alice pins eight chunks to shellA, so the shells' queues differ and
    # the less loaded one steals; carol's lm-forward takes a whole shell
    jobs = {"alice/mandelbrot": ("mandelbrot", inputs["mandelbrot"] * 8,
                                 {"affinity": "shellA"}),
            "bob/sobel": ("sobel", inputs["sobel"] * 4, {}),
            "carol/lm-forward": ("lm-forward", inputs["lm-forward"][:1],
                                 {"priority": 3})}
    try:
        handles = {name: daemon.submit(name.split("/")[0], module, chunks,
                                       **kw)
                   for name, (module, chunks, kw) in jobs.items()}
        outs = {name: _wait(h) for name, h in handles.items()}
        fab = dict(daemon.fabric.stats)
        print(f"phase four-chip: stats {json.dumps(daemon.stats)} "
              f"fabric {json.dumps(fab)}", flush=True)
    finally:
        daemon.shutdown()
    used = set()
    for name, out in outs.items():
        for i, o in enumerate(out):
            devs = sorted(d.id for d in o.devices())
            used.update(devs)
            shards = [tuple(s.data.shape) for s in o.addressable_shards]
            print(f"chunk {name}[{i}]: devices {devs} shards {shards}",
                  flush=True)
    if used != {d.id for d in devices}:
        _fail(f"chunks ran on devices {sorted(used)}, not on all four")
    if fab["steals"] < 1:
        _fail("no cross-shell steal happened")
    lm_out = outs["carol/lm-forward"][0]
    if len(lm_out.devices()) != 2:
        _fail(f"lm-forward ran on {len(lm_out.devices())} chips, not 2")
    # the same chunks on one chip
    one = devices[0]
    lm_np = np.asarray(lm_out)
    del daemon, handles, outs["carol/lm-forward"], lm_out
    gc.collect()
    from repro.core import zoo
    mesh = jax.sharding.Mesh(np.array([[one]]), ("data", "model"))
    for name, (module, chunks, _) in jobs.items():
        if module == "lm-forward":
            want = lm_reference(lm_cfg, [chunks[0][0]], one)[0]
            err = check_lm(lm_np, want, lm_cfg.vocab)
        else:
            builder = (zoo.build_mandelbrot if module == "mandelbrot"
                       else zoo.build_sobel)
            fn = jax.jit(builder(mesh, 1).fn)
            err = max(_IMAGE_CHECKS[module](
                o, fn(None, *jax.device_put(c, one)))
                for o, c in zip(outs[name], chunks))
        print(f"vs one chip {name}: max err {err}", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} TPU chips, "
              f"JAX found {len(devices)}")
    d = devices[0]
    print(f"device: {d.platform} {d.device_kind} x{len(devices)}; "
          f"compile cache: {cache_dir}", flush=True)

    from repro.configs import granite_3_8b
    from repro.core import default_registry
    cfg, tok_shape = granite_3_8b.SERVED, (8, 512)
    if args.chips == 4:
        run_four_chips(devices[:4], default_registry(), cfg, tok_shape)
    else:
        run_one_chip(d, default_registry(), cfg, tok_shape)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()

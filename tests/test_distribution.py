"""Distribution correctness: sharded execution must match single-device
numerics.  Runs in a subprocess with 8 fake host devices so the main test
process keeps its 1-device view."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from repro import configs
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh
from repro.models import api, io, stack
from repro.optim import adamw
from repro.sharding import partition

failures = []

def check(name, a, b, tol=2e-4):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    err = np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9)
    if not np.isfinite(err) or err > tol:
        failures.append(f"{name}: rel err {err}")

for arch in ["llama3.2-3b", "qwen3-moe-30b-a3b", "mamba2-780m",
             "jamba-v0.1-52b", "whisper-large-v3", "phi-3-vision-4.2b"]:
    cfg = configs.get(arch, reduced=True)
    cfg = dataclasses.replace(cfg, param_dtype=jnp.float32,
                              compute_dtype=jnp.float32,
                              kv_dtype=jnp.float32)
    if cfg.moe is not None:
        # capacity large enough that no tokens drop: dense vs EP dispatch
        # then agree exactly (capacity-binding drop order is impl-defined)
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, impl="ep", capacity_factor=8.0))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    cell = io.smoke_cell("train", b=4, s=32)
    batch = io.make_batch(cfg, cell, jax.random.PRNGKey(1))

    # single-device reference (dense MoE oracle)
    ref_cfg = (dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, impl="dense")) if cfg.moe is not None else cfg)
    ref_loss = stack.build_loss_fn(ref_cfg)(params, batch)

    # sharded: 2x4 mesh, train rules
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = partition.make_rules("train")
    loss_fn = stack.build_loss_fn(cfg, mesh, batch_axes=rules.batch_axes)
    state_sh = partition.tree_shardings(api.param_specs(cfg), mesh, rules)
    batch_sh = partition.tree_shardings(
        io.input_axis_specs(cfg, cell)["batch"], mesh, rules)
    with jax.set_mesh(mesh):
        def wrapped(p, b):
            with partition.use_rules(rules):
                return loss_fn(p, b)
        sh_loss = jax.jit(wrapped, in_shardings=(state_sh, batch_sh))(
            jax.device_put(params, state_sh),
            jax.device_put(batch, batch_sh))
    check(f"{arch}/train_loss", sh_loss, ref_loss,
          tol=5e-3 if cfg.moe is not None else 2e-4)

    # decode path with sequence-sharded cache vs local cache
    serve_rules = partition.make_rules("serve")
    b_, s_ = 4, 16
    pcell = io.smoke_cell("prefill", b=b_, s=s_)
    pbatch = io.make_batch(cfg, pcell, jax.random.PRNGKey(2))
    prefill_ref = jax.jit(stack.build_prefill_fn(ref_cfg, max_len=s_ + 2))
    decode_ref = jax.jit(stack.build_decode_fn(ref_cfg))
    cache_r, logits_r = prefill_ref(params, pbatch)
    tok = jnp.argmax(logits_r, -1)[:, None].astype(jnp.int32)
    _, _, dlogits_r = decode_ref(params, cache_r, tok, jnp.int32(s_))

    with jax.set_mesh(mesh):
        def pre(p, b):
            with partition.use_rules(serve_rules):
                return stack.build_prefill_fn(
                    cfg, max_len=s_ + 2, mesh=mesh,
                    batch_axes=serve_rules.batch_axes)(p, b)
        def dec(p, c, t, pos):
            with partition.use_rules(serve_rules):
                return stack.build_decode_fn(
                    cfg, mesh=mesh,
                    batch_axes=serve_rules.batch_axes)(p, c, t, pos)
        params_sh = jax.device_put(params, partition.tree_shardings(
            api.param_specs(cfg), mesh, serve_rules))
        cache_s, logits_s = jax.jit(pre)(params_sh, pbatch)
        check(f"{arch}/prefill_logits", logits_s, logits_r, tol=1e-3)
        _, _, dlogits_s = jax.jit(dec)(params_sh, cache_s, tok,
                                       jnp.int32(s_))
        check(f"{arch}/decode_logits", dlogits_s, dlogits_r, tol=1e-3)

if failures:
    print("FAILURES:", failures)
    raise SystemExit(1)
print("DISTRIBUTION_OK")
"""


@pytest.mark.slow
def test_sharded_matches_single_device():
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _CODE], env=env,
                         capture_output=True, text=True, timeout=1500)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    assert "DISTRIBUTION_OK" in out.stdout

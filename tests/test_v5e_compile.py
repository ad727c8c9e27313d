"""Compile the main path's kernels and the served `lm-forward` program for a
described TPU v5e chip, at real widths, without a chip attached.

Interpret mode (tests/test_kernels.py) cannot see what the TPU compiler
refuses: blocks not aligned to its tiling, too much fast memory, a program
larger than the device.  These compiles can.  Nothing runs, so they say
nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    import importlib.util
    import os
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU compiler (libtpu) is not installed")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from repro.launch.compile_cache import persistent_cache_off
    # with libtpu present, a failure to describe the chip is a failure
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    with persistent_cache_off():
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_granite_heads_s2048(one_chip):
    from repro.kernels.flash_attention import ops as fa_ops
    b, s, hq, hkv, hd = 8, 2048, 32, 8, 128       # granite-3-8b heads
    q = _sds((b, s, hq, hd), jnp.bfloat16, one_chip)
    kv = _sds((b, s, hkv, hd), jnp.bfloat16, one_chip)
    _assert_kernel(fa_ops.flash_attention.lower(q, kv, kv).compile())


def test_flash_attention_qwen3_heads_s512(one_chip):
    from repro.kernels.flash_attention import ops as fa_ops
    b, s, hq, hkv, hd = 8, 512, 40, 8, 128        # Qwen3-14B heads, served
    q = _sds((b, s, hq, hd), jnp.bfloat16, one_chip)
    kv = _sds((b, s, hkv, hd), jnp.bfloat16, one_chip)
    _assert_kernel(fa_ops.flash_attention.lower(q, kv, kv).compile())


@pytest.mark.parametrize("devices,kernel", [(1, True), (2, False)])
def test_default_attention_takes_the_kernel_on_one_chip(topo, devices,
                                                        kernel):
    """The default attn_impl serves the kernel in a program on one chip
    and keeps the XLA path in one sharded over two."""
    from repro.models import layers
    mesh = jax.sharding.Mesh(np.array(topo.devices[:devices]).reshape(
        devices, 1), ("data", "model"))
    spec = layers.AttentionSpec(n_heads=8, n_kv_heads=2, head_dim=128)
    d, rep = 512, NamedSharding(mesh, P())
    params = {"wq": _sds((d, 1024), jnp.bfloat16, rep),
              "wk": _sds((d, 256), jnp.bfloat16, rep),
              "wv": _sds((d, 256), jnp.bfloat16, rep),
              "wo": _sds((1024, d), jnp.bfloat16, rep)}
    x = _sds((4, 512, d), jnp.bfloat16, NamedSharding(mesh, P("data")))

    def fn(p, x):
        return layers.attention(p, x, spec, jnp.arange(512))[0]

    text = jax.jit(fn).lower(params, x).compile().as_text()
    assert ("tpu_custom_call" in text) is kernel


def test_decode_attention_cache_4096(one_chip):
    from repro.kernels.decode_attention import ops as da_ops
    b, s, hq, hkv, hd = 8, 4096, 32, 8, 128
    q = _sds((b, hq, hd), jnp.bfloat16, one_chip)
    cache = _sds((b, s, hkv, hd), jnp.bfloat16, one_chip)
    length = _sds((), jnp.int32, one_chip)
    _assert_kernel(da_ops.decode_attention.lower(
        q, cache, cache, length, scale=hd ** -0.5).compile())


def test_ssd_scan_mamba2_780m_widths(one_chip):
    from repro.kernels.ssd_scan import ops as ssd_ops
    # mamba2-780m: d_inner 3072 = 48 heads x 64, d_state 128, chunk 128
    b, l, h, p, g, n = 1, 2048, 48, 64, 1, 128
    x = _sds((b, l, h, p), jnp.bfloat16, one_chip)
    dt = _sds((b, l, h), jnp.float32, one_chip)
    a = _sds((h,), jnp.float32, one_chip)
    bc = _sds((b, l, g, n), jnp.bfloat16, one_chip)
    _assert_kernel(ssd_ops.ssd.lower(x, dt, a, bc, bc, chunk=128,
                                     impl="pallas").compile())


def _served_program_fits_one_chip(topo, **builder_args):
    """`zoo.build_lm_forward`'s program and its on-slot weight init,
    compiled for a 1x1 slot; asserts both fit the chip and returns the
    compiled forward."""
    from repro.core import zoo
    mesh = jax.sharding.Mesh(np.array(topo.devices[:1]).reshape(1, 1),
                             ("data", "model"))
    prog = zoo.build_lm_forward(mesh, 1, **builder_args)
    w_sh = jax.tree.map(lambda p: NamedSharding(mesh, p), prog.weight_pspecs)
    in_sh = NamedSharding(mesh, prog.input_pspecs[0])
    weights = jax.tree.map(lambda s, sh: _sds(s.shape, s.dtype, sh),
                           prog.abstract_weights, w_sh)
    tokens = _sds(prog.abstract_inputs[0].shape, jnp.int32, in_sh)
    assert tokens.shape == (8, 512)
    fwd = jax.jit(prog.fn).lower(weights, tokens).compile()
    mem = fwd.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES, mem
    key = _sds((2,), jnp.uint32, NamedSharding(mesh, P()))
    init = jax.jit(prog.init_weights, out_shardings=w_sh).lower(key) \
        .compile().memory_analysis()
    assert init.output_size_in_bytes + init.temp_size_in_bytes \
        < V5E_HBM_BYTES, init
    return fwd


def test_lm_forward_full_width_fits_one_chip(topo):
    """The served program, granite-3-8b at published widths on a 1x1
    slot, its attention on the flash kernel, and the init that builds
    its weights there."""
    # the default attn_impl serves the flash kernel here
    _assert_kernel(_served_program_fits_one_chip(topo))


def test_moonlight_forward_full_width_fits_one_chip(topo):
    """The `moonlight.batch` cell's program, Moonlight-16B-A3B at published
    widths (9 of 27 layers, every expert), its routed experts on the
    `moe_gmm` kernel, and the init that builds its 10.9 GB of weights."""
    import json
    from pathlib import Path
    model = json.loads((Path(__file__).resolve().parents[1] / "bench" /
                        "configs" / "moonlight-16b-a3b-9l.1chip.json")
                       .read_text())
    fwd = _served_program_fits_one_chip(topo, model=model, batch=8, seq=512)
    text = fwd.as_text()
    from repro.kernels.moe_gmm.moe_gmm import KERNEL_NAME
    assert "tpu_custom_call" in text and KERNEL_NAME in text

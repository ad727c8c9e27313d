"""Module builders for the FOS runtime: the accelerator zoo.

These are the FOS-JAX analogues of the paper's case-study accelerators:
  - mandelbrot : compute-bound fractal iteration (paper section 5.5)
  - sobel      : memory-bound 3x3 stencil (paper section 5.5)
  - matmul     : generic dense kernel (spector-style)
  - lm_forward : granite-3-8b forward at its published widths

Each builder(mesh, footprint) -> ModuleProgram.  Bigger footprints map to
wider data-parallel slots; implementation alternatives additionally scale
internal work (e.g. mandelbrot unroll) the way the paper's DCT used bigger
module variants.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.module import ModuleProgram


def _data_axis(mesh) -> str:
    return mesh.axis_names[0]


def build_mandelbrot(mesh, footprint: int, *, size: int = 256,
                     iters: int = 256) -> ModuleProgram:
    """Compute-bound: escape-time iteration over an image tile."""
    n_dev = int(np.prod(mesh.devices.shape))
    axis = _data_axis(mesh)

    def fn(_, grid_re, grid_im):
        zr = jnp.zeros_like(grid_re)
        zi = jnp.zeros_like(grid_im)
        count = jnp.zeros(grid_re.shape, jnp.int32)

        def body(i, carry):
            zr, zi, count = carry
            zr2, zi2 = zr * zr - zi * zi + grid_re, 2 * zr * zi + grid_im
            inside = zr2 * zr2 + zi2 * zi2 < 4.0
            return (jnp.where(inside, zr2, zr), jnp.where(inside, zi2, zi),
                    count + inside.astype(jnp.int32))

        zr, zi, count = jax.lax.fori_loop(0, iters, body, (zr, zi, count))
        return count

    shape = (size, size)
    spec = P(axis, None)
    return ModuleProgram(
        fn=fn,
        abstract_weights=None,
        abstract_inputs=(jax.ShapeDtypeStruct(shape, jnp.float32),
                         jax.ShapeDtypeStruct(shape, jnp.float32)),
        weight_pspecs=None,
        input_pspecs=(spec, spec),
        init_weights=None,
    )


def build_sobel(mesh, footprint: int, *, size: int = 1024) -> ModuleProgram:
    """Memory-bound 3x3 stencil over an image tile."""
    axis = _data_axis(mesh)
    kx = jnp.array([[-1, 0, 1], [-2, 0, 2], [-1, 0, 1]], jnp.float32)
    ky = kx.T

    def fn(_, img):
        img4 = img[None, :, :, None]
        conv = functools.partial(
            jax.lax.conv_general_dilated, window_strides=(1, 1),
            padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        gx = conv(img4, kx[:, :, None, None])
        gy = conv(img4, ky[:, :, None, None])
        return jnp.sqrt(gx * gx + gy * gy)[0, :, :, 0]

    spec = P(axis, None)
    return ModuleProgram(
        fn=fn, abstract_weights=None,
        abstract_inputs=(jax.ShapeDtypeStruct((size, size), jnp.float32),),
        weight_pspecs=None, input_pspecs=(spec,), init_weights=None)


def build_matmul(mesh, footprint: int, *, m: int = 512, k: int = 512,
                 n: int = 512) -> ModuleProgram:
    """Dense kernel with weights (vadd/spector stand-in)."""
    axis = _data_axis(mesh)

    def fn(w, x):
        return jnp.maximum(x @ w["a"] + w["b"], 0.0)

    def init(key):
        ka, kb = jax.random.split(key)
        return {"a": jax.random.normal(ka, (k, n), jnp.float32) * 0.02,
                "b": jnp.zeros((n,), jnp.float32)}

    return ModuleProgram(
        fn=fn,
        abstract_weights={"a": jax.ShapeDtypeStruct((k, n), jnp.float32),
                          "b": jax.ShapeDtypeStruct((n,), jnp.float32)},
        abstract_inputs=(jax.ShapeDtypeStruct((m, k), jnp.float32),),
        weight_pspecs={"a": P(None, None), "b": P(None)},
        input_pspecs=(P(axis, None),),
        init_weights=init)


def build_lm_forward(mesh, footprint: int, *, reduced: bool = False,
                     batch: int = 8, seq: int = 512) -> ModuleProgram:
    """granite-3-8b teacher-forced forward over a [batch, seq] token chunk,
    returning the last position's logits [batch, padded vocab].

    Serves `configs.granite_3_8b.SERVED` (every width as published, 16
    layers, bf16 weights; the cuts are listed beside it).  `reduced=True`
    serves the 2-layer REDUCED config instead, for CPU tests and examples
    (pass it through the descriptor: `lm_forward_descriptor(reduced=True)`).
    """
    from repro.configs import granite_3_8b
    from repro.models import api, stack

    cfg = granite_3_8b.REDUCED if reduced else granite_3_8b.SERVED
    axis = _data_axis(mesh)

    def fn(params, tokens):
        h, _ = stack.forward(params, cfg, {"tokens": tokens})
        return stack.unembed(params, cfg, h[:, -1:])[:, 0]

    specs = api.param_specs(cfg)
    pspecs = jax.tree.map(lambda _: P(), specs,
                          is_leaf=lambda x: isinstance(x, tuple))
    return ModuleProgram(
        fn=fn,
        abstract_weights=api.abstract_params(cfg),
        abstract_inputs=(jax.ShapeDtypeStruct((batch, seq), jnp.int32),),
        weight_pspecs=pspecs,
        input_pspecs=(P(axis, None),),
        init_weights=lambda key: api.init_params(cfg, key))

"""Jit-ready grouped matmul: the Pallas kernel forward, and as backward the
VJP of the XLA reference (`ref.gmm_ref`), so that a model differentiated
through the kernel keeps the reference's gradients."""
from __future__ import annotations

import functools

import jax

from repro.kernels.moe_gmm import moe_gmm as knl
from repro.kernels.moe_gmm import ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def gmm(lhs, rhs: tuple, tile_group, n_tiles, scale=None,
        interpret: bool = False):
    """See `moe_gmm.gmm`."""
    return knl.gmm(lhs, rhs, tile_group, n_tiles, scale, interpret=interpret)


def _gmm_fwd(lhs, rhs, tile_group, n_tiles, scale, interpret):
    return knl.gmm(lhs, rhs, tile_group, n_tiles, scale,
                   interpret=interpret), (lhs, rhs, tile_group, n_tiles,
                                          scale)


def _gmm_bwd(interpret, res, g):
    lhs, rhs, tile_group, n_tiles, scale = res
    _, vjp = jax.vjp(lambda x, w, s: ref.gmm_ref(x, w, tile_group, n_tiles,
                                                 s), lhs, rhs, scale)
    dx, dw, ds = vjp(g)
    return dx, dw, None, None, ds


gmm.defvjp(_gmm_fwd, _gmm_bwd)

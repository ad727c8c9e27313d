"""Jit-ready grouped matmul: the Pallas kernels forward, and as backward the
VJP of the XLA reference (`ref.gmm_ref`), so that a model differentiated
through the kernels keeps the reference's gradients."""
from __future__ import annotations

import functools

import jax

from repro.kernels.moe_gmm import moe_gmm as knl
from repro.kernels.moe_gmm import ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def gmm(lhs, rhs: tuple, tile_group, n_tiles, scale=None,
        interpret: bool = False, combine=None):
    """`moe_gmm.gmm`; with combine [T, K], each token's rows, the rows
    written packed and summed by `moe_gmm.combine` ([T, N]).  See
    `ref.gmm_ref`."""
    out = knl.gmm(lhs, rhs, tile_group, n_tiles, scale,
                  packed_out=combine is not None, interpret=interpret)
    if combine is None:
        return out
    return knl.combine(out, combine, rhs[0].shape[2], lhs.dtype,
                       interpret=interpret)


def _gmm_fwd(lhs, rhs, tile_group, n_tiles, scale, interpret, combine):
    return gmm(lhs, rhs, tile_group, n_tiles, scale, interpret, combine), (
        lhs, rhs, tile_group, n_tiles, scale, combine)


def _gmm_bwd(interpret, res, g):
    lhs, rhs, tile_group, n_tiles, scale, combine = res
    _, vjp = jax.vjp(lambda x, w, s: ref.gmm_ref(
        x, w, tile_group, n_tiles, s, combine), lhs, rhs, scale)
    dx, dw, ds = vjp(g)
    return dx, dw, None, None, ds, None


gmm.defvjp(_gmm_fwd, _gmm_bwd)

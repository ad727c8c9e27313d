"""Pallas TPU grouped matmul over token-expert pairs sorted by expert.

The rows of `lhs` come in tiles of TILE rows, and each tile belongs to one
expert (`tile_group`): each expert's rows are padded up to a multiple of
TILE (`models.moe.dropless_layout`), so no tile spans two experts and
every row is computed once, with no mask.

  grid = (M / TILE,), sequential: a step is one tile of rows against its
  expert's whole weight matrix ([K, N], both dims whole, so no k loop and
  no accumulator).  Consecutive tiles of one expert ask for the same
  weight block, which the pipeline does not fetch again, so each expert's
  weights cross from HBM once per call.  Steps from `n_tiles` on (tiles
  past the last expert's padding) compute nothing and fetch nothing: their
  index maps are clamped to the last tile in use, whose output they leave
  as it is.  Rows of those tiles are undefined.

With two weights the step computes SwiGLU's first half in one pass over
the rows, silu(x @ w_gate) * (x @ w_up), times a per-row scale where one
is given (a routed pair's combine weight).  bf16 operands feed the MXU
with f32 accumulation; the result is cast to lhs's dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "moe_gmm"             # the custom call's name in the HLO
TILE = 128                          # rows of a tile; experts pad to it


def _kernel(tile_group, n_tiles, x_ref, *refs, n_w: int, scaled: bool):
    del tile_group
    w_refs, o_ref = refs[:n_w], refs[-1]

    @pl.when(pl.program_id(0) < n_tiles[0])
    def _():
        x = x_ref[...]
        acc = [jnp.dot(x, w[...], preferred_element_type=jnp.float32)
               for w in w_refs]
        out = jax.nn.silu(acc[0]) * acc[1] if n_w == 2 else acc[0]
        if scaled:
            out = out * refs[n_w][...]
        o_ref[...] = out.astype(o_ref.dtype)


def gmm(lhs, rhs: tuple, tile_group, n_tiles, scale=None, *,
        interpret: bool = False):
    """lhs [M, K] (M a multiple of TILE) against one weight per expert:
    rhs = (w,), w [E, K, N], gives lhs_tile @ w[tile_group[tile]];
    rhs = (w_gate, w_up) gives silu(lhs_tile @ w_gate[g]) *
    (lhs_tile @ w_up[g]).  tile_group [M / TILE] int32, non-decreasing;
    n_tiles [1] int32, the tiles in use (at least one); scale: None or
    [M] float32, each output row's factor.  -> [M, N], lhs.dtype."""
    m, k = lhs.shape
    n = rhs[0].shape[2]
    assert m % TILE == 0 and all(w.shape[1:] == (k, n) for w in rhs), (
        lhs.shape, [w.shape for w in rhs])

    def row(i, nt):
        return jnp.minimum(i, jnp.maximum(nt[0] - 1, 0))

    x_spec = pl.BlockSpec((TILE, k), lambda i, tg, nt: (row(i, nt), 0))
    w_spec = pl.BlockSpec((None, k, n),
                          lambda i, tg, nt: (tg[row(i, nt)], 0, 0))
    o_spec = pl.BlockSpec((TILE, n), lambda i, tg, nt: (row(i, nt), 0))
    scales = () if scale is None else (scale.reshape(m, 1),)
    s_spec = [pl.BlockSpec((TILE, 1), lambda i, tg, nt: (row(i, nt), 0))
              ] * len(scales)
    # every block double-buffered, and the f32 products
    item = lhs.dtype.itemsize
    vmem = 2 * (len(rhs) * k * n + TILE * (k + n)) * item \
        + len(rhs) * TILE * n * 4
    return pl.pallas_call(
        functools.partial(_kernel, n_w=len(rhs), scaled=bool(scales)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(m // TILE,),
            in_specs=[x_spec] + [w_spec] * len(rhs) + s_spec,
            out_specs=o_spec),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + (8 << 20)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(tile_group, n_tiles, lhs, *rhs, *scales)

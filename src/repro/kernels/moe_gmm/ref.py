"""XLA's grouped matmul over the same tiled layout as the kernel, and
XLA's row gather where the combine kernel moves rows by DMA."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.moe_gmm.moe_gmm import TILE


def gmm_ref(lhs, rhs: tuple, tile_group, n_tiles, scale=None,
            combine=None):
    """`ops.gmm` through `lax.ragged_dot`: each expert's rows are the
    tiles it owns.  Rows of tiles from n_tiles on are zero.  combine
    [T, K]: each token's float32 sum of the rows of its K pairs, k = 0 to
    K-1 (a row gather)."""
    e, tiles = rhs[0].shape[0], tile_group.shape[0]
    owned = (tile_group[:, None] == jnp.arange(e)) \
        & (jnp.arange(tiles) < n_tiles[0])[:, None]
    sizes = (owned.sum(0) * TILE).astype(jnp.int32)
    acc = [jax.lax.ragged_dot(lhs, w, sizes,
                              preferred_element_type=jnp.float32)
           for w in rhs]
    out = jax.nn.silu(acc[0]) * acc[1] if len(acc) == 2 else acc[0]
    if scale is not None:
        out = out * scale[:, None]
    out = out.astype(lhs.dtype)
    if combine is None:
        return out
    # gathered [K, T, N]: K outermost, so the sum needs no relayout
    return jnp.sum(out[combine.T].astype(jnp.float32), axis=0).astype(
        out.dtype)

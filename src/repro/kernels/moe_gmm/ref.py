"""XLA's grouped matmul over the same tiled layout as the kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.moe_gmm.moe_gmm import TILE


def gmm_ref(lhs, rhs: tuple, tile_group, n_tiles, scale=None):
    """`moe_gmm.gmm` through `lax.ragged_dot`: each expert's rows are the
    tiles it owns.  Rows of tiles from n_tiles on are zero."""
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
    return out.astype(lhs.dtype)

"""Jit-ready wrapper for the flash-attention kernel ([B,S,H,hd] layout)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as knl


@functools.partial(jax.jit,
                   static_argnames=("causal", "scale", "block_q", "block_k",
                                    "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: [B,Sq,Hq,hd]; k,v: [B,Sk,Hkv,hd] -> [B,Sq,Hq,hd].

    Pads sequence lengths up to block multiples (padded kv keys sit at
    causal-masked positions > every real query, padded q rows are sliced
    off).  The kernel is causal-only: its padding relies on the mask.
    """
    if not causal:
        raise NotImplementedError(
            "flash_attention is causal-only; use the XLA attention path "
            "for non-causal inputs")
    b, sq, hq, hd = q.shape
    sk = k.shape[1]
    block_q = min(block_q, max(16, 1 << (sq - 1).bit_length()))
    block_k = min(block_k, max(16, 1 << (sk - 1).bit_length()))
    pq = (-sq) % block_q
    pk = (-sk) % block_k
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    if pq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pk), (0, 0)))
    out = knl.flash_attention_bhsd(qt, kt, vt, causal=True, scale=scale,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interpret)
    out = out[:, :, :sq]
    return jnp.transpose(out, (0, 2, 1, 3))

"""Jit-ready wrapper for the flash-attention kernel ([B,S,H,hd] layout).

The forward runs the Pallas kernel; the backward is the VJP of the plain
reference (`ref.attention_ref`), recomputed from q, k and v, so that a
model differentiated through the kernel keeps the reference's gradients.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as knl
from repro.kernels.flash_attention import ref

LANES = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(jax.jit,
                   static_argnames=("causal", "scale", "block", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    scale: float | None = None, block: int | None = None,
                    interpret: bool = False):
    """Causal self-attention. q: [B,S,Hq,hd]; k,v: [B,S,Hkv,hd] ->
    [B,S,Hq,hd].

    The block defaults to `knl.pick_block` of the padded length.  S is
    padded up to a block multiple (padded keys lie after every real
    query, so the causal mask hides them; padded q rows are sliced off)
    and hd up to a lane multiple (zero columns change no score and give
    zero output columns).
    """
    if not causal:
        raise NotImplementedError(
            "flash_attention is causal-only; use the XLA attention path "
            "for non-causal inputs")
    if k.shape[1] != q.shape[1]:
        raise ValueError(f"self-attention only: {q.shape[1]} queries, "
                         f"{k.shape[1]} keys")
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    return _flash(q, k, v, scale, block, interpret)


def _pad_to(x, seq: int, hd: int):
    if x.shape[1] == seq and x.shape[3] == hd:
        return x
    return jnp.pad(x, ((0, 0), (0, seq - x.shape[1]), (0, 0),
                       (0, hd - x.shape[3])))


def _forward(q, k, v, scale, block, interpret):
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    if block is None:
        sp = _round_up(s, LANES)
        block = knl.pick_block(sp)
    else:
        sp = _round_up(s, block)
    hdp = _round_up(hd, LANES)
    q, k, v = _pad_to(q, sp, hdp), _pad_to(k, sp, hdp), _pad_to(v, sp, hdp)
    out = knl.flash_attention_bsd(
        q.reshape(b, sp, hq * hdp), k.reshape(b, sp, hkv * hdp),
        v.reshape(b, sp, hkv * hdp), n_heads=hq, n_kv_heads=hkv,
        scale=scale, block=block, interpret=interpret)
    return out.reshape(b, sp, hq, hdp)[:, :s, :, :hd]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, scale, block, interpret):
    return _forward(q, k, v, scale, block, interpret)


def _flash_fwd(q, k, v, scale, block, interpret):
    return _forward(q, k, v, scale, block, interpret), (q, k, v)


def _flash_bwd(scale, block, interpret, res, g):
    _, vjp = jax.vjp(functools.partial(ref.attention_ref, causal=True,
                                       scale=scale), *res)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)

"""Pallas TPU flash-attention (causal, GQA-native) — forward kernel.

Blockwise online-softmax over the layout the projections produce:
q and o are [B, S, Hq*hd], k and v [B, S, Hkv*hd] (a reshape of
[B, S, H, hd]), so the kernel needs no transposed copy of its own.

  grid = (batch, kv_heads, S/block, S/block), the kv-block dimension
  innermost and sequential ("arbitrary"); VMEM scratch carries each
  head's running (acc, m, l) from one kv block to the next.

Scores are kept transposed, [keys, queries]: the softmax then reduces
across sublanes (elementwise across vregs) rather than across lanes, its
running max and sum are lane-dense rows, and p.v accumulates as
(p.v)^T = v^T p^T, transposed once per tile when the output is written.
At Qwen3-14B's served shape (8 x 512, 40/8 heads of 128) on a TPU v5e
this took a call from 0.88 to 0.38 ms, layout copies of its inputs
included.

GQA is native: one grid step takes the g = Hq/Hkv query heads that share a
kv head (a [block, g*hd] block of q, lanes j*hd..(j+1)*hd for head j)
against one [block, hd] block of k and v, so k and v are fetched once per
group and no KV repeat materialises.

Causal: q and kv blocks are square.  Grid steps above the diagonal do
nothing and fetch nothing (the kv index_map is clamped to the diagonal
block, and a repeated block index is not copied again).  Inside a step
the block is cut into sub x sub tiles: on the diagonal block the tiles
above the diagonal are skipped at trace time and only the tiles on it are
masked (one triangle, built once), so the causal half of the square is
all that is computed.  The diagonal step is the last a q block needs, and
it writes the output.

Precision: bf16 operands feed the MXU with f32 accumulation; the softmax
is f32; p is cast to v's dtype before p.v, as the XLA path casts its
probabilities.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


KERNEL_NAME = "flash_attention"     # the custom call's name in the HLO
MAX_BLOCK = 512
SUB = 128                           # tile of the causal skip and the mask
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, group: int, head_dim: int, block: int,
                  sub: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    n = block // sub

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def attend(diagonal: bool):
        if diagonal:    # keys on rows, queries on columns: key <= query
            tri = (jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
                   <= jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1))
        for a in range(n):
            cols = pl.ds(a * sub, sub)
            for j in range(group):
                lanes = pl.ds(j * head_dim, head_dim)
                q = q_ref[0, cols, lanes]                     # [sub, hd]
                m, l = m_ref[j, :, cols], l_ref[j, :, cols]   # [1, sub]
                acc = acc_ref[lanes, cols]                    # [hd, sub] f32
                for c in range(a + 1 if diagonal else n):
                    keys = pl.ds(c * sub, sub)
                    # scores transposed, [keys, queries]: the softmax
                    # reduces across sublanes and m, l stay lane-dense
                    st = jax.lax.dot_general(
                        k_ref[0, keys, :], q, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
                    if diagonal and c == a:
                        st = jnp.where(tri, st, NEG_INF)
                    m_new = jnp.maximum(m, jnp.max(st, axis=0, keepdims=True))
                    p = jnp.exp(st - m_new)
                    alpha = jnp.exp(m - m_new)
                    l = alpha * l + jnp.sum(p, axis=0, keepdims=True)
                    v = v_ref[0, keys, :]                     # [sub, hd]
                    acc = alpha * acc + jax.lax.dot_general(  # (p.v)^T
                        v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    m = m_new
                if diagonal:
                    o_ref[0, cols, lanes] = (
                        acc / jnp.maximum(l, 1e-30)).T.astype(o_ref.dtype)
                else:
                    m_ref[j, :, cols], l_ref[j, :, cols] = m, l
                    acc_ref[lanes, cols] = acc

    pl.when(ki < qi)(lambda: attend(False))
    pl.when(ki == qi)(lambda: attend(True))


def pick_block(s: int) -> int:
    """The q and kv block for a padded length s: the whole sequence up to
    MAX_BLOCK (one grid step per batch row and kv head), else the largest
    of 512, 256, 128 that divides it."""
    if s <= MAX_BLOCK:
        return s
    return next(b for b in (512, 256, 128) if s % b == 0)


def flash_attention_bsd(q, k, v, *, n_heads: int, n_kv_heads: int,
                        scale: float, block: int, interpret: bool = False):
    """Causal self-attention. q: [B, S, Hq*hd]; k, v: [B, S, Hkv*hd]
    -> [B, S, Hq*hd].

    S must be a multiple of `block`; tiles are SUB x SUB, or the block
    where SUB does not divide it.  On a TPU hd, block and S must be
    multiples of 128 (ops.py pads).
    """
    b, s, qd = q.shape
    assert n_heads % n_kv_heads == 0
    g = n_heads // n_kv_heads
    hd = qd // n_heads
    assert k.shape == v.shape == (b, s, n_kv_heads * hd), (q.shape, k.shape)
    assert s % block == 0, (s, block)
    sub = SUB if block % SUB == 0 else block
    nb = s // block

    kernel = functools.partial(_flash_kernel, scale=scale, group=g,
                               head_dim=hd, block=block, sub=sub)
    # steps above the diagonal repeat the diagonal block: the pipeline
    # does not fetch a block whose index did not change
    kv_spec = pl.BlockSpec((1, block, hd), lambda bi, hi, qi, ki: (
        bi, jnp.minimum(ki, qi), hi))
    q_spec = pl.BlockSpec((1, block, g * hd),
                          lambda bi, hi, qi, ki: (bi, qi, hi))

    return pl.pallas_call(
        kernel,
        grid=(b, n_kv_heads, nb, nb),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, n_heads * hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * hd, block), jnp.float32),     # acc^T per head
            pltpu.VMEM((g, 1, block), jnp.float32),       # m
            pltpu.VMEM((g, 1, block), jnp.float32),       # l
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(q, k, v)

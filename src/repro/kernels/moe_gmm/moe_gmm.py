"""Pallas TPU grouped matmul over token-expert pairs sorted by expert, and
the gather-sum that brings each token's rows back (`combine`).

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

`combine` copies each token's K rows by DMA, through their indices held
in SMEM, and sums them.  HBM tiles an array's rows in groups of 8 (16 for
bf16), so one row of [M, D] cannot be copied alone: with `packed_out`
the matmul writes its rows packed instead (`pack_rows`), each row in
32-bit words, 128 a sublane, on whole tiles of its own, in as many bytes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "moe_gmm"             # the custom call's name in the HLO
COMBINE_NAME = "moe_combine"
TILE = 128                          # rows of a tile; experts pad to it
LANES = 128
COMBINE_BLOCK = 128                 # tokens of a combine step
_HI = np.uint32(0xFFFF0000)          # a word's high half


# -- rows packed for single-row copies ---------------------------------------


def packs(d: int, dtype) -> bool:
    """Whether rows of width d and `dtype` can be packed: 4-byte elements
    in whole sublanes of 128, or 2-byte ones in whole pairs of them."""
    size = jnp.dtype(dtype).itemsize
    return size in (2, 4) and d % (LANES * (4 // size)) == 0


def packed_sublanes(d: int, dtype) -> int:
    """Sublanes of one packed row: its D elements in 32-bit words (a
    16-bit dtype puts column j and j + D/2 in one word), 128 a sublane,
    padded up to a whole tile of 8."""
    assert packs(d, dtype), (d, dtype)
    return -(-d * jnp.dtype(dtype).itemsize // (4 * LANES) // 8) * 8


def _words(x):
    """x [n, d] -> its packed row's sublanes, each [n, 128] uint32."""
    d = x.shape[1]
    if x.dtype.itemsize == 4:
        words = jax.lax.bitcast_convert_type(x, jnp.uint32)
    else:
        bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
        words = (bits[:, : d // 2] >> 16) | (bits[:, d // 2:] & _HI)
    return [words[:, c * LANES:(c + 1) * LANES]
            for c in range(words.shape[1] // LANES)]


def pack_rows(x):
    """[N, D] -> [N * S, 128] uint32, row n on sublanes [n*S, (n+1)*S):
    in XLA, the layout `gmm(..., packed_out=True)` writes."""
    n, d = x.shape
    words = _words(x)
    pad = [jnp.zeros_like(words[0])] * (packed_sublanes(d, x.dtype)
                                        - len(words))
    return jnp.stack(words + pad, axis=1).reshape(-1, LANES)


def _column_pieces(d: int, dtype):
    """(word sublane, half) of each 128-column piece of a row, in column
    order; half None for a 4-byte dtype, else "lo" or "hi"."""
    if jnp.dtype(dtype).itemsize == 4:
        return [(c, None) for c in range(d // LANES)]
    half = d // 2 // LANES
    return [(c, "lo") for c in range(half)] + [(c, "hi") for c in range(half)]


def _unpack(words, half, dtype):
    """A [R, 128] uint32 slab -> its [R, 128] float32 columns."""
    if half is None:
        return jax.lax.bitcast_convert_type(words, dtype).astype(jnp.float32)
    words = words << 16 if half == "lo" else words & _HI
    return jax.lax.bitcast_convert_type(words, jnp.float32)


def _store_packed(ref, x, dtype):
    """Write x [n, d] (float32, rounded to dtype) packed into ref."""
    s = packed_sublanes(x.shape[1], dtype)
    for c, w in enumerate(_words(x.astype(dtype))):
        ref[pl.ds(c, x.shape[0], stride=s), :] = w


def _row_copy(src, s: int, src_row, dst, dst_row, sem):
    """The DMA of packed row `src_row` of src (HBM) to row `dst_row` of
    dst (VMEM), signalling sem."""
    return pltpu.make_async_copy(
        src.at[pl.ds(pl.multiple_of(src_row * s, 8), s)],
        dst.at[pl.ds(pl.multiple_of(dst_row * s, 8), s)], sem)


def _wait_rows(src, dst, sem):
    """Wait for the row copies that fill dst, all signalled on sem: a DMA
    semaphore counts what arrived, so one wait the size of dst (src is no
    smaller) waits for them all."""
    pltpu.make_async_copy(src.at[pl.ds(0, dst.shape[0])], dst, sem).wait()


# -- the grouped matmul -------------------------------------------------------


def _kernel(tile_group, n_tiles, x_ref, *refs, n_w: int, scaled: bool,
            packed_out: bool):
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
        if packed_out:
            _store_packed(o_ref, out, x.dtype)
        else:
            o_ref[...] = out.astype(o_ref.dtype)


def gmm(lhs, rhs: tuple, tile_group, n_tiles, scale=None, *,
        packed_out: bool = False, interpret: bool = False):
    """lhs [M, K] (M a multiple of TILE) against one weight per expert:
    rhs = (w,), w [E, K, N], gives lhs_tile @ w[tile_group[tile]];
    rhs = (w_gate, w_up) gives silu(lhs_tile @ w_gate[g]) *
    (lhs_tile @ w_up[g]).  tile_group [M / TILE] int32, non-decreasing;
    n_tiles [1] int32, the tiles in use (at least one); scale: None or
    [M] float32, each output row's factor.  -> [M, N], lhs.dtype; with
    packed_out, those rows packed (`pack_rows`, [M * S, 128] uint32)."""
    m, k = lhs.shape
    n = rhs[0].shape[2]
    assert m % TILE == 0 and all(w.shape[1:] == (k, n) for w in rhs), (
        lhs.shape, [w.shape for w in rhs])

    def row(i, nt):
        return jnp.minimum(i, jnp.maximum(nt[0] - 1, 0))

    x_spec = pl.BlockSpec((TILE, k), lambda i, tg, nt: (row(i, nt), 0))
    w_spec = pl.BlockSpec((None, k, n),
                          lambda i, tg, nt: (tg[row(i, nt)], 0, 0))
    if packed_out:
        s = packed_sublanes(n, lhs.dtype)
        out_shape = jax.ShapeDtypeStruct((m * s, LANES), jnp.uint32)
        o_spec = pl.BlockSpec((TILE * s, LANES),
                              lambda i, tg, nt: (row(i, nt), 0))
    else:
        out_shape = jax.ShapeDtypeStruct((m, n), lhs.dtype)
        o_spec = pl.BlockSpec((TILE, n), lambda i, tg, nt: (row(i, nt), 0))
    scales = () if scale is None else (scale.reshape(m, 1),)
    s_spec = [pl.BlockSpec((TILE, 1), lambda i, tg, nt: (row(i, nt), 0))
              ] * len(scales)
    # every block double-buffered, and the f32 products (twice over where
    # the rows are packed)
    item = lhs.dtype.itemsize
    vmem = 2 * (len(rhs) * k * n + TILE * (k + n)) * item \
        + len(rhs) * TILE * n * 4 * (1 + packed_out)
    return pl.pallas_call(
        functools.partial(_kernel, n_w=len(rhs), scaled=bool(scales),
                          packed_out=packed_out),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(m // TILE,),
            in_specs=[x_spec] + [w_spec] * len(rhs) + s_spec,
            out_specs=o_spec),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + (8 << 20)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(tile_group, n_tiles, lhs, *rhs, *scales)


# -- the combine ---------------------------------------------------------------


def _combine_kernel(row, ys, o_ref, buf, sem, *, t: int, k: int, d: int,
                    dtype):
    i, bt = pl.program_id(0), o_ref.shape[0]
    s = packed_sublanes(d, dtype)

    def fetch(block, slot):
        def token(b, carry):
            tok = jnp.minimum(block * bt + b, t - 1)
            for j in range(k):
                _row_copy(ys, s, row[tok * k + j], buf.at[slot, j], b,
                          sem.at[slot]).start()
            return carry
        jax.lax.fori_loop(0, bt, token, 0)

    @pl.when(i == 0)
    def _():
        fetch(0, 0)

    @pl.when(i + 1 < pl.num_programs(0))
    def _():
        fetch(i + 1, (i + 1) % 2)

    slot = i % 2
    for j in range(k):
        _wait_rows(ys, buf.at[slot, j], sem.at[slot])
    for p, (c, half) in enumerate(_column_pieces(d, dtype)):
        acc = None
        for j in range(k):
            x = _unpack(buf[slot, j, pl.ds(c, bt, stride=s), :], half, dtype)
            acc = x if acc is None else acc + x
        o_ref[:, p * LANES:(p + 1) * LANES] = acc.astype(o_ref.dtype)


def combine(ys, row, d: int, dtype, *, interpret: bool = False):
    """Each token's sum of its K rows: ys packed (`pack_rows` of [M, d]
    `dtype`, or `gmm(..., packed_out=True)`), row [T, K] int32, the row of
    each of the token's pairs.  The rows are summed in float32, k = 0 to
    K-1, and the sum cast to `dtype`.  -> [T, d]."""
    t, k = row.shape
    s = packed_sublanes(d, dtype)
    bt = min(COMBINE_BLOCK, -(-t // 16) * 16)
    out_block = bt * d * jnp.dtype(dtype).itemsize
    vmem = 2 * k * bt * s * LANES * 4 + 2 * out_block + 4 * bt * LANES * 4
    return pl.pallas_call(
        functools.partial(_combine_kernel, t=t, k=k, d=d, dtype=dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(pl.cdiv(t, bt),),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((bt, d), lambda i, r: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, k, bt * s, LANES), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((t, d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + (8 << 20)),
        interpret=interpret,
        name=COMBINE_NAME,
    )(row.reshape(-1), ys)

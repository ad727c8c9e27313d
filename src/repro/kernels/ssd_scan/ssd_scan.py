"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

grid = (batch, heads, n_chunks); the chunk dimension is sequential
("arbitrary") and the [P, N] SSD state lives in VMEM scratch across chunks —
the inter-chunk recurrence never round-trips HBM (the XLA path materialises
per-chunk states).  Within a chunk everything is quadratic in the chunk
length Q (default 128: MXU-aligned) and runs out of VMEM:

  working set ~ x(Q,P) + b,c(Q,N) + scores(Q,Q) + state(P,N)
  ~ 128*128*4B * 5 ~ 0.4 MiB.

B/C are group-shared across heads (G | H) via index_map head folding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, dtc_ref, cum_ref, cumc_ref, b_ref, c_ref,
                y_ref, state_out_ref, state_ref, *, chunk: int,
                n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)                       # [Q, P]
    dt_row = dt_ref[0, 0]                                     # [1, Q]
    dt_col = dtc_ref[0, 0]                                    # [Q, 1]
    cum_row = cum_ref[0, 0]                                   # [1, Q]
    cum_col = cumc_ref[0, 0]                                  # [Q, 1]
    total = cum_col[chunk - 1:, :]                            # [1, 1]
    b = b_ref[0, 0].astype(jnp.float32)                      # [Q, N]
    c = c_ref[0, 0].astype(jnp.float32)                      # [Q, N]

    # within-chunk decay L[q, k] = exp(cum_q - cum_k) for k <= q
    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lmat = jnp.where(ki <= qi, jnp.exp(cum_col - cum_row), 0.0)
    scores = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * lmat * dt_row  # [Q, K]
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # cross-chunk: y += exp(cum_q) * C_q . S_prev
    state = state_ref[...]                                    # [N, P]
    y_off = jax.lax.dot_general(
        c, state, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * jnp.exp(cum_col)
    y_ref[0, 0] = (y + y_off).astype(y_ref.dtype)
    # state update: S = exp(cum_Q) S + sum_k exp(cum_Q - cum_k) dt_k B_k x_k
    w = jnp.exp(total - cum_col) * dt_col                     # [Q, 1]
    s_new = jax.lax.dot_general(
        b * w, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                   # [N, P]
    # [1, 1] -> [N, 1] -> [N, P]: the TPU broadcasts one axis at a time
    decay = jnp.exp(jnp.broadcast_to(total, (state.shape[0], 1)))
    state_ref[...] = state * decay + s_new

    @pl.when(ci == n_chunks - 1)
    def _finish():
        state_out_ref[0, 0] = state_ref[...]


def ssd_pallas(x, dt, a, b, c, *, chunk: int = 128, initial_state=None,
               interpret: bool = False):
    """x: [B,L,H,P]; dt: [B,L,H]; a: [H]; b,c: [B,L,G,N].
    Returns (y [B,L,H,P] f32, final_state [B,H,P,N] f32).

    Matches repro.kernels.ssd_scan.ref.ssd_ref.  initial_state is folded in
    afterwards via the same decay algebra (kernels start from zero state).
    """
    bsz, seqlen, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    assert seqlen % chunk == 0
    nc = seqlen // chunk
    rep = h // g

    xt = jnp.transpose(x, (0, 2, 1, 3))                       # [B,H,L,P]
    dtt = jnp.transpose(dt, (0, 2, 1)).astype(jnp.float32)    # [B,H,L]
    # the within-chunk cumulative decay is computed here, by the same
    # cumsum as the reference (the TPU kernel language has none)
    adt = dtt * a.astype(jnp.float32)[None, :, None]
    cum = jnp.cumsum(adt.reshape(bsz, h, nc, chunk), axis=-1).reshape(
        bsz, h, seqlen)
    bt = jnp.transpose(b, (0, 2, 1, 3))                       # [B,G,L,N]
    ct = jnp.transpose(c, (0, 2, 1, 3))

    grid = (bsz, h, nc)
    row = pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci))
    col = pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0))
    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)
    y, state = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            # per-position scalars (dt, cum) arrive as a row and as a
            # column, the two layouts the kernel broadcasts them in
            row, col, row, col,
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci: (bi, hi // rep, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci: (bi, hi // rep, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, seqlen, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, dtt[:, :, None, :], dtt[..., None], cum[:, :, None, :],
      cum[..., None], bt, ct)

    y = jnp.transpose(y, (0, 2, 1, 3))                        # [B,L,H,P]
    state = jnp.transpose(state, (0, 1, 3, 2))                # [B,H,P,N]
    if initial_state is not None:
        # linearity: contribution of S0 decays by exp(sum a dt) cumulatively
        adt = dt.astype(jnp.float32) * a.astype(jnp.float32)[None, None, :]
        cum = jnp.cumsum(adt, axis=1)                         # [B,L,H]
        s0 = initial_state.astype(jnp.float32)                # [B,H,P,N]
        rep_ax = h // g
        ch = jnp.repeat(c.astype(jnp.float32), rep_ax, axis=2)  # [B,L,H,N]
        y_init = jnp.einsum("blhn,bhpn,blh->blhp", ch, s0, jnp.exp(cum))
        y = y + y_init
        state = state + s0 * jnp.exp(cum[:, -1])[..., None, None]
    return y, state

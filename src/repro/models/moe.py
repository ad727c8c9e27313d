"""Mixture-of-Experts FFN.

Three implementations.  Two share capacity semantics (tested for
equivalence):

- ``dense``: GShard-style one-hot dispatch/combine einsums.  Simple and
  shape-static; used as the correctness oracle and for tiny smoke configs.
- ``ep``: production expert-parallel path in ``jax.shard_map``.  Experts are
  sharded over the ``model`` mesh axis; activations arrive batch-sharded over
  (pod, data) and replicated over ``model``, so *dispatch is a local gather*
  (each model-shard already holds every token of its data shard) and combine
  is a single psum over ``model`` — the same all-reduce a TP MLP would pay.
  Expert weights are optionally ZeRO-3 sharded over ``data`` and all-gathered
  just-in-time inside the shard_map (``fsdp_experts``).

and one is dropless:

- ``dropless``: every token-expert pair is computed once, with no
  capacity.  Pairs are sorted by expert into rows padded per expert to a
  tile (``dropless_layout``), run through a grouped matmul (the Pallas
  ``moe_gmm`` kernel on one TPU chip, XLA's ``ragged_dot`` elsewhere),
  each row scaled by its pair's combine weight, then gathered back to its
  token and summed.

Routing (``route``): softmax top-k with normalised combine weights and a
load-balancing aux loss (Switch-style), or DeepSeek-V3's sigmoid scores
chosen by top-k of score + a per-expert bias and weighted by the chosen
scores, normalised.  ``dense`` and ``ep`` drop what exceeds capacity.
A shared expert (``params["shared"]``), where present, is added to every
implementation's output.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.moe_gmm.moe_gmm import TILE
from repro.models import layers


@dataclasses.dataclass(frozen=True)
class MoESpec:
    n_experts: int
    top_k: int
    d_ff: int                   # per-expert hidden width
    capacity_factor: float = 1.25
    impl: str = "dense"         # "dense" | "ep" | "dropless"
    fsdp_experts: bool = False  # ZeRO-3 gather of expert weights over "data"
    ep_axis: str = "model"
    fsdp_axis: str = "data"
    router: str = "softmax"     # "softmax" | "sigmoid_bias"
    routed_scale: float = 1.0   # sigmoid_bias: times the combine weights

    def __post_init__(self):
        if self.router not in ("softmax", "sigmoid_bias"):
            raise ValueError(f"router {self.router!r}")
        if self.router == "softmax" and self.routed_scale != 1.0:
            raise ValueError("routed_scale is the sigmoid_bias router's")
        if self.router == "sigmoid_bias" and self.impl == "ep":
            raise ValueError("the ep path routes by softmax only")


def router_probs(params, x: jax.Array, spec: MoESpec):
    """x: [T, D] -> (top-k probs [T,K], top-k idx [T,K], aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        params["w_router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, spec.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # Switch/GShard load-balance loss: E * sum_e f_e * p_e
    pe = jnp.mean(probs, axis=0)                               # [E]
    fe = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_i, spec.n_experts), axis=1), axis=0)
    aux = spec.n_experts * jnp.sum(pe * fe)
    return top_p, top_i, aux


def router_sigmoid_bias(params, x: jax.Array, spec: MoESpec):
    """DeepSeek-V3's `noaux_tc` router with one expert group: sigmoid
    scores; the top-k of score + `router_bias` chosen; the chosen scores,
    normalised, times `routed_scale` as combine weights.  No aux loss."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        params["w_router"].astype(jnp.float32))
    scores = jax.nn.sigmoid(logits)
    _, top_i = jax.lax.top_k(
        scores + params["router_bias"].astype(jnp.float32), spec.top_k)
    # the chosen scores by a masked sum (a gather of single elements
    # costs the chip as much as one of whole rows)
    top_w = jnp.sum(jnp.where(
        top_i[..., None] == jnp.arange(spec.n_experts), scores[:, None, :],
        0.0), axis=-1)
    top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    return top_w * spec.routed_scale, top_i, jnp.zeros((), jnp.float32)


def route(params, x: jax.Array, spec: MoESpec):
    """x: [T, D] -> (combine weights [T,K], experts [T,K], aux_loss)."""
    if spec.router == "sigmoid_bias":
        return router_sigmoid_bias(params, x, spec)
    return router_probs(params, x, spec)


def _expert_ffn(w1, w3, w2, x):
    """Batched per-expert SwiGLU: x [E, C, D]; w1/w3 [E, D, F]; w2 [E, F, D]."""
    gate = jnp.einsum("ecd,edf->ecf", x, w1.astype(x.dtype))
    up = jnp.einsum("ecd,edf->ecf", x, w3.astype(x.dtype))
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    return jnp.einsum("ecf,efd->ecd", h, w2.astype(x.dtype))


def _capacity(n_tokens: int, spec: MoESpec) -> int:
    c = int(n_tokens * spec.top_k * spec.capacity_factor / spec.n_experts)
    return max(8, ((c + 7) // 8) * 8)  # pad to 8 for TPU-friendly tiling


# ---------------------------------------------------------------------------
# dense (one-hot) oracle
# ---------------------------------------------------------------------------


def moe_dense(params, x: jax.Array, spec: MoESpec):
    """x: [B, S, D] -> (y, aux). One-hot dispatch; exact capacity semantics."""
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    top_p, top_i, aux = route(params, xt, spec)
    cap = _capacity(t, spec)
    # position of each (token, k) within its expert queue
    onehot = jax.nn.one_hot(top_i, spec.n_experts, dtype=jnp.int32)  # [T,K,E]
    flat = onehot.reshape(t * spec.top_k, spec.n_experts)
    pos = jnp.cumsum(flat, axis=0) * flat - 1                  # [T*K, E]
    pos = pos.reshape(t, spec.top_k, spec.n_experts)
    within = (pos >= 0) & (pos < cap)
    # dispatch tensor [T, E, C]
    disp = jnp.zeros((t, spec.n_experts, cap), dtype=x.dtype)
    pos_c = jnp.clip(pos, 0, cap - 1)
    disp = disp.at[
        jnp.arange(t)[:, None, None],
        jnp.broadcast_to(jnp.arange(spec.n_experts)[None, None, :],
                         pos.shape),
        pos_c,
    ].add(jnp.where(within, 1.0, 0.0).astype(x.dtype))
    combine = disp * jnp.einsum(
        "tk,tke->te", top_p.astype(x.dtype),
        onehot.astype(x.dtype))[:, :, None]
    xe = jnp.einsum("tec,td->ecd", disp, xt)                   # [E, C, D]
    ye = _expert_ffn(params["w1"], params["w3"], params["w2"], xe)
    yt = jnp.einsum("tec,ecd->td", combine, ye)
    return yt.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# expert-parallel shard_map path
# ---------------------------------------------------------------------------


def _sorted_dispatch_local(xt, top_p, top_i, e_lo, e_loc, cap,
                           spec: MoESpec):
    """Gather tokens destined for local experts [e_lo, e_lo + e_loc).

    xt: [T, D]; e_lo may be traced (axis_index), e_loc must be static.
    Returns (xe [E_loc, C, D], src_idx [E_loc, C], weight [E_loc, C]) where
    src_idx rows index into xt (clipped; weight 0 when slot empty / over
    capacity).
    """
    t = xt.shape[0]
    flat_i = top_i.reshape(-1)                                  # [T*K]
    flat_p = top_p.reshape(-1)
    flat_src = jnp.repeat(jnp.arange(t), spec.top_k)
    local = (flat_i >= e_lo) & (flat_i < e_lo + e_loc)
    # stable sort by expert id; non-local pushed to the end
    key = jnp.where(local, flat_i - e_lo, e_loc)
    order = jnp.argsort(key, stable=True)
    key_s = key[order]
    src_s = flat_src[order]
    p_s = flat_p[order]
    # rank within expert group
    same = jax.nn.one_hot(key_s, e_loc + 1, dtype=jnp.int32)
    rank = (jnp.cumsum(same, axis=0) * same).sum(-1) - 1        # [T*K]
    within = (key_s < e_loc) & (rank < cap)
    slot = jnp.where(within, key_s * cap + jnp.clip(rank, 0, cap - 1), e_loc * cap)
    src_idx = jnp.full((e_loc * cap + 1,), 0, dtype=jnp.int32)
    weight = jnp.zeros((e_loc * cap + 1,), dtype=jnp.float32)
    src_idx = src_idx.at[slot].set(jnp.where(within, src_s, 0))
    weight = weight.at[slot].add(jnp.where(within, p_s, 0.0))
    src_idx = src_idx[:-1].reshape(e_loc, cap)
    weight = weight[:-1].reshape(e_loc, cap)
    xe = xt[src_idx.reshape(-1)].reshape(e_loc, cap, -1)
    xe = xe * (weight[..., None] > 0).astype(xe.dtype)
    return xe, src_idx, weight


def moe_ep(params, x: jax.Array, spec: MoESpec, mesh: jax.sharding.Mesh,
           batch_axes=("data",)):
    """Expert-parallel MoE under shard_map over the full mesh.

    x: [B, S, D] with batch sharded over ``batch_axes`` and replicated over
    the EP axis. Expert weights w1/w3/w2: [E, D, F]/[E, D, F]/[E, F, D],
    sharded E over ``ep_axis`` (+ D or F over ``fsdp_axis`` if fsdp_experts).
    """
    b, s, d = x.shape
    ep = spec.ep_axis
    n_ep = mesh.shape[ep]
    assert spec.n_experts % n_ep == 0, (spec.n_experts, n_ep)
    e_loc = spec.n_experts // n_ep
    fsdp_w = spec.fsdp_axis if spec.fsdp_experts else None

    w_spec = P(ep, fsdp_w, None)
    w2_spec = P(ep, None, fsdp_w)
    x_spec = P(batch_axes, None, None)

    def body(wr, w1, w3, w2, xl):
        if spec.fsdp_experts:
            w1 = jax.lax.all_gather(w1, spec.fsdp_axis, axis=1, tiled=True)
            w3 = jax.lax.all_gather(w3, spec.fsdp_axis, axis=1, tiled=True)
            w2 = jax.lax.all_gather(w2, spec.fsdp_axis, axis=2, tiled=True)
        bl, sl, _ = xl.shape
        t = bl * sl
        xt = xl.reshape(t, d)
        top_p, top_i, aux = router_probs({"w_router": wr}, xt, spec)
        cap = _capacity(t, spec)
        idx = jax.lax.axis_index(ep)
        e_lo = idx * e_loc
        xe, src_idx, weight = _sorted_dispatch_local(
            xt, top_p, top_i, e_lo, e_loc, cap, spec)
        ye = _expert_ffn(w1, w3, w2, xe)                        # [E_loc, C, D]
        ye = ye * weight[..., None].astype(ye.dtype)
        yt = jnp.zeros((t, d), dtype=ye.dtype)
        yt = yt.at[src_idx.reshape(-1)].add(ye.reshape(-1, d))
        yt = jax.lax.psum(yt, ep)
        # aux differs per data shard and is identical across ep shards;
        # average over every mesh axis so the out_spec P() (fully
        # replicated) is semantically true.
        from repro.sharding.partition import flat_axes
        aux = jax.lax.pmean(aux, flat_axes(batch_axes) + (ep,))
        return yt.reshape(bl, sl, d), aux

    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), w_spec, w_spec, w2_spec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(params["w_router"], params["w1"], params["w3"], params["w2"], x)
    return y, aux


# ---------------------------------------------------------------------------
# dropless: sorted pairs through a grouped matmul
# ---------------------------------------------------------------------------


def dropless_rows(n_pairs: int, n_experts: int, tile: int = TILE) -> int:
    """Rows of the padded layout: enough for any routing, each expert's
    rows padded up to a tile multiple."""
    return -(-(n_pairs + n_experts * (tile - 1)) // tile) * tile


def dropless_layout(top_i: jax.Array, top_w: jax.Array, n_experts: int,
                    tile: int = TILE):
    """Sort the token-expert pairs (`top_i` [T, K], pair p = t*K + k) by
    expert, stably, into rows where each expert's pairs start on a tile
    boundary.  Returns (row [T*K]: the row of each pair; pair [M]: the
    pair of each row, T*K for a padding row; scale [M]: each row's
    combine weight (`top_w`), 0 for a padding row; tile_expert [M/tile]:
    the expert of each tile, non-decreasing; n_tiles [1]: the tiles in
    use).  Gathers and scatters of single elements cost the chip about as
    much as those of whole rows, so there is one, placing index and
    weight together."""
    pairs = top_i.reshape(-1)
    n = pairs.shape[0]
    assert n < 2 ** 24, n                       # indices exact in float32
    onehot = jax.nn.one_hot(pairs, n_experts, dtype=jnp.int32)    # [P, E]
    padded = (jnp.sum(onehot, axis=0) + tile - 1) // tile * tile  # [E]
    ends = jnp.cumsum(padded)
    # the row each pair would take in each expert's block; its own kept
    row = jnp.sum(onehot * (jnp.cumsum(onehot, axis=0) - 1 + ends - padded),
                  axis=-1)
    m = dropless_rows(n, n_experts, tile)
    placed = jnp.zeros((m, 2), jnp.float32).at[:, 0].set(n).at[row].set(
        jnp.stack([jnp.arange(n, dtype=jnp.float32),
                   top_w.reshape(-1).astype(jnp.float32)], axis=-1))
    tile_start = jnp.arange(m // tile, dtype=jnp.int32)[:, None] * tile
    tile_expert = jnp.minimum(jnp.sum(ends[None, :] <= tile_start, -1),
                              n_experts - 1).astype(jnp.int32)
    n_tiles = (ends[-1:] // tile).astype(jnp.int32)
    return (row, placed[:, 0].astype(jnp.int32), placed[:, 1], tile_expert,
            n_tiles)


def _gmm(lhs, rhs: tuple, tile_expert, n_tiles, layer, scale, mesh):
    """Layer `layer` of each whole stack in `rhs` ([L, E, K, N]) against the
    tiled rows (`scale`: None, or each output row's factor): the Pallas
    kernel on a TPU in a program on one device,
    which reads the layer's experts in place (the stacks viewed as L x E
    experts); XLA's ragged_dot on any other platform, and across devices
    (a pallas_call is not partitioned)."""
    from repro.kernels.moe_gmm import ops, ref

    def kernel(lhs, rhs, tile_expert, n_tiles, layer, scale):
        n_exp = rhs[0].shape[1]
        return ops.gmm(lhs, tuple(w.reshape(-1, *w.shape[2:]) for w in rhs),
                       tile_expert + layer * n_exp, n_tiles, scale, False)

    def xla(lhs, rhs, tile_expert, n_tiles, layer, scale):
        return ref.gmm_ref(lhs, tuple(w[layer] for w in rhs), tile_expert,
                           n_tiles, scale)

    args = (lhs, rhs, tile_expert, n_tiles, layer, scale)
    if layers._mesh_devices(lhs, mesh) == 1:
        return jax.lax.platform_dependent(*args, tpu=kernel, default=xla)
    return xla(*args)


def moe_dropless(params, x: jax.Array, spec: MoESpec, mesh=None):
    """x: [B, S, D] -> (y, aux, experts [B, S, K]).  Every pair computed
    once, none dropped.

    `params["w1"]`, `["w3"]`, `["w2"]` are one layer's experts, or whole
    stacks over the layers with `params["layer"]` the index of this one
    (as `hoist_experts` passes them)."""
    b, s, d = x.shape
    t, k = b * s, spec.top_k
    xt = x.reshape(t, d)
    top_w, top_i, aux = route(params, xt, spec)
    row, pair, scale, tile_expert, n_tiles = dropless_layout(
        top_i, top_w, spec.n_experts)
    w1, w3, w2 = (params[n].astype(x.dtype) for n in _EXPERT_WEIGHTS)
    layer = params.get("layer")
    if layer is None:
        w1, w3, w2, layer = w1[None], w3[None], w2[None], jnp.int32(0)
    # a padding row (pair T*K) reads the last token and is zeroed
    xs = jnp.where((pair < t * k)[:, None],
                   jnp.take(xt, pair // k, axis=0, mode="clip"), 0)
    # each row scaled by its pair's combine weight before the down
    # projection (linear in its rows), so that the combine is a plain sum
    h = _gmm(xs, (w1, w3), tile_expert, n_tiles, layer, scale, mesh)
    ys = _gmm(h, (w2,), tile_expert, n_tiles, layer, None, mesh)
    # gathered [K, T, D]: K outermost, so the sum needs no relayout
    yt = jnp.sum(ys[row.reshape(t, k).T].astype(jnp.float32), axis=0)
    return (yt.astype(x.dtype).reshape(b, s, d), aux,
            top_i.reshape(b, s, k))


_EXPERT_WEIGHTS = ("w1", "w3", "w2")


def hoist_experts(blocks, moe_subs: list[str], spec: MoESpec | None):
    """Ready stacked layers (`blocks[sub]["moe"]` for each sub-layer in
    `moe_subs`, leaves with a leading groups axis) for a scan over them.

    The dropless path's kernel reads a layer's experts in place out of the
    whole stacks, where an operand the scan slices per layer would be
    copied each layer (the experts are most of the weights).  So its
    experts' weights leave the scanned tree, whole, and each group's index
    enters it.  Returns (blocks to scan, per_group: one scanned group's
    params -> the params the MoE sub-layers take).  Other implementations
    scan their experts as they are."""
    if spec is None or spec.impl != "dropless" or not moe_subs:
        return blocks, lambda group: group
    blocks, whole = dict(blocks), {}
    for name in moe_subs:
        sub = dict(blocks[name])
        moe_p = dict(sub["moe"])
        whole[name] = {k: moe_p.pop(k) for k in _EXPERT_WEIGHTS}
        blocks[name] = dict(sub, moe=moe_p)
    n_groups = jax.tree.leaves(whole)[0].shape[0]
    blocks["layer"] = jnp.arange(n_groups, dtype=jnp.int32)

    def per_group(group):
        group = dict(group)
        layer = group.pop("layer")
        for name, ws in whole.items():
            sub = group[name]
            group[name] = dict(sub, moe=dict(sub["moe"], layer=layer, **ws))
        return group

    return blocks, per_group


def moe_ffn(params, x, spec: MoESpec, mesh=None, batch_axes=("data",)):
    """x: [B, S, D] -> (y, aux, experts): `experts` [B, S, K], each token's
    chosen experts, from the dropless path; None from the capacity paths."""
    experts = None
    if spec.impl == "dropless":
        y, aux, experts = moe_dropless(params, x, spec, mesh)
    elif spec.impl == "dense" or mesh is None:
        y, aux = moe_dense(params, x, spec)
    else:
        y, aux = moe_ep(params, x, spec, mesh, batch_axes)
    if "shared" in params:
        y = y + layers.swiglu_mlp(params["shared"], x)
    return y, aux, experts

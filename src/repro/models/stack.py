"""Decoder stacks, losses, prefill and decode steps for every family.

All functions are pure and jit-able; `mesh`/`batch_axes` are static context
used only by the expert-parallel MoE path (None => dense MoE oracle).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import api, layers, mamba as mamba_mod, moe as moe_mod
from repro.models.api import ModelConfig
from repro.sharding import partition


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _norm(sub, prefix, x, cfg: ModelConfig):
    if cfg.norm_kind == "rms":
        return layers.rms_norm(x, sub[f"{prefix}_w"], cfg.norm_eps)
    return layers.layer_norm(x, sub[f"{prefix}_w"], sub[f"{prefix}_b"])


def moe_spec(cfg: ModelConfig) -> moe_mod.MoESpec:
    m = cfg.moe
    return moe_mod.MoESpec(
        n_experts=m.n_experts, top_k=m.top_k, d_ff=m.d_ff,
        capacity_factor=m.capacity_factor, impl=m.impl,
        fsdp_experts=m.fsdp_experts, router=m.router,
        routed_scale=m.routed_scale)


def sinusoidal_positions(seq: int, dim: int) -> jax.Array:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, dim, 2, dtype=jnp.float32)
                  * (-jnp.log(10000.0) / dim))
    pe = jnp.zeros((seq, dim), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    raise ValueError(cfg.remat)


# ---------------------------------------------------------------------------
# one sub-layer (mixer + optional cross-attn + ffn)
# ---------------------------------------------------------------------------


def _sublayer(sub, cfg: ModelConfig, plan_item, h, positions, *,
              cache=None, cache_pos=None, cross_kv=None, enc_out=None,
              mesh=None, batch_axes=("data",), attn_causal=True):
    """Returns (h, new_cache, aux, experts): `experts` the MoE FFN's
    choices [B, S, K] where it reports them (`moe.moe_ffn`), else None."""
    mixer, ffn = plan_item
    aux = jnp.zeros((), jnp.float32)
    experts = None
    new_cache = {}
    if mixer == "attn" and cfg.mla:
        kv = None if cache is None else {"k": cache["k"], "v": cache["v"]}
        y, new_kv = layers.mla_attention(
            sub["attn"], _norm(sub, "ln1", h, cfg), cfg.mla_spec, positions,
            attn_impl=cfg.attn_impl, kv_cache=kv, cache_pos=cache_pos,
            mesh=mesh)
        h = h + y
        if new_kv is not None:
            new_cache.update(new_kv)
    elif mixer == "attn":
        spec = cfg.attn_spec
        if not attn_causal:
            spec = dataclasses.replace(spec, causal=False)
        kv = None if cache is None else {"k": cache["k"], "v": cache["v"]}
        y, new_kv = layers.attention(
            sub["attn"], _norm(sub, "ln1", h, cfg), spec, positions,
            attn_impl=cfg.attn_impl, kv_cache=kv, cache_pos=cache_pos,
            mesh=mesh)
        h = h + y
        if new_kv is not None:
            new_cache.update(new_kv)
    else:
        state = None
        if cache is not None:
            state = (cache["ssm"], cache["conv_x"], cache["conv_bc"])
        y, new_state = mamba_mod.mamba_block(
            sub["mamba"], _norm(sub, "ln1", h, cfg), cfg.mamba_spec,
            state=state)
        h = h + y
        new_cache.update({"ssm": new_state[0], "conv_x": new_state[1],
                          "conv_bc": new_state[2]})
    if "xattn" in sub:
        if cross_kv is None:
            assert enc_out is not None
            ck = layers.cross_kv_from_encoder(sub["xattn"], enc_out,
                                              cfg.attn_spec)
        else:
            ck = (cross_kv["xk"], cross_kv["xv"])
        y, _ = layers.attention(
            sub["xattn"], _norm(sub, "lnx", h, cfg), cfg.attn_spec,
            positions, attn_impl="xla", cross_kv=ck, mesh=mesh)
        h = h + y
        if cache is not None and cross_kv is None:
            new_cache.update({"xk": ck[0], "xv": ck[1]})
        elif cross_kv is not None:
            new_cache.update({"xk": cross_kv["xk"], "xv": cross_kv["xv"]})
    if ffn == "dense":
        h = h + layers.mlp(sub["mlp"], _norm(sub, "ln2", h, cfg),
                           cfg.mlp_kind)
    elif ffn == "moe":
        y, aux, experts = moe_mod.moe_ffn(
            sub["moe"], _norm(sub, "ln2", h, cfg), moe_spec(cfg), mesh,
            batch_axes)
        h = h + y
    return h, new_cache, aux, experts


# ---------------------------------------------------------------------------
# scanned stack
# ---------------------------------------------------------------------------


def run_stack(blocks, cfg: ModelConfig, h, positions, *, plan=None,
              cache=None, cache_pos=None, enc_out=None, decode_cross=False,
              mesh=None, batch_axes=("data",), attn_causal=True):
    """Scan the (stacked) block params over h.

    blocks: pytree whose leaves have a leading `groups` axis.
    cache: matching pytree (leading groups axis) or None.
    Returns (h, new_cache, aux_sum, experts): `experts` [layers, B, S, K],
    the choices of the MoE sub-layers that report them, in layer order,
    else None.
    """
    if plan is None:
        _, plan = cfg.layer_plan()
    moe_subs = [f"sub{i}" for i, (_, ffn) in enumerate(plan) if ffn == "moe"]
    blocks, per_group = moe_mod.hoist_experts(
        blocks, moe_subs, moe_spec(cfg) if cfg.moe is not None else None)

    def body(carry, xs):
        hh, aux_acc = carry
        group, cache_g = xs
        group = per_group(group)
        # sequence-parallel residual stream: the scan carry (the only
        # activation persisted per layer under remat="full") is sharded
        # over the model axis along seq when the rules say so
        hh = partition.constrain(hh, ("batch", "seq", "embed_act"))
        new_cache_g, experts_g = {}, {}
        for i, item in enumerate(plan):
            sub = group[f"sub{i}"]
            sub_cache = None if cache_g is None else cache_g[f"sub{i}"]
            cross_kv = None
            if decode_cross and sub_cache is not None and "xk" in sub_cache:
                cross_kv = {"xk": sub_cache["xk"], "xv": sub_cache["xv"]}
            hh, nc, aux, experts = _sublayer(
                sub, cfg, item, hh, positions, cache=sub_cache,
                cache_pos=cache_pos, cross_kv=cross_kv, enc_out=enc_out,
                mesh=mesh, batch_axes=batch_axes, attn_causal=attn_causal)
            new_cache_g[f"sub{i}"] = nc
            if experts is not None:
                experts_g[f"sub{i}"] = experts
            aux_acc = aux_acc + aux
        return (hh, aux_acc), (new_cache_g, experts_g)

    body = _remat(body, cfg)
    zero = jnp.zeros((), jnp.float32)
    if not cfg.scan_layers:
        # unrolled (used by the dry-run cost-extrapolation compiles; every
        # layer appears in the HLO so cost_analysis counts it exactly)
        n_groups = jax.tree.leaves(blocks)[0].shape[0]
        carry = (h, zero)
        outs = []
        for i in range(n_groups):
            group_i = jax.tree.map(lambda x: x[i], blocks)
            cache_i = (None if cache is None
                       else jax.tree.map(lambda x: x[i], cache))
            carry, out = body(carry, (group_i, cache_i))
            outs.append(out)
        h, aux = carry
        new_cache, experts = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        if cache is None:
            new_cache = None
        return h, new_cache, aux, _layer_order(experts, moe_subs)
    if cache is None:
        # lax.scan requires xs pytrees to agree; use params-only xs
        def body_nocache(carry, group):
            return body(carry, (group, None))
        (h, aux), (_, experts) = jax.lax.scan(body_nocache, (h, zero),
                                              blocks)
        return h, None, aux, _layer_order(experts, moe_subs)
    (h, aux), (new_cache, experts) = jax.lax.scan(body, (h, zero),
                                                  (blocks, cache))
    return h, new_cache, aux, _layer_order(experts, moe_subs)


def _layer_order(experts: dict, moe_subs: list[str]):
    """{sub: [groups, B, S, K]} -> [layers, B, S, K] in layer order (the
    groups' sub-layers in turn), or None where no sub-layer reported."""
    subs = [experts[name] for name in moe_subs if name in experts]
    if not subs:
        return None
    stacked = jnp.stack(subs, axis=1)
    return stacked.reshape(-1, *stacked.shape[2:])


def run_stacks(params, cfg: ModelConfig, h, positions, *, cache=None,
               **kw):
    """The leading dense layers (`cfg.first_dense`, their own scan over
    `params["dense_blocks"]`), then the scanned groups.  With leading
    dense layers the cache is {"dense_blocks": ..., "blocks": ...}, else
    the groups' cache alone.  Returns (h, new_cache, aux_sum, experts), as
    `run_stack` (the leading dense layers choose no experts)."""
    if not cfg.first_dense:
        return run_stack(params["blocks"], cfg, h, positions, cache=cache,
                         **kw)
    part = (lambda k: None) if cache is None else cache.get
    h, dense_cache, aux0, _ = run_stack(
        params["dense_blocks"], cfg, h, positions, plan=api.DENSE_PLAN,
        cache=part("dense_blocks"), **kw)
    h, cache, aux1, experts = run_stack(params["blocks"], cfg, h, positions,
                                        cache=part("blocks"), **kw)
    if cache is not None:
        cache = {"dense_blocks": dense_cache, "blocks": cache}
    return h, cache, aux0 + aux1, experts


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------


def embed_tokens(params, cfg: ModelConfig, tokens):
    emb = params["embed"]["tok"]
    return emb[tokens].astype(cfg.compute_dtype)


def unembed(params, cfg: ModelConfig, h):
    if cfg.tie_embeddings:
        w = params["embed"]["tok"].astype(cfg.compute_dtype)   # [Vp, D]
        logits = jnp.einsum("bsd,vd->bsv", h, w,
                            preferred_element_type=jnp.float32)
    else:
        w = params["lm_head"].astype(cfg.compute_dtype)        # [D, Vp]
        logits = jnp.einsum("bsd,dv->bsv", h, w,
                            preferred_element_type=jnp.float32)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    if cfg.padded_vocab != cfg.vocab:
        # mask Megatron-style vocab padding slots
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab
        logits = jnp.where(pad_mask, logits, -1e30)
    return logits


# ---------------------------------------------------------------------------
# full forwards
# ---------------------------------------------------------------------------


def _encode(params, cfg: ModelConfig, frames, mesh, batch_axes):
    """Whisper encoder over stub frame embeddings [B, Se, D]."""
    se = frames.shape[1]
    h = frames.astype(cfg.compute_dtype)
    h = h + sinusoidal_positions(se, cfg.d_model).astype(cfg.compute_dtype)
    positions = jnp.arange(se)
    h, _, _, _ = run_stack(params["enc_blocks"], cfg, h, positions,
                        plan=[("attn", "dense")], mesh=mesh,
                        batch_axes=batch_axes, attn_causal=False)
    return _norm(params["enc_final"], "lnf", h, cfg)


def forward(params, cfg: ModelConfig, batch, *, mesh=None,
            batch_axes=("data",), with_experts=False):
    """Training/teacher-forcing forward. batch: dict with `tokens` [B,S]
    (+ `frames` for encdec, `patches` for vlm). Returns (h_final, aux),
    and with `with_experts` also the routed experts each token chose at
    each MoE layer, [layers, B, S, K] (None where no layer reports them:
    `run_stack`)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    h = embed_tokens(params, cfg, tokens)
    positions = jnp.arange(s)
    enc_out = None
    if cfg.family == "encdec":
        enc_out = _encode(params, cfg, batch["frames"], mesh, batch_axes)
        h = h + params["dec_pos"][:s].astype(cfg.compute_dtype)
    if cfg.family == "vlm":
        patches = batch["patches"].astype(cfg.compute_dtype)
        h = jnp.concatenate([patches, h[:, patches.shape[1]:]], axis=1)
    h, _, aux, experts = run_stacks(params, cfg, h, positions,
                                    enc_out=enc_out, mesh=mesh,
                                    batch_axes=batch_axes)
    h = _norm(params["final"], "lnf", h, cfg)
    return (h, aux, experts) if with_experts else (h, aux)


def _gold_logit(logits, targets):
    """logits[..., targets] via a masked sum, NOT take_along_axis: a gather
    along the vocab-sharded axis makes GSPMD all-gather the whole logits
    tensor (measured: ~4 GB/step of AG+scatter-AR on yi-9b); the masked sum
    reduces shard-locally and psums a scalar per position."""
    vpos = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                    logits.ndim - 1)
    return jnp.sum(jnp.where(vpos == targets[..., None], logits, 0.0),
                   axis=-1)


def loss_from_hidden(params, cfg: ModelConfig, h, tokens, aux):
    """Next-token CE, optionally chunked over the sequence to avoid
    materialising [B, S, V] logits."""
    b, s = tokens.shape
    targets = tokens[:, 1:]
    hh = h[:, :-1]
    n = b * (s - 1)
    if cfg.loss_chunk and (s - 1) % cfg.loss_chunk == 0:
        nc = (s - 1) // cfg.loss_chunk
        hh = hh.reshape(b, nc, cfg.loss_chunk, cfg.d_model)
        tt = targets.reshape(b, nc, cfg.loss_chunk)

        @jax.checkpoint  # don't keep per-chunk logits as scan residuals
        def chunk_loss(carry, xs):
            hc, tc = xs
            logits = unembed(params, cfg, hc)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = _gold_logit(logits, tc)
            return carry + jnp.sum(lse - gold), None

        total, _ = jax.lax.scan(
            chunk_loss, jnp.zeros((), jnp.float32),
            (jnp.moveaxis(hh, 1, 0), jnp.moveaxis(tt, 1, 0)))
        loss = total / n
    else:
        logits = unembed(params, cfg, hh)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = _gold_logit(logits, targets)
        loss = jnp.sum(lse - gold) / n
    aux_w = cfg.moe.aux_loss_weight if cfg.moe is not None else 0.0
    return loss + aux_w * aux


def build_loss_fn(cfg: ModelConfig, mesh=None, batch_axes=("data",)):
    def loss_fn(params, batch):
        h, aux = forward(params, cfg, batch, mesh=mesh,
                         batch_axes=batch_axes)
        return loss_from_hidden(params, cfg, h, batch["tokens"], aux)
    return loss_fn


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int):
    """ShapeDtypeStructs for the decode cache (matches run_stack layout)."""
    shapes = _cache_shapes(cfg, batch, max_len)
    return jax.tree.map(
        lambda sd: jax.ShapeDtypeStruct(sd[0], sd[1]), shapes,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))


def _with_dense(cfg: ModelConfig, group_tree):
    """`group_tree(n_groups, plan)` for the scanned groups, and beside it
    for the leading dense layers where the model has them (the cache
    layout `run_stacks` takes)."""
    tree = group_tree(*cfg.layer_plan())
    if not cfg.first_dense:
        return tree
    return {"dense_blocks": group_tree(cfg.first_dense, api.DENSE_PLAN),
            "blocks": tree}


def _cache_shapes(cfg: ModelConfig, batch: int, max_len: int):
    return _with_dense(cfg, lambda n_groups, plan: _group_cache_shapes(
        cfg, n_groups, plan, batch, max_len))


def _group_cache_shapes(cfg: ModelConfig, n_groups, plan, batch: int,
                        max_len: int):
    ms = cfg.mamba_spec if cfg.family in ("ssm", "hybrid") else None
    group = {}
    for i, (mixer, ffn) in enumerate(plan):
        sub = {}
        if mixer == "attn" and cfg.mla:
            kv_shape = (n_groups, batch, max_len, cfg.n_heads)
            sub["k"] = (kv_shape + (cfg.head_dim + cfg.qk_rope_dim,),
                        cfg.kv_dtype)
            sub["v"] = (kv_shape + (cfg.v_head_dim,), cfg.kv_dtype)
        elif mixer == "attn":
            kv_shape = (n_groups, batch, max_len, cfg.n_kv_heads,
                        cfg.head_dim)
            sub["k"] = (kv_shape, cfg.kv_dtype)
            sub["v"] = (kv_shape, cfg.kv_dtype)
        else:
            sub["ssm"] = ((n_groups, batch, ms.n_heads, ms.headdim,
                           ms.d_state), jnp.float32)
            sub["conv_x"] = ((n_groups, batch, ms.conv_kernel - 1,
                              ms.d_inner), cfg.kv_dtype)
            sub["conv_bc"] = ((n_groups, batch, ms.conv_kernel - 1,
                               ms.bc_dim), cfg.kv_dtype)
        if cfg.family == "encdec":
            x_shape = (n_groups, batch, cfg.enc_seq, cfg.n_kv_heads,
                       cfg.head_dim)
            sub["xk"] = (x_shape, cfg.kv_dtype)
            sub["xv"] = (x_shape, cfg.kv_dtype)
        group[f"sub{i}"] = sub
    return group


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    shapes = _cache_shapes(cfg, batch, max_len)
    return jax.tree.map(
        lambda sd: jnp.zeros(sd[0], sd[1]), shapes,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))


def cache_axis_specs(cfg: ModelConfig, enc_len: int = 0):
    """Logical axes pytree matching the cache."""
    return _with_dense(cfg, lambda n_groups, plan: _group_cache_axes(
        cfg, plan))


def _group_cache_axes(cfg: ModelConfig, plan):
    group = {}
    for i, (mixer, ffn) in enumerate(plan):
        sub = {}
        if mixer == "attn":
            ax = ("layers", "batch", "seq_kv", "kv_heads_kv", None)
            sub["k"] = ax
            sub["v"] = ax
        else:
            sub["ssm"] = ("layers", "batch", "heads_ssm", None, None)
            sub["conv_x"] = ("layers", "batch", None, "inner")
            sub["conv_bc"] = ("layers", "batch", None, None)
        if cfg.family == "encdec":
            ax = ("layers", "batch", None, "kv_heads_kv", None)
            sub["xk"] = ax
            sub["xv"] = ax
        group[f"sub{i}"] = sub
    return group


def build_prefill_fn(cfg: ModelConfig, max_len: int, mesh=None,
                     batch_axes=("data",)):
    """prefill(params, batch) -> (cache, last_logits [B, V])."""
    def prefill(params, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        cache = init_cache(cfg, b, max_len)
        h = embed_tokens(params, cfg, tokens)
        positions = jnp.arange(s)
        enc_out = None
        if cfg.family == "encdec":
            enc_out = _encode(params, cfg, batch["frames"], mesh, batch_axes)
            h = h + params["dec_pos"][:s].astype(cfg.compute_dtype)
        if cfg.family == "vlm":
            patches = batch["patches"].astype(cfg.compute_dtype)
            h = jnp.concatenate([patches, h[:, patches.shape[1]:]], axis=1)
        h, cache, _, _ = run_stacks(
            params, cfg, h, positions, cache=cache, cache_pos=0,
            enc_out=enc_out, mesh=mesh, batch_axes=batch_axes)
        h = _norm(params["final"], "lnf", h, cfg)
        logits = unembed(params, cfg, h[:, -1:])[:, 0]
        return cache, logits
    return prefill


def build_decode_fn(cfg: ModelConfig, mesh=None, batch_axes=("data",)):
    """decode(params, cache, tokens [B,1], pos) -> (cache, next_tok, logits)."""
    def decode(params, cache, tokens, pos):
        b, s = tokens.shape
        h = embed_tokens(params, cfg, tokens)
        positions = pos + jnp.arange(s)
        if cfg.family == "encdec":
            h = h + jax.lax.dynamic_slice_in_dim(
                params["dec_pos"], pos, s, 0).astype(cfg.compute_dtype)
        h, cache, _, _ = run_stacks(
            params, cfg, h, positions, cache=cache, cache_pos=pos,
            decode_cross=(cfg.family == "encdec"),
            mesh=mesh, batch_axes=batch_axes)
        h = _norm(params["final"], "lnf", h, cfg)
        logits = unembed(params, cfg, h)[:, -1]
        next_tok = jnp.argmax(logits, axis=-1).astype(tokens.dtype)
        return cache, next_tok, logits
    return decode

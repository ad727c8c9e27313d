"""`moe-forward`: a DeepSeek-V3 decoder (latent attention, leading dense
layers, routed experts chosen by a sigmoid router with a score-correction
bias, shared experts), scored teacher-forced over a [batch, seq] chunk of
token ids; the module returns {"logits": the last position's logits over
the padded vocabulary, "experts": the routed experts each token chose at
each MoE layer, [layers, batch, seq, top_k]}.

Inputs, the plain reference, the comparison and the work per chunk, with
`lm-forward.py`'s interface; its chunks (`make_pool`, `tokens_per_chunk`)
serve here as they are.  The reference imports nothing of the program: it
makes its own copy of the weights from the seed (the program's on-slot
init draws: one truncated normal per tensor, keyed by the tensor's name;
the router's score-correction bias is drawn like the rest) and runs the
forward pass in float32 at `highest` matmul precision, one layer at a
time, with the equations transformers' `DeepseekV3ForCausalLM` states.
Routing loops over every expert, with no capacity and nothing dropped.

The comparison is routing-aware.  Random routers choose by margins near
bfloat16's effect on a score, so a bfloat16 program and a float32
reference that each route for themselves part ways at a fifth of the
token-layer calls, and their logits then differ as much as a float8
one's.  So the reference takes each output's expert choices: it weights
and runs the experts the output chose, computing their scores itself,
and holds the logits to a bfloat16-sized limit; and it holds each choice
to its own router, by how far the weakest chosen expert's biased score
lies below its own k-th highest (`moe_route_gap`).
"""
from __future__ import annotations

import importlib.util
import zlib
from pathlib import Path

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_modules_lm_forward_for_moe", Path(__file__).with_name(
        "lm-forward.py"))
_LM = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_LM)

CONTROL = _LM.CONTROL
make_pool = _LM.make_pool
tokens_per_chunk = _LM.tokens_per_chunk
# transformers' DeepseekV3Attention builds kv_a_layernorm with RMSNorm's
# default epsilon, not the configuration's rms_norm_eps
KV_NORM_EPS = 1e-6


def _sizes(cfg: dict) -> dict:
    f = cfg["moe_intermediate_size"]
    return {"L": cfg["num_hidden_layers"], "D": cfg["hidden_size"],
            "H": cfg["num_attention_heads"], "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
            "r": cfg["kv_lora_rank"], "F": cfg["intermediate_size"],
            "Fe": f, "Fs": f * cfg["n_shared_experts"],
            "E": cfg["n_routed_experts"], "K": cfg["num_experts_per_tok"],
            "dense": cfg["first_k_dense_replace"],
            "V": cfg["vocab_size"], "Vp": (cfg["vocab_size"] + 255) // 256
            * 256, "tied": bool(cfg["tie_word_embeddings"])}


def flops_per_chunk(cfg: dict, mod: dict) -> float:
    """Operations of one chunk as the module computes it: 2 per weight
    each token meets (latent attention's projections; the dense layers'
    MLP; in each MoE layer the router, the top-k routed experts and the
    shared expert); attention's two S x S products over every head (the
    whole square: the causal mask is applied to a full score matrix); and
    the output projection at the last position only."""
    z = _sizes(cfg)
    b, s = _LM._chunk(mod)
    D, H = z["D"], z["H"]
    qk = z["nope"] + z["rope"]
    attn = D * H * qk + D * (z["r"] + z["rope"]) \
        + z["r"] * H * (z["nope"] + z["v"]) + H * z["v"] * D
    dense = 3 * D * z["F"]
    moe = D * z["E"] + z["K"] * 3 * D * z["Fe"] + 3 * D * z["Fs"]
    per_token = z["L"] * attn + z["dense"] * dense \
        + (z["L"] - z["dense"]) * moe
    scores = z["L"] * 2 * b * s * s * H * (qk + z["v"])
    logits = 2 * b * D * z["Vp"]
    return float(2 * per_token * b * s + scores + logits)


def gmm_work(cfg: dict, mod: dict) -> dict:
    """The routed experts' grouped matmul per chunk, from the
    configuration and the chunk: `flops`, 2 x T x K x 3 x D x F per MoE
    layer; `bytes` of HBM, each MoE layer's E x 3 x D x F expert weights
    read once, and its T x K routed rows of D read and written."""
    z = _sizes(cfg)
    b, s = _LM._chunk(mod)
    item = _itemsize(cfg)
    pairs = b * s * z["K"]
    n = z["L"] - z["dense"]
    return {"flops": float(n * 2 * pairs * 3 * z["D"] * z["Fe"]),
            "bytes": float(n * item * (z["E"] * 3 * z["D"] * z["Fe"]
                                       + 2 * pairs * z["D"]))}


def _itemsize(cfg: dict) -> int:
    import jax.numpy as jnp
    return jnp.dtype(cfg["torch_dtype"]).itemsize


# -- the plain reference -----------------------------------------------------


# a layer's tensors in the order the reference's layers take them; the
# program stacks the leading dense layers and the MoE layers apart, under
# these names
_DENSE, _MOE = "['dense_blocks']/['sub0']/", "['blocks']/['sub0']/"
_ATTN = ["['ln1_w']", "['attn']/['wq']", "['attn']/['wkv_a']",
         "['attn']/['kv_norm']", "['attn']/['wkv_b']", "['attn']/['wo']",
         "['ln2_w']"]
_MLP = ["['mlp']/['w_gate']", "['mlp']/['w_up']", "['mlp']/['w_down']"]
_EXPERTS = ["['moe']/['w_router']", "['moe']/['router_bias']",
            "['moe']/['w1']", "['moe']/['w3']", "['moe']/['w2']",
            "['moe']/['shared']/['w_gate']", "['moe']/['shared']/['w_up']",
            "['moe']/['shared']/['w_down']"]
_EMBED, _FINAL, _HEAD = "['embed']/['tok']", "['final']/['lnf_w']", \
    "['lm_head']"


def param_table(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """Tensor name -> (shape, init).  Layer tensors are stacked over the
    dense layers and over the MoE layers; norm weights start at one."""
    z = _sizes(cfg)
    D, H, r, qk = z["D"], z["H"], z["r"], z["nope"] + z["rope"]
    attn = [(D,), (D, H * qk), (D, r + z["rope"]), (r,),
            (r, H * (z["nope"] + z["v"])), (H * z["v"], D), (D,)]
    mlp = [(D, z["F"]), (D, z["F"]), (z["F"], D)]
    experts = [(D, z["E"]), (z["E"],), (z["E"], D, z["Fe"]),
               (z["E"], D, z["Fe"]), (z["E"], z["Fe"], D), (D, z["Fs"]),
               (D, z["Fs"]), (z["Fs"], D)]
    ones = {"['ln1_w']", "['ln2_w']", "['attn']/['kv_norm']"}
    table = {_EMBED: ((z["Vp"], D), "normal")}
    for prefix, n, names, shapes in (
            (_DENSE, z["dense"], _ATTN + _MLP, attn + mlp),
            (_MOE, z["L"] - z["dense"], _ATTN + _EXPERTS, attn + experts)):
        for name, shape in zip(names, shapes):
            table[prefix + name] = ((n, *shape),
                                    "ones" if name in ones else "normal")
    table[_FINAL] = ((D,), "ones")
    if not z["tied"]:
        table[_HEAD] = ((D, z["Vp"]), "normal")
    return table


def make_weights(cfg: dict, key: int, device=None):
    """The weights, in the configuration's dtype, made on `device` in one
    jitted call: a truncated normal on [-2, 2] scaled by min(0.02,
    fan_in ** -0.5), keyed by `key` folded with the CRC-32 of the name."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg["torch_dtype"])
    table = param_table(cfg)

    def gen(k):
        out = {}
        for name, (shape, init) in table.items():
            if init == "ones":
                out[name] = jnp.ones(shape, dtype)
                continue
            kk = jax.random.fold_in(k, zlib.crc32(name.encode()) % 2 ** 31)
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            scale = min(0.02, (1.0 / max(fan_in, 1)) ** 0.5)
            out[name] = (jax.random.truncated_normal(
                kk, -2.0, 2.0, shape, jnp.float32) * scale).astype(dtype)
        return out

    kdev = jax.device_put(jax.random.PRNGKey(key), device)
    return jax.jit(gen)(kdev)


def _forward(cfg: dict, lowp=None, forced=False):
    """forward(weights, tokens [B, S]) -> (last-position logits [B, V]
    float32, experts [MoE layers, B, S, K], gap).  `lowp`: round every
    matmul operand to this dtype first (the control).  With `forced`,
    forward(weights, tokens, experts) routes each token to the `experts`
    given, weighted by its own scores, and `gap` is the largest amount by
    which a given choice's biased score lies below the k-th highest
    (infinity where a token's choices are not K distinct experts);
    otherwise the reference chooses, and `gap` is 0."""
    import jax
    import jax.numpy as jnp
    z = _sizes(cfg)
    f32 = jnp.float32
    eps = cfg["rms_norm_eps"]
    H, nope, rope, r = z["H"], z["nope"], z["rope"], z["r"]
    scale = (nope + rope) ** -0.5
    inv_freq = 1.0 / (cfg["rope_theta"] ** (
        np.arange(0, rope, 2, dtype=np.float32) / rope))
    lp = None if lowp is None else jnp.dtype(lowp)

    def rnd(x):
        return x if lp is None else x.astype(lp).astype(f32)

    def mm(spec, a, b):
        return jnp.einsum(spec, rnd(a), rnd(b),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=f32)

    def rms(x, w, eps=eps):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * w.astype(f32)

    def rope_interleaved(x, pos):       # x [B, S, h, rope]
        # transformers' apply_rotary_pos_emb_interleave: even dims, then
        # odd, then the half rotation
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
        ang = pos[:, None].astype(f32) * inv_freq
        sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
        x1, x2 = x[..., :rope // 2], x[..., rope // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    def swiglu(x, wg, wu, wd):
        return mm("...f,fd->...d", jax.nn.silu(mm("...d,df->...f", x, wg))
                  * mm("...d,df->...f", x, wu), wd)

    def attention(h, lw):
        ln1, wq, wkv_a, kv_norm, wkv_b, wo = lw
        b, s, _ = h.shape
        pos = jnp.arange(s)
        x = rms(h, ln1)
        q = mm("bsd,dh->bsh", x, wq).reshape(b, s, H, nope + rope)
        kva = mm("bsd,dr->bsr", x, wkv_a)
        kv = mm("bsr,rh->bsh", rms(kva[..., :r], kv_norm, KV_NORM_EPS),
                wkv_b).reshape(b, s, H, nope + z["v"])
        k_pe = rope_interleaved(kva[:, :, None, r:], pos)
        q = jnp.concatenate([q[..., :nope], rope_interleaved(
            q[..., nope:], pos)], -1)
        k = jnp.concatenate([kv[..., :nope], jnp.repeat(k_pe, H, 2)], -1)
        sc = mm("bqhd,bkhd->bhqk", q, k) * scale
        mask = pos[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), -1)
        o = mm("bhqk,bkhd->bqhd", p, kv[..., nope:])
        return h + mm("bsh,hd->bsd", o.reshape(b, s, H * z["v"]), wo)

    def dense_layer(h, lw):
        h = attention(h, lw[:6])
        return h + swiglu(rms(h, lw[6]), *lw[7:]), None

    def moe_layer(h, xs):
        lw, given = xs
        h = attention(h, lw[:6])
        ln2, wr, bias, w1, w3, w2, *shared = lw[6:]
        b, s, d = h.shape
        K, E = z["K"], z["E"]
        x = rms(h, ln2).reshape(b * s, d)
        scores = jax.nn.sigmoid(mm("td,de->te", x, wr))
        biased = scores + bias.astype(f32)
        kth, top = jax.lax.top_k(biased, K)
        gap = jnp.zeros((), f32)
        if given is not None:
            top = given.reshape(b * s, K)
        # an index out of range is no expert: its one-hot row is zero
        chosen = jnp.sum(jax.nn.one_hot(top, E, dtype=f32), 1)
        if given is not None:
            weakest = jnp.min(jnp.where(chosen > 0, biased, jnp.inf), -1)
            distinct = jnp.all(jnp.sum(chosen, -1) == K) & jnp.all(
                chosen <= 1)
            gap = jnp.where(distinct, jnp.maximum(
                jnp.max(kth[:, -1] - weakest), 0.0), jnp.inf)
        weight = scores * chosen
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20) \
            * cfg["routed_scaling_factor"]

        def expert(y, ew):              # every token, weight 0 if unrouted
            e1, e3, e2, we = ew
            return y + we[:, None] * swiglu(x, e1, e3, e2), None

        y, _ = jax.lax.scan(expert, swiglu(x, *shared), (w1, w3, w2,
                                                        weight.T))
        return h + y.reshape(b, s, d), (top.reshape(b, s, K), gap)

    def forward(w, tokens, experts=None):
        h = w[_EMBED][tokens].astype(f32)
        h, _ = jax.lax.scan(dense_layer, h,
                            [w[_DENSE + k] for k in _ATTN + _MLP])
        h, (top, gap) = jax.lax.scan(
            moe_layer, h, ([w[_MOE + k] for k in _ATTN + _EXPERTS],
                           experts))
        h = rms(h[:, -1], w[_FINAL])
        logits = (mm("bd,vd->bv", h, w[_EMBED]) if z["tied"]
                  else mm("bd,dv->bv", h, w[_HEAD]))
        return logits[:, :z["V"]], top, jnp.max(gap)

    if forced:
        return jax.jit(forward)
    return jax.jit(lambda w, tokens: forward(w, tokens))


def reference(cfg: dict, mod: dict, items: list[tuple], key: int,
              device=None, lowp=None) -> list[dict]:
    """For each chunk in `items`, what `compare` holds an output to: the
    chunk's tokens and the weights' key, from which `compare` runs the
    float32 reference along the output's own expert choices.  With `lowp`
    (the control) also the reference's own forward at that precision,
    routing for itself, in the module's output form ("logits" float32
    [B, vocab], "experts")."""
    import jax
    out = [{"tokens": t, "key": key, "device": device} for (t,) in items]
    if lowp is None:
        return out
    w = make_weights(cfg, key, device)
    fwd = _forward(cfg, lowp)
    for o in out:
        logits, top, _ = fwd(w, jax.device_put(o["tokens"], device))
        o.update(logits=np.asarray(logits), experts=np.asarray(top))
    del w
    return out


def _output(o):
    """An output as the module returned it: the harness passes each
    through `np.asarray`, which wraps a dict in a 0-d object array."""
    if isinstance(o, np.ndarray) and o.shape == () and o.dtype == object:
        return o.item()
    return o


def compare(cfg: dict, mod: dict, got: list, want: list) -> dict:
    """The float32 reference, run along each output's expert choices,
    against the output: `lm_logit_err` and `lm_logit_rms` as
    `lm-forward.py` computes them, and `moe_route_gap`, over every token
    and MoE layer of every chunk the largest amount by which a chosen
    expert's biased score (the reference's own) lies below the k-th
    highest.  A wrong shape, or a token whose choices are not K distinct
    experts, reads infinity."""
    import jax
    z = _sizes(cfg)
    worst = {"lm_logit_err": np.inf, "lm_logit_rms": np.inf,
             "moe_route_gap": np.inf}
    keys = {(w["key"], w["device"]) for w in want}
    assert len(keys) == 1, keys            # one seed's weights
    (key, device), = keys
    w = make_weights(cfg, key, device)
    fwd = _forward(cfg, forced=True)
    logits, ref_logits, gap = [], [], 0.0
    for g, wt in zip(got, want):
        g = _output(g)
        b, s = wt["tokens"].shape
        if not isinstance(g, dict) or np.shape(g.get("experts")) != (
                z["L"] - z["dense"], b, s, z["K"]):
            return worst
        experts = np.asarray(g["experts"])
        ref, _, chunk_gap = fwd(w, jax.device_put(wt["tokens"], device),
                                jax.device_put(experts.astype(np.int32),
                                               device))
        logits.append(g["logits"])
        ref_logits.append(np.asarray(ref))
        gap = max(gap, float(chunk_gap))
    del w
    return _LM.compare(cfg, mod, logits, ref_logits) | {"moe_route_gap": gap}

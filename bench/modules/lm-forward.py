"""`lm-forward`: a dense decoder with grouped-query attention, scored
teacher-forced over a [batch, seq] chunk of token ids; the module returns
the last position's logits over the padded vocabulary.

Inputs, the plain reference, the comparison and the work per chunk.  The
reference imports nothing of the program: it makes its own copy of the
weights from the seed (the same random draws as the program's on-slot
init: one truncated normal per tensor, keyed by the tensor's name) and
runs the forward pass in float32 at `highest` matmul precision, one layer
at a time.  Sizes and equations come from the configuration's keys
(Hugging Face names), as the published model states them: Qwen3's
RMS-normed queries and keys (`model_type` "qwen3"), granite's scalar
multipliers, tied or untied output projection.
"""
from __future__ import annotations

import zlib

import numpy as np

# the control computes one precision below the bfloat16 the module serves
CONTROL = "float8_e4m3fn"
# model types whose attention RMS-normalises each query and key head
# before the rotary embedding (Qwen3)
QK_NORM = {"qwen3"}


def _sizes(cfg: dict) -> dict:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return {"L": cfg["num_hidden_layers"], "D": d, "Hq": cfg[
        "num_attention_heads"], "Hkv": cfg["num_key_value_heads"], "hd": hd,
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            # the embedding is stored padded to a multiple of 256 rows
            "Vp": (cfg["vocab_size"] + 255) // 256 * 256,
            "qk_norm": cfg["model_type"] in QK_NORM,
            "tied": bool(cfg["tie_word_embeddings"])}


def _chunk(mod: dict) -> tuple[int, int]:
    a = mod["builder_args"]
    return a["batch"], a["seq"]


def make_pool(cfg: dict, mod: dict, rng: np.random.Generator,
              n: int = 32) -> list[tuple]:
    """`n` chunks of token ids drawn uniformly from the whole vocabulary."""
    b, s = _chunk(mod)
    return [(rng.integers(0, cfg["vocab_size"], (b, s)).astype(np.int32),)
            for _ in range(n)]


def tokens_per_chunk(cfg: dict, mod: dict) -> int:
    b, s = _chunk(mod)
    return b * s


def flops_per_chunk(cfg: dict, mod: dict) -> float:
    """Operations of one chunk as the module computes it: 2 per parameter
    of the layers per token; the attention's two S x S products over every
    head (the whole square: the causal mask is applied to a full score
    matrix); and the output projection at the last position only."""
    z = _sizes(cfg)
    b, s = _chunk(mod)
    D, hd = z["D"], z["hd"]
    attn = D * z["Hq"] * hd * 2 + D * z["Hkv"] * hd * 2
    mlp = 3 * D * z["F"]
    layers = 2 * z["L"] * (attn + mlp) * b * s
    scores = z["L"] * 2 * 2 * b * s * s * z["Hq"] * hd
    logits = 2 * b * D * z["Vp"]
    return float(layers + scores + logits)


# -- the plain reference -----------------------------------------------------


# a layer's tensors in the order the reference's layer takes them; the
# program stacks each over the layers under these names
_BLOCK = "['blocks']/['sub0']/"
_LAYER = ["['ln1_w']", "['attn']/['wq']", "['attn']/['wk']",
          "['attn']/['wv']", "['attn']/['wo']", "['ln2_w']",
          "['mlp']/['w_gate']", "['mlp']/['w_up']", "['mlp']/['w_down']"]
_QK_NORM = ["['attn']/['q_norm']", "['attn']/['k_norm']"]
_EMBED, _FINAL = "['embed']/['tok']", "['final']/['lnf_w']"
_HEAD = "['lm_head']"


def _layer_names(z: dict) -> list[str]:
    return _LAYER + (_QK_NORM if z["qk_norm"] else [])


def param_table(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """Tensor name -> (shape, init).  Layer tensors are stacked over the
    layers; norm weights start at one."""
    z = _sizes(cfg)
    L, D, F, hd = z["L"], z["D"], z["F"], z["hd"]
    q, kv = z["Hq"] * hd, z["Hkv"] * hd
    shapes = [(D,), (D, q), (D, kv), (D, kv), (q, D), (D,), (D, F), (D, F),
              (F, D), (hd,), (hd,)]
    table = {_EMBED: ((z["Vp"], D), "normal")}
    for name, shape in zip(_layer_names(z), shapes):
        table[_BLOCK + name] = ((L, *shape),
                                "ones" if len(shape) == 1 else "normal")
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


def _forward(cfg: dict, lowp=None):
    """forward(weights, tokens [B, S]) -> last-position logits [B, V],
    float32.  `lowp`: round every matmul operand to this dtype first (the
    control)."""
    import jax
    import jax.numpy as jnp
    z = _sizes(cfg)
    f32 = jnp.float32
    eps = cfg["rms_norm_eps"]
    # granite's scalars; a model that states none has the usual ones
    attn_mult = cfg.get("attention_multiplier", z["hd"] ** -0.5)
    emb_mult = cfg.get("embedding_multiplier", 1.0)
    res_mult = cfg.get("residual_multiplier", 1.0)
    logit_div = cfg.get("logits_scaling", 1.0)
    g = z["Hq"] // z["Hkv"]
    hd = z["hd"]
    inv_freq = 1.0 / (cfg["rope_theta"] ** (
        np.arange(0, hd, 2, dtype=np.float32) / hd))

    lp = None if lowp is None else jnp.dtype(lowp)

    def rnd(x):
        return x if lp is None else x.astype(lp).astype(f32)

    def mm(spec, a, b):
        return jnp.einsum(spec, rnd(a), rnd(b),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=f32)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * w.astype(f32)

    def rope(x, pos):                   # x [B, S, H, hd], half rotation
        ang = pos[:, None].astype(f32) * inv_freq          # [S, hd/2]
        sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    def forward(w, tokens):
        b, s = tokens.shape
        pos = jnp.arange(s)
        mask = pos[None, :] <= pos[:, None]                 # [q, k]
        h = w[_EMBED][tokens].astype(f32) * emb_mult

        def layer(h, lw):
            ln1, wq, wk, wv, wo, ln2, wg, wu, wd, *qkn = lw
            x = rms(h, ln1)
            q = mm("bsd,dh->bsh", x, wq).reshape(b, s, z["Hq"], hd)
            k = mm("bsd,dh->bsh", x, wk).reshape(b, s, z["Hkv"], hd)
            if qkn:
                q, k = rms(q, qkn[0]), rms(k, qkn[1])
            q, k = rope(q, pos), rope(k, pos)
            v = mm("bsd,dh->bsh", x, wv).reshape(b, s, z["Hkv"], hd)
            k, v = jnp.repeat(k, g, 2), jnp.repeat(v, g, 2)
            sc = mm("bqhd,bkhd->bhqk", q, k) * attn_mult
            p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), -1)
            o = mm("bhqk,bkhd->bqhd", p, v).reshape(b, s, z["Hq"] * hd)
            h = h + res_mult * mm("bsh,hd->bsd", o, wo)
            x = rms(h, ln2)
            a = jax.nn.silu(mm("bsd,df->bsf", x, wg)) \
                * mm("bsd,df->bsf", x, wu)
            h = h + res_mult * mm("bsf,fd->bsd", a, wd)
            return h, None

        h, _ = jax.lax.scan(layer, h,
                            [w[_BLOCK + k] for k in _layer_names(z)])
        h = rms(h[:, -1], w[_FINAL])
        logits = (mm("bd,vd->bv", h, w[_EMBED]) if z["tied"]
                  else mm("bd,dv->bv", h, w[_HEAD])) / logit_div
        return logits[:, :z["V"]]

    return jax.jit(forward)


def reference(cfg: dict, mod: dict, items: list[tuple], key: int,
              device=None, lowp=None) -> list[np.ndarray]:
    """Logits of each chunk in `items`, float32 [B, vocab]."""
    import jax
    w = make_weights(cfg, key, device)
    fwd = _forward(cfg, lowp)
    out = [np.asarray(fwd(w, jax.device_put(t, device))) for (t,) in items]
    del w
    return out


def compare(cfg: dict, mod: dict, got: list, want: list) -> dict:
    """`lm_logit_err`: over every row of every chunk, the largest
    |program - reference| logit, relative to the row's largest |reference|
    logit; `lm_logit_rms`: the same with the root mean square of both.
    A wrong shape or a non-finite logit reads infinity."""
    worst, worst_rms = 0.0, 0.0
    V = cfg["vocab_size"]
    for g, w in zip(got, want):
        g = np.asarray(g, np.float64)
        w = np.asarray(w, np.float64)
        if g.ndim != 2 or g.shape[0] != w.shape[0] or g.shape[1] < V:
            return {"lm_logit_err": np.inf, "lm_logit_rms": np.inf}
        g = g[:, :V]
        if not np.all(np.isfinite(g)):
            return {"lm_logit_err": np.inf, "lm_logit_rms": np.inf}
        d = g - w
        worst = max(worst, float(np.max(np.max(np.abs(d), 1)
                                        / np.max(np.abs(w), 1))))
        worst_rms = max(worst_rms, float(np.max(
            np.sqrt(np.mean(d * d, 1) / np.mean(w * w, 1)))))
    return {"lm_logit_err": worst, "lm_logit_rms": worst_rms}

"""The dropless routed experts, latent attention, the configuration
builder from Hugging Face keys, and the unchanged program of the dense
decoder the benchmark already serves (CPU)."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import api, hf, layers, moe

ROOT = Path(__file__).resolve().parents[1]
MOONLIGHT = json.loads((ROOT / "bench" / "configs" /
                        "moonlight-16b-a3b-9l.1chip.json").read_text())


def _moe_params(key, d, e, f, shared=0):
    ks = jax.random.split(key, 8)
    p = {"w_router": jax.random.normal(ks[0], (d, e)) * 0.3,
         "router_bias": jax.random.normal(ks[1], (e,)) * 0.1,
         "w1": jax.random.normal(ks[2], (e, d, f)) * d ** -0.5,
         "w3": jax.random.normal(ks[3], (e, d, f)) * d ** -0.5,
         "w2": jax.random.normal(ks[4], (e, f, d)) * f ** -0.5}
    if shared:
        p["shared"] = {
            "w_gate": jax.random.normal(ks[5], (d, shared)) * d ** -0.5,
            "w_up": jax.random.normal(ks[6], (d, shared)) * d ** -0.5,
            "w_down": jax.random.normal(ks[7], (shared, d)) * shared ** -0.5}
    return p


def _expert_loop(p, x, weight):
    """Every token through every expert, scaled by its combine weight
    ([T, E], zero where unrouted): no capacity, nothing dropped."""
    def ffn(x, w1, w3, w2):
        return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2
    y = sum(weight[:, e:e + 1] * ffn(x, p["w1"][e], p["w3"][e], p["w2"][e])
            for e in range(p["w1"].shape[0]))
    if "shared" in p:
        s = p["shared"]
        y = y + ffn(x, s["w_gate"], s["w_up"], s["w_down"])
    return y


def _plain_weights(p, x, spec):
    """The combine weights [T, E] by each router's published equations."""
    logits = x @ p["w_router"]
    if spec.router == "softmax":
        probs = jax.nn.softmax(logits, -1)
        _, top = jax.lax.top_k(probs, spec.top_k)
        w = probs * jax.nn.one_hot(top, probs.shape[-1]).sum(1)
        return w / w.sum(-1, keepdims=True), top
    scores = jax.nn.sigmoid(logits)
    _, top = jax.lax.top_k(scores + p["router_bias"], spec.top_k)
    w = scores * jax.nn.one_hot(top, scores.shape[-1]).sum(1)
    return w / w.sum(-1, keepdims=True) * spec.routed_scale, top


@pytest.mark.parametrize("router,one_expert", [
    ("softmax", False), ("sigmoid_bias", False), ("sigmoid_bias", True)])
def test_dropless_is_the_expert_loop(router, one_expert):
    """Dropless routed experts (and a shared one) equal every token run
    through each of its experts, and report the experts each token chose;
    with every token sent to one expert, nothing is dropped."""
    b, s, d, e, f, k = 2, 48, 32, 8, 16, 3
    p = _moe_params(jax.random.PRNGKey(3), d, e, f, shared=24)
    if one_expert:
        p["router_bias"] = p["router_bias"].at[5].set(100.0)
    spec = moe.MoESpec(n_experts=e, top_k=k, d_ff=f, impl="dropless",
                       router=router,
                       routed_scale=2.446 if router == "sigmoid_bias" else 1)
    x = jax.random.normal(jax.random.PRNGKey(4), (b, s, d))
    y, _, experts = jax.jit(lambda p, x: moe.moe_ffn(p, x, spec))(p, x)
    xt = x.reshape(-1, d)
    weight, top = _plain_weights(p, xt, spec)
    np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                  np.sort(np.asarray(top), -1).reshape(
                                      b, s, k))
    if one_expert:
        assert np.all(np.asarray(weight[:, 5]) > 0)
    want = _expert_loop(p, xt, weight).reshape(b, s, d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    if one_expert:
        # the capacity path drops what exceeds its share: here, most
        # of expert 5's tokens
        dense = dataclasses.replace(spec, impl="dense")
        y_dense, _, none = moe.moe_ffn(p, x, dense)
        assert none is None
        assert not np.allclose(np.asarray(y_dense), np.asarray(want),
                               atol=1e-3)


@pytest.mark.parametrize("kw,what", [
    ({"router": "sigmoid_bias", "impl": "ep"}, "softmax only"),
    ({"routed_scale": 2.0}, "sigmoid_bias router's"),
    ({"router": "sigmoid"}, "router"),
])
def test_moe_spec_refuses_what_no_path_computes(kw, what):
    with pytest.raises(ValueError, match=what):
        moe.MoESpec(n_experts=8, top_k=2, d_ff=16, **kw)


def test_dropless_layout_places_every_pair_once():
    top_i = jax.random.randint(jax.random.PRNGKey(0), (100, 3), 0, 8)
    top_i = top_i.at[:60, 0].set(2)                 # one crowded expert
    top_w = jax.random.uniform(jax.random.PRNGKey(1), (100, 3))
    row, pair, scale, tile_expert, n_tiles = map(
        np.asarray, moe.dropless_layout(top_i, top_w, 8, tile=16))
    pairs = np.asarray(top_i).reshape(-1)
    assert pair.shape == (moe.dropless_rows(300, 8, 16),)
    np.testing.assert_array_equal(pair[row], np.arange(300))
    assert np.sum(pair < 300) == 300                # the rest is padding
    np.testing.assert_array_equal(scale[row], np.asarray(top_w).reshape(-1))
    assert np.all(scale[pair == 300] == 0)
    # each expert's pairs, in order, fill whole tiles of their own
    tiles_of = tile_expert[row // 16]
    np.testing.assert_array_equal(tiles_of, pairs)
    assert np.all(np.diff(tile_expert) >= 0)
    sizes = np.bincount(pairs, minlength=8)
    assert n_tiles[0] == np.sum(-(-sizes // 16))
    for e in range(8):
        rows = np.sort(row[pairs == e])
        assert np.all(np.diff(rows) == 1) and rows[0] % 16 == 0


def test_latent_attention_is_plain_jnp():
    """`layers.mla_attention` against the equations written out: the
    latent normed at 1e-6, keys' rotary part shared by every head, rope
    over interleaved pairs, scale (nope + rope)^-1/2, causal."""
    b, s, d, h, nope, rope, v, r = 2, 16, 32, 4, 8, 4, 6, 12
    spec = layers.MLASpec(n_heads=h, nope_dim=nope, rope_dim=rope, v_dim=v,
                          kv_lora_rank=r, rope_theta=50000.0)
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    p = {"wq": jax.random.normal(ks[0], (d, h * (nope + rope))) * 0.2,
         "wkv_a": jax.random.normal(ks[1], (d, r + rope)) * 0.2,
         "kv_norm": 1.0 + 0.1 * jax.random.normal(ks[2], (r,)),
         "wkv_b": jax.random.normal(ks[3], (r, h * (nope + v))) * 0.2,
         "wo": jax.random.normal(ks[4], (h * v, d)) * 0.2}
    x = jax.random.normal(ks[5], (b, s, d))
    got, _ = layers.mla_attention(p, x, spec, jnp.arange(s))

    def rot(t):                                     # [.., s, heads, rope]
        pairs = t.reshape(*t.shape[:-1], rope // 2, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        freq = 50000.0 ** (-np.arange(0, rope, 2) / rope)
        ang = np.arange(s)[:, None, None] * freq
        # the rotated pair (x1, x2) at angle a, laid out as halves
        return jnp.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                                x2 * np.cos(ang) + x1 * np.sin(ang)], -1)

    q = (x @ p["wq"]).reshape(b, s, h, nope + rope)
    kva = x @ p["wkv_a"]
    lat = kva[..., :r]
    lat = lat / jnp.sqrt(jnp.mean(lat ** 2, -1, keepdims=True) + 1e-6) \
        * p["kv_norm"]
    kv = (lat @ p["wkv_b"]).reshape(b, s, h, nope + v)
    qf = jnp.concatenate([q[..., :nope], rot(q[..., nope:])], -1)
    kf = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        rot(kva[:, :, None, r:]), (b, s, h, rope))], -1)
    sc = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * (nope + rope) ** -0.5
    sc = jnp.where(np.tril(np.ones((s, s), bool)), sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), kv[..., nope:])
    want = o.reshape(b, s, h * v) @ p["wo"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_builder_gives_the_registered_moonlight():
    cfg = hf.model_config(dict(MOONLIGHT, num_hidden_layers=27))
    reg = configs.get("moonlight-16b-a3b")
    assert dataclasses.replace(cfg, name=reg.name,
                               param_dtype=reg.param_dtype) == reg
    assert cfg.param_dtype == jnp.bfloat16
    assert api.param_count(hf.model_config(MOONLIGHT)) == 5_432_847_360


@pytest.mark.parametrize("change,key", [
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"n_group": 8, "topk_group": 4}, "topk_group"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"rope_scaling": {"type": "yarn", "factor": 40}}, "rope_scaling"),
    ({"rope_interleave": False}, "rope_interleave"),
    ({"num_key_value_heads": 8}, "num_key_value_heads"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"model_type": "deepseek_v2"}, "model_type"),
])
def test_builder_refuses_what_the_stack_does_not_compute(change, key):
    with pytest.raises(ValueError, match=key):
        hf.model_config(dict(MOONLIGHT, **change))


# Qwen3's equations at a size whose attention the flash kernel serves
TINY_QWEN3 = {
    "name": "tiny", "model_type": "qwen3", "hidden_size": 256,
    "intermediate_size": 512, "num_attention_heads": 2,
    "num_key_value_heads": 1, "head_dim": 128, "num_hidden_layers": 2,
    "vocab_size": 1000, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "torch_dtype": "bfloat16"}


def _lowered_text(build, platform):
    from jax.sharding import NamedSharding
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    prog = build(mesh, 1, model=TINY_QWEN3, batch=2, seq=128)
    ws = jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                          sharding=NamedSharding(mesh, p)),
        prog.abstract_weights, prog.weight_pspecs)
    tok = jax.ShapeDtypeStruct((2, 128), jnp.int32, sharding=NamedSharding(
        mesh, prog.input_pspecs[0]))
    # no source locations in the kernel's serialized body: they are
    # metadata, and they name the checkout's paths
    old = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        return jax.jit(prog.fn).trace(ws, tok).lower(
            lowering_platforms=(platform,)).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", old)


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("builder", ["zoo", "bench"])
def test_dense_decoder_program_is_unchanged(platform, builder):
    """The Qwen3 `lm-forward` program, lowered for each platform, is the
    text the program gave before latent attention, leading dense layers,
    the dropless experts and `norm_eps` came in (tests/fixtures, written
    from that program by `bench.builders` with this function)."""
    if builder == "zoo":
        from repro.core.zoo import build_lm_forward
    else:
        import sys
        sys.path.insert(0, str(ROOT))
        from bench.builders import build_lm_forward
    want = (ROOT / "tests" / "fixtures" /
            f"qwen3_lm_forward.{platform}.mlir").read_text()
    assert _lowered_text(build_lm_forward, platform) == want

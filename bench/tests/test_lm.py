"""`lm-forward`'s work per chunk, its reference's weights against the
program's tensors and init, and its reference against Hugging Face
transformers' own implementation of each published model."""
import json

import jax
import numpy as np
import pytest

import conftest
from bench import builders, harness

LM = harness.load_file_module(conftest.ROOT / "bench" / "modules" /
                              "lm-forward.py")
CONFIGS = conftest.ROOT / "bench" / "configs"
QWEN = json.loads((CONFIGS / "qwen3-14b-10l.1chip.json").read_text())
GRANITE = json.loads((CONFIGS / "granite-3-8b-16l.1chip.json").read_text())
ENTRY = QWEN["modules"][0]


def test_flops_per_chunk_is_the_derivation():
    """27.50 TFLOP per 8 x 512 chunk: 2 x 3.303 G layer parameters x 4096
    tokens, 0.43 TFLOP of full S x S attention, 12.5 GFLOP of logits over
    the padded vocabulary (152064)."""
    layer_params = 10 * (2 * 5120 * 5120 + 2 * 5120 * 1024
                         + 3 * 5120 * 17408)
    assert layer_params == pytest.approx(3.303e9, rel=1e-3)
    want = (2 * layer_params * 4096 + 10 * 4 * 8 * 512 ** 2 * 40 * 128
            + 2 * 8 * 5120 * 152064)
    assert LM.flops_per_chunk(QWEN, ENTRY) == want
    assert want == pytest.approx(27.50e12, rel=1e-3)
    assert LM.tokens_per_chunk(QWEN, ENTRY) == 4096


def _program_shapes(cfg):
    from repro.models import api
    flat, _ = jax.tree.flatten_with_path(api.abstract_params(cfg))
    return {"/".join(str(k) for k in path): tuple(leaf.shape)
            for path, leaf in flat}


@pytest.mark.parametrize("which", ["qwen3", "tiny", "granite"])
def test_reference_weights_match_the_programs_tensors(which):
    """Every tensor the program's model holds is one the reference makes,
    by the same name and shape (the reference's copy of the weights
    follows the program's names)."""
    if which == "granite":
        from repro.configs import granite_3_8b
        cfg, mine = granite_3_8b.SERVED, GRANITE
    else:
        mine = QWEN if which == "qwen3" else conftest.TINY_LM
        cfg = builders.model_config(mine)
    assert _program_shapes(cfg) == {
        k: s for k, (s, _) in LM.param_table(mine).items()}


def test_reference_weights_are_the_programs_init():
    """The reference makes, from the same key, the weights the program's
    on-slot init makes (tiny size)."""
    from repro.models import api
    key = harness.weights_key(2 ** 33 + 7)
    cfg = builders.model_config(conftest.TINY_LM)
    prog = api.init_params(cfg, jax.random.PRNGKey(key))
    flat, _ = jax.tree.flatten_with_path(prog)
    mine = LM.make_weights(conftest.TINY_LM, key)
    assert len(flat) == len(mine)
    for path, leaf in flat:
        name = "/".join(str(k) for k in path)
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(mine[name]))


def test_builder_refuses_what_the_program_does_not_compute():
    with pytest.raises(ValueError, match="embedding_multiplier"):
        builders.model_config(dict(GRANITE, model_type="qwen3"))
    with pytest.raises(ValueError, match="model_type"):
        builders.model_config(GRANITE)


# granite's published scalars, at a tiny width
TINY_GRANITE = dict(
    conftest.TINY_LM, model_type="granite", tie_word_embeddings=True,
    rope_theta=10000.0, rms_norm_eps=1e-5, attention_multiplier=0.0078125,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=16.0)


def _hf_model(cfg, w):
    """transformers' implementation of `cfg`'s model, holding the
    reference's weights `w` (float32)."""
    import torch
    import transformers
    z = LM._sizes(cfg)
    common = dict(
        vocab_size=z["Vp"], hidden_size=z["D"], intermediate_size=z["F"],
        num_hidden_layers=z["L"], num_attention_heads=z["Hq"],
        num_key_value_heads=z["Hkv"], rope_theta=cfg["rope_theta"],
        rms_norm_eps=cfg["rms_norm_eps"], max_position_embeddings=4096,
        tie_word_embeddings=z["tied"], hidden_act="silu",
        attention_bias=False, torch_dtype="float32")
    if cfg["model_type"] == "qwen3":
        hf = transformers.Qwen3ForCausalLM(transformers.Qwen3Config(
            head_dim=z["hd"], **common))
    else:
        hf = transformers.GraniteForCausalLM(transformers.GraniteConfig(
            attention_multiplier=cfg["attention_multiplier"],
            embedding_multiplier=cfg["embedding_multiplier"],
            residual_multiplier=cfg["residual_multiplier"],
            logits_scaling=cfg["logits_scaling"], mlp_bias=False,
            **common))

    def t(x, transpose=False):
        x = torch.tensor(np.asarray(x, np.float32))
        return x.T.contiguous() if transpose else x

    b = LM._BLOCK
    with torch.no_grad():
        hf.model.embed_tokens.weight.copy_(t(w[LM._EMBED]))
        hf.model.norm.weight.copy_(t(w[LM._FINAL]))
        if not z["tied"]:
            hf.lm_head.weight.copy_(t(w[LM._HEAD], True))
        for i, layer in enumerate(hf.model.layers):
            def g(name, transpose=False):
                return t(w[b + name][i], transpose)
            a = layer.self_attn
            layer.input_layernorm.weight.copy_(g("['ln1_w']"))
            layer.post_attention_layernorm.weight.copy_(g("['ln2_w']"))
            a.q_proj.weight.copy_(g("['attn']/['wq']", True))
            a.k_proj.weight.copy_(g("['attn']/['wk']", True))
            a.v_proj.weight.copy_(g("['attn']/['wv']", True))
            a.o_proj.weight.copy_(g("['attn']/['wo']", True))
            if z["qk_norm"]:
                a.q_norm.weight.copy_(g("['attn']/['q_norm']"))
                a.k_norm.weight.copy_(g("['attn']/['k_norm']"))
            layer.mlp.gate_proj.weight.copy_(g("['mlp']/['w_gate']", True))
            layer.mlp.up_proj.weight.copy_(g("['mlp']/['w_up']", True))
            layer.mlp.down_proj.weight.copy_(g("['mlp']/['w_down']", True))
    return hf.eval()


@pytest.mark.parametrize("cfg", [conftest.TINY_LM, TINY_GRANITE],
                         ids=["qwen3", "granite"])
def test_reference_is_the_published_model(cfg):
    """Hugging Face transformers' Qwen3 and Granite, given the reference's
    weights, give the reference's logits (float32, tiny size, CPU): the
    reference follows each published model, not the program."""
    import torch
    cfg = dict(cfg, torch_dtype="float32")
    entry = cfg["modules"][0]
    items = LM.make_pool(cfg, entry, np.random.default_rng(5))[:2]
    key = 123
    want = LM.reference(cfg, entry, items, key)
    hf = _hf_model(cfg, LM.make_weights(cfg, key))
    with torch.no_grad():
        got = [hf(torch.tensor(tok, dtype=torch.long)).logits[:, -1]
               .numpy() for (tok,) in items]
    err = LM.compare(cfg, entry, got, want)["lm_logit_err"]
    assert err < 1e-4, err
    assert LM.compare(cfg, entry, want, want)["lm_logit_err"] == 0.0

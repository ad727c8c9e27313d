"""`moe-forward`'s work per chunk, its reference's weights against the
program's tensors and init, the reference against transformers' own
DeepSeek-V3, the program against the reference, and whole runs of a tiny
MoE cell through the daemon (CPU)."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conftest
from bench import harness

MOE = harness.load_file_module(conftest.ROOT / "bench" / "modules" /
                               "moe-forward.py")
MOONLIGHT = json.loads((conftest.ROOT / "bench" / "configs" /
                        "moonlight-16b-a3b-9l.1chip.json").read_text())
ENTRY = MOONLIGHT["modules"][0]
# DeepSeek-V3's equations (latent attention, one leading dense layer,
# routed experts with a sigmoid router and a score-correction bias, a
# shared expert) at a tiny width
TINY = {
    "name": "tiny-moe.1slot", "source": "test", "model_type": "deepseek_v3",
    "hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 4,
    "num_key_value_heads": 4, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
    "v_head_dim": 32, "kv_lora_rank": 64, "q_lora_rank": None,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "moe_intermediate_size": 64, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "n_shared_experts": 1, "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": 2.446,
    "moe_layer_freq": 1, "vocab_size": 1000, "rope_theta": 50000.0,
    "rms_norm_eps": 1e-05, "hidden_act": "silu",
    "tie_word_embeddings": False, "attention_bias": False,
    "torch_dtype": "bfloat16", "reduced": [], "chips": 1,
    "fabric": conftest.TINY_LM["fabric"], "policy": {"preemptive": True},
    "modules": [{"name": "moe-forward",
                 "entrypoint": "repro.core.zoo:build_lm_forward",
                 "model_from_config": True,
                 "impls": [{"name": "x1", "footprint": 1,
                            "est_chunk_ms": 5.0}],
                 "builder_args": {"batch": 2, "seq": 64}}],
    "checks": {"lm_logit_err": 0.07, "moe_route_gap": 0.01},
}
TINY_TRAFFIC = {"tenants": [
    {"name": "batch0", "role": "batch", "module": "moe-forward",
     "loop": "closed", "outstanding": 2}],
    "check_sample": {"batch0": 2}}
SEED = 2 ** 33 + 17


def test_flops_per_chunk_is_the_derivation():
    """6.32 TFLOP per 8 x 512 chunk: routed experts 3.402, shared 1.134,
    latent attention's projections 1.015, the dense layer 0.567, attention
    0.193, the router and the head at the last position."""
    d, tokens = 2048, 4096
    mla = d * 16 * 192 + d * 576 + 512 * 16 * 256 + 16 * 128 * d
    routed = 8 * 2 * tokens * 6 * 3 * d * 1408
    shared = 8 * 2 * tokens * 3 * d * 2816
    router = 8 * 2 * tokens * d * 64
    projections = 9 * 2 * tokens * mla
    dense = 2 * tokens * 3 * d * 11264
    scores = 9 * 2 * 8 * 512 ** 2 * 16 * (192 + 128)
    head = 2 * 8 * d * 163840
    assert routed == pytest.approx(3.402e12, rel=1e-3)
    assert shared == pytest.approx(1.134e12, rel=1e-3)
    assert projections == pytest.approx(1.015e12, rel=1e-3)
    assert dense == pytest.approx(0.567e12, rel=1e-3)
    assert scores == pytest.approx(0.1933e12, rel=1e-3)
    want = routed + shared + router + projections + dense + scores + head
    assert MOE.flops_per_chunk(MOONLIGHT, ENTRY) == want
    assert want == pytest.approx(6.32e12, rel=1e-3)
    assert MOE.tokens_per_chunk(MOONLIGHT, ENTRY) == tokens


def test_gmm_work_is_the_derivation():
    """Per MoE layer 2 x T x 6 x 3 x D x F operations; the 64 experts'
    three bf16 matrices read once, and the T x 6 routed rows of D read and
    written."""
    pairs, d, f = 4096 * 6, 2048, 1408
    work = MOE.gmm_work(MOONLIGHT, ENTRY)
    assert work["flops"] == 8 * 2 * pairs * 3 * d * f
    assert work["bytes"] == 8 * 2 * (64 * 3 * d * f + 2 * pairs * d)
    assert work["flops"] == pytest.approx(3.40e12, rel=5e-3)
    assert work["bytes"] == pytest.approx(10.46e9, rel=1e-3)


def _program_shapes(cfg):
    from repro.models import api
    flat, _ = jax.tree.flatten_with_path(api.abstract_params(cfg))
    return {"/".join(str(k) for k in path): tuple(leaf.shape)
            for path, leaf in flat}


@pytest.mark.parametrize("which", ["moonlight", "tiny"])
def test_reference_weights_match_the_programs_tensors(which):
    from repro.models import hf
    mine = MOONLIGHT if which == "moonlight" else TINY
    assert _program_shapes(hf.model_config(mine)) == {
        k: s for k, (s, _) in MOE.param_table(mine).items()}


def test_reference_weights_are_the_programs_init():
    from repro.models import api, hf
    key = harness.weights_key(SEED)
    prog = api.init_params(hf.model_config(TINY), jax.random.PRNGKey(key))
    flat, _ = jax.tree.flatten_with_path(prog)
    mine = MOE.make_weights(TINY, key)
    assert len(flat) == len(mine)
    for path, leaf in flat:
        name = "/".join(str(k) for k in path)
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(mine[name]))
    # the score-correction bias is drawn, not left at zero
    bias = np.asarray(mine["['blocks']/['sub0']/['moe']/['router_bias']"],
                      np.float32)
    assert np.all(bias != 0) and np.std(bias) > 0.005


def _items(cfg, n=2):
    return MOE.make_pool(cfg, cfg["modules"][0], np.random.default_rng(5))[:n]


def _program(cfg):
    from repro.core import zoo
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    return zoo.build_lm_forward(mesh, 1, model=cfg,
                                **cfg["modules"][0]["builder_args"])


def test_program_is_the_reference_in_float32():
    """The module's program (the dropless path, XLA's grouped matmul on
    the CPU), at float32 weights and compute, against the reference: the
    expert choices the reference's own router makes (a gap of 0), and
    logits within float32 rounding (1e-4 of the row's largest)."""
    from repro.models import hf, stack
    cfg = dict(TINY, torch_dtype="float32")
    mcfg = dataclasses.replace(hf.model_config(cfg),
                               compute_dtype=jnp.float32)
    prog = _program(cfg)
    key = harness.weights_key(SEED)
    params = prog.init_weights(jax.random.PRNGKey(key))

    @jax.jit
    def fwd(tokens):
        h, _, experts = stack.forward(params, mcfg, {"tokens": tokens},
                                      with_experts=True)
        return {"logits": stack.unembed(params, mcfg, h[:, -1:])[:, 0],
                "experts": experts}

    items = _items(cfg)
    got = [fwd(t) for (t,) in items]
    assert got[0]["experts"].shape == (2, 2, 64, 2)
    want = MOE.reference(cfg, cfg["modules"][0], items, key)
    out = MOE.compare(cfg, cfg["modules"][0], got, want)
    assert out["lm_logit_err"] < 1e-4, out
    assert out["moe_route_gap"] < 1e-6, out


def _mutant(cfg, key, items, tensor=None, experts=None):
    """Outputs of the reference's own forward in the module's form, with
    one tensor zeroed (a program that leaves out what it multiplies), or
    with each output's experts replaced by `experts(e)`."""
    w = MOE.make_weights(cfg, key)
    if tensor is not None:
        w = dict(w, **{tensor: w[tensor] * 0})
    fwd = MOE._forward(cfg)
    out = []
    for (t,) in items:
        logits, top, _ = fwd(w, t)
        top = np.asarray(top)
        out.append({"logits": np.asarray(logits),
                    "experts": top if experts is None else experts(top)})
    return out


@pytest.mark.parametrize("fault", [
    "router bias ignored", "shared expert left out", "an expert chosen twice",
    "an expert out of range"])
def test_comparison_refuses_a_faulty_program(fault):
    """Each fault, in outputs otherwise computed as the reference computes
    them, comes out not correct by the tiny cell's limits, while the
    module's program at bfloat16 comes out correct."""
    entry = TINY["modules"][0]
    key = harness.weights_key(SEED)
    items = _items(TINY, 3)
    moe = MOE._MOE + "['moe']/"

    def twice(e):
        return np.concatenate([e[..., :1], e[..., :-1]], -1)

    def out_of_range(e):
        e = e.copy()
        e[0, 0, 0, 0] = TINY["n_routed_experts"]
        return e

    got = {"router bias ignored": lambda: _mutant(
               TINY, key, items, moe + "['router_bias']"),
           "shared expert left out": lambda: _mutant(
               TINY, key, items, moe + "['shared']/['w_down']"),
           "an expert chosen twice": lambda: _mutant(
               TINY, key, items, experts=twice),
           "an expert out of range": lambda: _mutant(
               TINY, key, items, experts=out_of_range)}[fault]()
    want = MOE.reference(TINY, entry, items, key)
    limits = TINY["checks"]
    bad = MOE.compare(TINY, entry, got, want)
    assert any(bad[k] > lim for k, lim in limits.items()), bad

    prog = _program(TINY)
    params = jax.jit(prog.init_weights)(jax.random.PRNGKey(key))
    sound = MOE.compare(TINY, entry, [jax.jit(prog.fn)(params, t)
                                      for (t,) in items], want)
    assert all(sound[k] <= lim for k, lim in limits.items()), sound


def _hf_model(cfg, w):
    """transformers' DeepseekV3ForCausalLM holding the reference's
    weights `w` (float32), the router's score-correction bias included."""
    import torch
    import transformers
    z = MOE._sizes(cfg)
    hf = transformers.DeepseekV3ForCausalLM(transformers.DeepseekV3Config(
        vocab_size=z["Vp"], hidden_size=z["D"], intermediate_size=z["F"],
        moe_intermediate_size=z["Fe"], num_hidden_layers=z["L"],
        num_attention_heads=z["H"], num_key_value_heads=z["H"],
        n_shared_experts=cfg["n_shared_experts"], n_routed_experts=z["E"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        kv_lora_rank=z["r"], q_lora_rank=None,
        qk_rope_head_dim=z["rope"], qk_nope_head_dim=z["nope"],
        v_head_dim=z["v"], n_group=1, topk_group=1,
        num_experts_per_tok=z["K"], first_k_dense_replace=z["dense"],
        norm_topk_prob=True, rope_theta=cfg["rope_theta"],
        rms_norm_eps=cfg["rms_norm_eps"], max_position_embeddings=4096,
        tie_word_embeddings=z["tied"], hidden_act="silu",
        attention_bias=False, torch_dtype="float32"))

    def t(x, transpose=False):
        x = torch.tensor(np.asarray(x, np.float32))
        return x.transpose(-1, -2).contiguous() if transpose else x

    with torch.no_grad():
        hf.model.embed_tokens.weight.copy_(t(w[MOE._EMBED]))
        hf.model.norm.weight.copy_(t(w[MOE._FINAL]))
        hf.lm_head.weight.copy_(t(w[MOE._HEAD], True))
        for i, layer in enumerate(hf.model.layers):
            prefix, j = ((MOE._DENSE, i) if i < z["dense"]
                         else (MOE._MOE, i - z["dense"]))

            def g(name, transpose=False):
                return t(w[prefix + name][j], transpose)
            a = layer.self_attn
            layer.input_layernorm.weight.copy_(g("['ln1_w']"))
            layer.post_attention_layernorm.weight.copy_(g("['ln2_w']"))
            a.q_proj.weight.copy_(g("['attn']/['wq']", True))
            a.kv_a_proj_with_mqa.weight.copy_(g("['attn']/['wkv_a']", True))
            a.kv_a_layernorm.weight.copy_(g("['attn']/['kv_norm']"))
            a.kv_b_proj.weight.copy_(g("['attn']/['wkv_b']", True))
            a.o_proj.weight.copy_(g("['attn']/['wo']", True))
            if i < z["dense"]:
                mlp, names = layer.mlp, MOE._MLP
            else:
                m = layer.mlp
                m.gate.weight.copy_(g("['moe']/['w_router']", True))
                m.gate.e_score_correction_bias.copy_(
                    g("['moe']/['router_bias']"))
                for e, ex in enumerate(m.experts):
                    ex.gate_proj.weight.copy_(g("['moe']/['w1']", True)[e])
                    ex.up_proj.weight.copy_(g("['moe']/['w3']", True)[e])
                    ex.down_proj.weight.copy_(g("['moe']/['w2']", True)[e])
                mlp, names = m.shared_experts, MOE._EXPERTS[5:]
            mlp.gate_proj.weight.copy_(g(names[0], True))
            mlp.up_proj.weight.copy_(g(names[1], True))
            mlp.down_proj.weight.copy_(g(names[2], True))
    return hf.eval()


def test_reference_is_the_published_model():
    """transformers' DeepseekV3ForCausalLM, given the reference's weights
    (a drawn score-correction bias, epsilon 1e-5, one leading dense
    layer), gives the reference's logits (float32, tiny size, CPU)."""
    import torch
    cfg = dict(TINY, torch_dtype="float32")
    entry = cfg["modules"][0]
    items = _items(cfg)
    w = MOE.make_weights(cfg, 321)
    fwd = MOE._forward(cfg)
    want = [np.asarray(fwd(w, tok)[0]) for (tok,) in items]
    model = _hf_model(cfg, w)
    with torch.no_grad():
        got = [model(torch.tensor(tok, dtype=torch.long)).logits[:, -1]
               .numpy() for (tok,) in items]
    err = MOE._LM.compare(cfg, entry, got, want)["lm_logit_err"]
    assert err < 1e-4, err


@pytest.fixture
def tiny_moe_root(tmp_path):
    """conftest's tiny tree with the tiny MoE cell `tiny.moe` added."""
    root = conftest.make_tree(tmp_path)
    (root / "bench" / "configs" / f"{TINY['name']}.json").write_text(
        json.dumps(TINY))
    (root / "bench" / "traffic" / "moe.tiny.json").write_text(
        json.dumps(TINY_TRAFFIC))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(
        {"name": TINY["name"], "source": "test",
         "file": f"bench/configs/{TINY['name']}.json", "reduced": [],
         "why": "test"})
    spec["workloads"].append(
        {"name": "tiny.moe", "config": TINY["name"], "traffic": "moe.tiny",
         "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.mark.parametrize("trace", [False, True])
def test_moe_run_is_correct(tiny_moe_root, trace):
    """The tiny MoE cell through the daemon's normal path reads correct,
    and its control (the reference in float8) does not."""
    out = harness.run("tiny.moe", SEED, 1.0, trace, root=tiny_moe_root,
                      require_tpu=False, control=not trace,
                      trace_dir=tiny_moe_root / "bench" / "out" / "trace")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    if trace:
        # no chip: no device plane, so no device metric
        assert not {"moe_mfu", "experts_ms",
                    "gmm_roofline"} & set(out["metrics"])
    else:
        assert out["metrics"]["tokens_per_s"]["value"] > 0
        assert out["control_correct"] is False

"""`ModelConfig` from a configuration's Hugging Face keys.

For the model types whose published equations the stack computes: Qwen3
(RMS-normed queries and keys) and DeepSeek-V3 (latent attention without a
query LoRA, leading dense layers, routed experts with a sigmoid router
and a score-correction bias, shared experts).  A configuration that
states anything else is refused, so that no model runs under a name whose
equations the program departs from.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.models.api import ModelConfig, MoEConfig

# scalars the stack does not have, and the value that means "none"
_UNIT_SCALARS = ("embedding_multiplier", "residual_multiplier",
                 "logits_scaling")
_FALSE_KEYS = ("attention_bias", "mlp_bias", "use_sliding_window",
               "rope_scaling")


def _refuse(model: dict, bad: list[str]) -> None:
    if bad:
        raise ValueError(f"{model.get('name', model.get('model_type'))}: "
                         f"the program's stack does not compute {bad} as "
                         f"stated")


def _common(model: dict) -> dict:
    """The keys every supported type shares; refuses what the stack lacks
    of them."""
    bad = [k for k in _FALSE_KEYS if model.get(k)]
    bad += [k for k in _UNIT_SCALARS if model.get(k, 1.0) != 1.0]
    if model.get("hidden_act", "silu") != "silu":
        bad.append("hidden_act")
    _refuse(model, bad)
    return dict(
        name=model.get("name", model["model_type"]),
        n_layers=model["num_hidden_layers"], d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"], d_ff=model["intermediate_size"],
        vocab=model["vocab_size"], rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        param_dtype=jnp.dtype(model["torch_dtype"]))


def _qwen3(model: dict) -> ModelConfig:
    hd = model["head_dim"]
    _refuse(model, ["attention_multiplier"] if model.get(
        "attention_multiplier", hd ** -0.5) != hd ** -0.5 else [])
    return ModelConfig(family="dense", head_dim=hd, qk_norm=True,
                       n_kv_heads=model["num_key_value_heads"],
                       **_common(model))


def _deepseek_v3(model: dict) -> ModelConfig:
    bad = [k for k, ok in (
        ("q_lora_rank", model.get("q_lora_rank") is None),
        ("num_key_value_heads", model["num_key_value_heads"]
         == model["num_attention_heads"]),
        ("scoring_func", model.get("scoring_func") == "sigmoid"),
        ("topk_method", model.get("topk_method") == "noaux_tc"),
        # every group kept: no group-limited routing
        ("topk_group", model.get("topk_group", 1)
         == model.get("n_group", 1)),
        ("norm_topk_prob", model.get("norm_topk_prob") is True),
        ("moe_layer_freq", model.get("moe_layer_freq", 1) == 1),
        ("rope_interleave", model.get("rope_interleave", True) is True),
        ("first_k_dense_replace", model["first_k_dense_replace"]
         < model["num_hidden_layers"])) if not ok]
    _refuse(model, bad)
    f = model["moe_intermediate_size"]
    return ModelConfig(
        family="moe", n_kv_heads=model["num_attention_heads"],
        head_dim=model["qk_nope_head_dim"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_rope_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        first_dense=model["first_k_dense_replace"],
        moe=MoEConfig(
            n_experts=model["n_routed_experts"],
            top_k=model["num_experts_per_tok"], d_ff=f, impl="dropless",
            router="sigmoid_bias",
            routed_scale=float(model["routed_scaling_factor"]),
            shared_d_ff=f * model["n_shared_experts"]),
        **_common(model))


MODEL_TYPES = {"qwen3": _qwen3, "deepseek_v3": _deepseek_v3}


def model_config(model: dict) -> ModelConfig:
    """The program's `ModelConfig` for `model`, a configuration's keys;
    ValueError where it states what the stack cannot compute."""
    kind = model.get("model_type")
    if kind not in MODEL_TYPES:
        raise ValueError(f"model_type {kind!r}: the program's stack runs "
                         f"only {sorted(MODEL_TYPES)} as published")
    return MODEL_TYPES[kind](model)

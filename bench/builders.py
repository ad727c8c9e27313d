"""Module builders that a configuration can name as a module's
`entrypoint` (`bench.builders:<fn>`), for models the program's own zoo
does not serve.

`build_lm_forward` serves a dense decoder through the program's model
stack (`repro.models.stack`), as `repro.core.zoo.build_lm_forward` does
for the one model that builder is fixed to, but sized from the
configuration's keys (Hugging Face names), which the harness passes as
`model` when the module entry says `"model_from_config": true`.  It
refuses a configuration that states anything the program's stack does
not compute, so that no model runs under a name whose equations it
departs from.
"""
from __future__ import annotations

# model types whose blocks the program's dense stack computes as
# published, and the stack's switches each needs
MODEL_TYPES = {"qwen3": {"qk_norm": True}}
# what the program's stack fixes: `layers.rms_norm`'s epsilon, SiLU-gated
# MLPs, and no scalar multipliers
RMS_NORM_EPS = 1e-6
_UNIT_SCALARS = ("embedding_multiplier", "residual_multiplier",
                 "logits_scaling")


def model_config(model: dict):
    """The program's `ModelConfig` for `model`, a configuration's keys;
    ValueError where the configuration states what the stack cannot run."""
    import jax.numpy as jnp

    from repro.models.api import ModelConfig

    kind = model.get("model_type")
    if kind not in MODEL_TYPES:
        raise ValueError(f"model_type {kind!r}: the program's stack runs "
                         f"only {sorted(MODEL_TYPES)} as published")
    hd = model["head_dim"]
    bad = [k for k in _UNIT_SCALARS if model.get(k, 1.0) != 1.0]
    if model.get("attention_multiplier", hd ** -0.5) != hd ** -0.5:
        bad.append("attention_multiplier")
    if model.get("rms_norm_eps") != RMS_NORM_EPS:
        bad.append("rms_norm_eps")
    if model.get("hidden_act", "silu") != "silu":
        bad.append("hidden_act")
    bad += [k for k in ("attention_bias", "mlp_bias", "use_sliding_window",
                        "rope_scaling") if model.get(k)]
    if bad:
        raise ValueError(f"{model.get('name')}: the program's stack does "
                         f"not compute {bad} as stated")
    return ModelConfig(
        name=model["name"], family="dense",
        n_layers=model["num_hidden_layers"], d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=hd,
        d_ff=model["intermediate_size"], vocab=model["vocab_size"],
        rope_theta=float(model["rope_theta"]),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        param_dtype=jnp.dtype(model["torch_dtype"]),
        **MODEL_TYPES[kind])


def build_lm_forward(mesh, footprint: int, *, model: dict, batch: int,
                     seq: int):
    """Teacher-forced forward over a [batch, seq] chunk of token ids,
    returning the last position's logits [batch, padded vocab]; weights
    made on the slot from `AccelModule.weights_key`."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.module import ModuleProgram
    from repro.models import api, stack

    cfg = model_config(model)
    axis = mesh.axis_names[0]

    def fn(params, tokens):
        h, _ = stack.forward(params, cfg, {"tokens": tokens})
        return stack.unembed(params, cfg, h[:, -1:])[:, 0]

    pspecs = jax.tree.map(lambda _: P(), api.param_specs(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    return ModuleProgram(
        fn=fn,
        abstract_weights=api.abstract_params(cfg),
        abstract_inputs=(jax.ShapeDtypeStruct((batch, seq), jnp.int32),),
        weight_pspecs=pspecs,
        input_pspecs=(P(axis, None),),
        init_weights=lambda key: api.init_params(cfg, key))

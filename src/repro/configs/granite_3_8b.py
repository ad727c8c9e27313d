"""granite-3-8b [dense] — GQA.  [hf:ibm-granite/granite-3.0-8b-base]"""
import dataclasses

import jax.numpy as jnp

from repro.models.api import ModelConfig

CONFIG = ModelConfig(
    name="granite-3-8b", family="dense",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8, head_dim=128,
    d_ff=12800, vocab=49155, rope_theta=10000.0, tie_embeddings=True)

# What `lm-forward` serves on one TPU v5e chip (16 GB of HBM).  Every
# width is as published: d_model, heads, head_dim, d_ff, the full vocabulary.
# The deployment it stands for is one chip of a 40-layer pipeline: this
# chip holds 16 of the layers and the embedding (tied: it is also the LM
# head), the other 24 layers would be further stages.  Cuts from CONFIG:
#   - depth: n_layers 40 -> 16 (6.8 GB of bf16 weights; all 40, the
#     published 8.17B parameters, would be 16.3 GB);
#   - weight dtype: bf16, the checkpoint's published dtype (the
#     ModelConfig default is float32);
#   - weights: random, from a seed (`AccelModule.weights_key`), not the
#     checkpoint's.
# Not modelled: the published config's scalar multipliers (embedding,
# attention, residual, logits); they change no shape and no FLOP count.
SERVED = dataclasses.replace(CONFIG, name="granite-3-8b-16l", n_layers=16,
                             param_dtype=jnp.bfloat16)

REDUCED = ModelConfig(
    name="granite-3-8b-reduced", family="dense",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
    d_ff=128, vocab=256, rope_theta=10000.0, tie_embeddings=True)

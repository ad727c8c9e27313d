"""moonlight-16b-a3b [moe] — DeepSeek-V3's block: latent attention, one
leading dense layer, 64 routed experts (top-6, sigmoid router with a
score-correction bias) and 2 shared.
[hf:moonshotai/Moonlight-16B-A3B; hf]"""
from repro.models.api import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
    d_ff=11264, vocab=163840, rope_theta=50000.0, norm_eps=1e-5,
    kv_lora_rank=512, qk_rope_dim=64, v_head_dim=128, first_dense=1,
    moe=MoEConfig(n_experts=64, top_k=6, d_ff=1408, impl="dropless",
                  router="sigmoid_bias", routed_scale=2.446,
                  shared_d_ff=2816))

REDUCED = ModelConfig(
    name="moonlight-16b-a3b-reduced", family="moe",
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
    d_ff=128, vocab=256, rope_theta=50000.0, norm_eps=1e-5,
    kv_lora_rank=32, qk_rope_dim=8, v_head_dim=16, first_dense=1,
    moe=MoEConfig(n_experts=8, top_k=2, d_ff=32, impl="dropless",
                  router="sigmoid_bias", routed_scale=2.446,
                  shared_d_ff=64))

"""moonlight-16b-a3b [moe]: DeepSeek-V3 architecture (model type
``deepseek_v3``): multi-head latent attention, 64 fine-grained routed experts
(6 per token, sigmoid scores with a correction bias) plus 2 shared experts,
one leading dense layer.

27L (1 dense + 26 MoE), d_model=2048, 16H; MLA kv_lora_rank=512,
qk_nope=128, qk_rope=64, v=128, no query compression; dense MLP 11264,
experts 1408; vocab=163840 untied; RoPE theta 50000; RMSNorm eps 1e-5.
[hf:moonshotai/Moonlight-16B-A3B]
"""

import dataclasses

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    vocab=163840,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50_000.0,
    n_experts=64,
    top_k=6,
    router_score="sigmoid",
    routed_scale=2.446,
    n_shared_experts=2,
    first_dense_layers=1,
    d_ff_dense=11264,
    norm_eps=1e-5,
)


def smoke_config() -> ModelConfig:
    return dataclasses.replace(
        CONFIG, n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=32, vocab=512,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        n_experts=8, top_k=2, d_ff_dense=128,
    )

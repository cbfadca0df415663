"""deepseek-v2-lite-16b [moe] — MLA (kv_lora 512, no q compression) with
YaRN rope scaling; layer 0 dense, then 26 layers of 64 routed experts (top-6,
softmax scores, gates not renormalised) plus 2 shared experts.

Published config: https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json
(arXiv:2405.04434). Full V2 has 160 routed experts; V2-Lite has 64.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,                 # the single leading dense layer
    vocab_size=102400,
    rope_theta=10000.0,
    use_mla=True,
    kv_lora_rank=512,
    q_lora_rank=0,              # lite: no q compression
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    head_dim=192,               # nope + rope
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    num_experts=64,
    num_shared_experts=2,
    top_k=6,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    moe_d_ff=1408,
    first_dense_layers=1,
    norm_eps=1e-6,
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite "
           "(arXiv:2405.04434)",
)

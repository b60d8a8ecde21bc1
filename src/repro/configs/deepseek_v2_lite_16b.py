"""deepseek-v2-lite-16b [moe] — MLA kv_lora=512, 1 dense + 26 MoE layers of
2 shared + 64 routed experts, top-6 [arXiv:2405.04434].

Sizes as the published config.json
(https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json):
no q-LoRA, rope key 64 + nope 128, v 128; ``first_k_dense_replace`` 1 with
a SwiGLU of 10,944; routed experts of 1,408, softmax then greedy top-6
without renormalising (``norm_topk_prob`` false, ``routed_scaling_factor``
1); YaRN rope (factor 40 over 4,096 positions, beta 32/1, mscale and
mscale_all_dim 0.707); an unscaled token embedding and an untied head.
Served with the absorbed (latent-space) MLA decode.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    source="arXiv:2405.04434",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,              # v_head_dim; qk = nope 128 + rope 64
    d_ff=10944,                # the leading dense layer's SwiGLU
    vocab_size=102400,
    mlp_act="silu",
    embed_scale=False,
    tie_embeddings=False,
    norm_eps=1e-6,
    attn_kind="mla",
    rope_theta=10000.0,
    yarn_factor=40.0,          # rope_scaling, as published
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    mla_absorbed=True,
    num_experts=64,
    first_dense_layers=1,
    num_shared_experts=2,
    top_k=6,
    norm_topk_prob=False,
    moe_d_ff=1408,
)


def reduced() -> ModelConfig:
    return CONFIG.replace(
        name="deepseek-v2-lite-16b-reduced", num_layers=2, d_model=256,
        num_heads=4, num_kv_heads=4, head_dim=32, d_ff=512, vocab_size=512,
        kv_lora_rank=64, qk_nope_dim=32, qk_rope_dim=16, v_head_dim=32,
        num_experts=4, num_shared_experts=1, top_k=2, moe_d_ff=128)

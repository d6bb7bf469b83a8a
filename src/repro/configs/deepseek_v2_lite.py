"""deepseek-v2-lite — MLA without query compression, YaRN RoPE, and a
fine-grained MoE (2 shared + 64 routed experts, top-6, softmax scores not
renormalised).  The first layer is dense.  [arXiv:2405.04434;
https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json]
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,     # MLA: all heads share the compressed latent
    head_dim=128,        # qk_nope head dim; see MLAConfig for the full split
    d_ff=10944,          # the dense first layer's FFN width
    d_ff_dense=10944,
    vocab_size=102400,
    attention_kind="mla",
    mla=MLAConfig(
        q_lora_rank=0,   # queries projected directly
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
    ),
    moe=MoEConfig(
        num_experts=64,
        num_shared_experts=2,
        top_k=6,
        d_ff_expert=1408,
        first_k_dense=1,
        every=1,
        scoring="softmax",   # norm_topk_prob false, routed_scaling_factor 1
        aux_loss_coef=0.001,
    ),
    rope_theta=10000.0,
    rope_interleaved=True,   # the published code rotates pairs (2i, 2i+1)
    yarn_factor=40.0,
    yarn_original_max_positions=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    norm_eps=1e-6,
    act="silu",
)

"""deepseek-v2-lite-16b — MoE with MLA (kv_lora=512), 64 routed experts
top-6 + 2 shared, one leading dense layer.
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json]

As published: 27 layers, the first dense (``first_k_dense_replace`` 1);
every other layer routes each token to 6 of 64 experts by softmax, keeping
the raw probabilities (``norm_topk_prob`` false, ``routed_scaling_factor``
1), beside 2 shared experts.  Attention is MLA without query compression,
with YaRN rotary scaling (factor 40 over 4096 original positions).
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,           # MLA: heads share the compressed latent cache
    head_dim=128,              # nope head dim
    d_ff=10944,                # dense FFN of the leading layer
    vocab_size=102400,
    ffn_activation="swiglu",
    norm_eps=1e-6,
    rope_theta=10000.0,
    rope_scaling_factor=40.0,  # YaRN
    rope_mscale_all_dim=0.707,
    num_experts=64,
    num_experts_per_tok=6,
    num_shared_experts=2,
    moe_d_ff=1408,
    first_dense_layers=1,
    norm_topk_prob=False,
    use_mla=True,
    kv_lora_rank=512,
    q_lora_rank=0,             # v2-lite has no q compression
    rope_head_dim=64,
    v_head_dim=128,
)

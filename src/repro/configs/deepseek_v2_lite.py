"""deepseek-v2-lite [moe]: latent attention, 2 shared + 64 routed experts.

27L d_model=2048 16H, MLA (kv_lora_rank 512, no q_lora; qk_nope 128,
qk_rope 64, v_head 128) with YaRN rope (factor 40 over 4,096 positions),
one leading dense SwiGLU of 10,944, then MoE layers of 64 experts of 1,408,
softmax top-6 without renormalising the gates, vocab 102,400.
[https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json,
arXiv:2405.04434]

``EP8`` is one chip's share of an 8-chip expert-parallel worker, at the
published widths (model-configs guide §4): the dense layer and 4 MoE layers
(further pipeline stages hold the rest), experts 0-7 of each MoE layer's
64, an eighth of the vocabulary. At 4,096 tokens a worker its gradient
pass needs the layer checkpoint, which a run turns on with ``--remat``.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,           # the leading dense layer's SwiGLU
    moe_d_ff=1408,
    vocab_size=102400,
    num_experts=64,
    num_experts_per_tok=6,
    num_shared_experts=2,
    first_dense_layers=1,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    router_aux_weight=0.001,  # alpha: not in the config (assumed)
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    rope_factor=40.0,
    rope_original_max_pos=4096,
    rope_beta_fast=32.0,
    rope_beta_slow=1.0,
    rope_mscale=0.707,
    rope_mscale_all_dim=0.707,
    mlp_type="swiglu",
)

EP8 = CONFIG.cut(moe_layers=4, experts_held=8, first_expert=0,
                 vocab_size=12800).replace(name="deepseek-v2-lite-ep8")

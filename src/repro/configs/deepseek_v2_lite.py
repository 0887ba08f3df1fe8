"""deepseek-v2-lite [moe, MLA] — multi-head latent attention (one 512 + 64
latent per token and layer), YaRN rotary scaling, layer 0 a dense SwiGLU
MLP, layers 1-26 64 routed experts of 1408 (softmax, greedy top-6, no
renormalization) plus 2 shared experts.
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json]

Served through `InferenceEngine.serve` only: the latent form of
attention exists in the paged serving step (`models.attention
.mla_span_attention_paged`), not in the rectangular forward/generate
paths.
"""
from repro.configs.base import ModelConfig, MoEConfig, RopeScaling

YARN = RopeScaling(factor=40.0, original_max_position_embeddings=4096,
                   beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                   mscale_all_dim=0.707)


def full() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        layout="moe",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        head_dim=192,
        d_ff=10944,                       # layer 0's dense MLP
        vocab_size=102400,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=10000.0,
        rope_scaling=YARN,
        moe=MoEConfig(num_experts=64, top_k=6, num_shared=2,
                      d_expert=1408, first_dense=1,
                      norm_topk_prob=False, routed_scaling_factor=1.0),
        mlp_act="swiglu",
        norm_eps=1e-6,
    )


def smoke() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-smoke",
        layout="moe",
        num_layers=3,
        d_model=64,
        num_heads=4,
        num_kv_heads=4,
        head_dim=24,
        d_ff=96,
        vocab_size=256,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        rope_theta=10000.0,
        rope_scaling=YARN,
        moe=MoEConfig(num_experts=8, top_k=3, num_shared=1, d_expert=32,
                      first_dense=1, norm_topk_prob=False),
        mlp_act="swiglu",
        norm_eps=1e-6,
        dtype="float32",
        remat=False,
    )

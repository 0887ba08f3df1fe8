"""deepseek-moe-16b [moe] — layer 0 a dense SwiGLU MLP (d_ff 10944), layers
1-27 2 shared + 64 routed fine-grained experts of 1408, top-6, gate
weights not renormalized.
[arXiv:2401.06066; huggingface.co/deepseek-ai/deepseek-moe-16b-base
config.json: first_k_dense_replace 1, intermediate_size 10944,
moe_intermediate_size 1408, norm_topk_prob false]"""
from repro.configs.base import ModelConfig, MoEConfig


def full() -> ModelConfig:
    return ModelConfig(
        name="deepseek-moe-16b",
        layout="moe",
        num_layers=28,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        d_ff=10944,                       # layer 0's dense MLP
        vocab_size=102400,
        moe=MoEConfig(num_experts=64,
                      top_k=6,
                      num_shared=2,
                      capacity_factor=1.25,
                      d_expert=1408,      # per fine-grained expert
                      first_dense=1,
                      norm_topk_prob=False),
        mlp_act="swiglu",
    )


def smoke() -> ModelConfig:
    return ModelConfig(
        name="deepseek-moe-16b-smoke",
        layout="moe",
        num_layers=2,
        d_model=64,
        num_heads=4,
        num_kv_heads=4,
        d_ff=96,
        vocab_size=256,
        # cf = E/k: dropless in the smoke tests (prefix consistency)
        moe=MoEConfig(num_experts=8, top_k=3, num_shared=1,
                      capacity_factor=2.7, d_expert=48, first_dense=1,
                      norm_topk_prob=False),
        mlp_act="swiglu",
        dtype="float32",
        remat=False,
    )

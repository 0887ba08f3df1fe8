"""Model configuration schema covering every assigned architecture family.

One dataclass drives the whole zoo: dense / MoE / SSM / hybrid layouts,
GQA geometry, attention flavors (sliding window, local-global alternation,
logit soft-capping), MLP flavors (SwiGLU, squared-ReLU, GELU), Mamba1/2
blocks, and stub modality frontends (audio / vision token streams).
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    num_shared: int = 0            # shared (always-on) experts, deepseek-style
    capacity_factor: float = 1.25  # training/rectangular dispatch only
    router_jitter: float = 0.0
    d_expert: Optional[int] = None  # one expert's width (default d_ff)
    first_dense: int = 0           # leading layers with a dense d_ff MLP
    norm_topk_prob: bool = True    # renormalize the top-k gate weights
    routed_scaling_factor: float = 1.0

    def expert_width(self, d_ff: int) -> int:
        return self.d_expert or d_ff


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN rotary scaling (DeepSeek-V2): frequencies past the
    `original_max_position_embeddings` correction range are divided by
    `factor`, and attention logits are scaled by mscale(mscale_all_dim)^2.

    DeepSeek-V2 also multiplies cos and sin by mscale(mscale) /
    mscale(mscale_all_dim); the served rotary applies no such factor, so
    a configuration where it is not 1 is refused."""
    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def __post_init__(self):
        if self.factor > 1 and self.mscale != self.mscale_all_dim:
            raise ValueError(
                f"YaRN mscale {self.mscale} != mscale_all_dim "
                f"{self.mscale_all_dim} scales cos/sin, which the served "
                f"rotary does not apply")


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    version: int = 1               # 1 = Mamba1 selective scan, 2 = Mamba2 SSD
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64             # Mamba2 only
    dt_rank: Optional[int] = None  # default d_model // 16
    chunk: int = 128               # chunked-scan block (perf option)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    layout: str = "dense"          # dense | moe | ssm | hybrid
    num_layers: int = 4
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    d_ff: int = 1024
    vocab_size: int = 1024
    head_dim: Optional[int] = None  # default d_model // num_heads

    # attention flavor
    attn_window: Optional[int] = None       # sliding-window size (Mixtral)
    local_global_period: int = 0            # >0: alternate local/global (Gemma2)
    local_window: int = 4096                # window of the "local" layers
    logit_softcap: float = 0.0              # Gemma2 attn soft-capping
    final_softcap: float = 0.0              # Gemma2 final-logit soft-capping
    rope_theta: float = 10000.0
    rope_scaling: Optional[RopeScaling] = None  # YaRN (DeepSeek-V2)
    rotary_pct: float = 1.0                 # StableLM partial rotary
    pos_emb: str = "rope"                   # rope | sinusoidal | none
    attn_impl: str = "auto"                 # auto | full | chunked
    attn_chunk: int = 1024                  # KV block for chunked attention
    # serving attention over the blocked KV pool (span_attention_paged):
    # "kernel" = Pallas paged-attention (block-table DMA walk, online
    # softmax, in-kernel int8-KV dequant); "ref" = the jnp gather oracle;
    # "auto" = kernel on TPU, oracle on CPU (same dispatch rule as the
    # matmul kernels — interpret-mode Pallas inside the big jitted step
    # would bloat the HLO for tests while the TPU path gets the O(ctx)
    # streaming win).
    paged_attn_impl: str = "auto"           # auto | kernel | ref

    # multi-head latent attention (DeepSeek-V2): kv_lora_rank > 0 caches
    # one (kv_lora_rank + qk_rope_head_dim)-wide latent per token and
    # layer instead of per-head K and V; queries are qk_nope_head_dim +
    # qk_rope_head_dim wide per head, values v_head_dim
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # MLP flavor
    mlp_act: str = "swiglu"                 # swiglu | relu2 | gelu | geglu

    # mixture-of-experts / ssm blocks
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    hybrid_period: int = 6                  # Zamba2: shared attn every N blocks

    # modality frontend stub: "none" -> token ids; "audio"/"vision" ->
    # precomputed frame/patch embeddings are fed directly (see input_specs).
    frontend: str = "none"

    # numerics / norms
    kv_cache_bits: int = 16                 # 16 (model dtype) | 8 (int8+scales)
    norm: str = "rmsnorm"                   # rmsnorm | layernorm
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    tie_embeddings: bool = False

    # training-time policy
    remat: bool = True
    remat_policy: str = "full"              # full | dots (save matmul outs)
    loss_chunk: int = 2048                  # vocab-chunked loss block (tokens)

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def first_dense(self) -> int:
        """Leading layers that run a dense MLP in a MoE layout."""
        return self.moe.first_dense if self.layout == "moe" else 0

    @property
    def is_attention_free(self) -> bool:
        return self.layout == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic decode: SSM / hybrid / bounded-window attention."""
        if self.layout in ("ssm", "hybrid"):
            return True
        return self.attn_window is not None and self.local_global_period == 0

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks)."""
        d, L, V = self.d_model, self.num_layers, self.vocab_size
        n = V * d  # embed
        if not self.tie_embeddings:
            n += V * d
        hd = self.head_dim
        attn = d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd \
            + self.num_heads * hd * d
        if self.is_mla:
            c, r = self.kv_lora_rank, self.qk_rope_head_dim
            qd = self.qk_nope_head_dim + r
            attn = (d * self.num_heads * qd + d * (c + r)
                    + c * self.num_heads * (self.qk_nope_head_dim
                                            + self.v_head_dim)
                    + self.num_heads * self.v_head_dim * d)
        mlp_mats = 3 if self.mlp_act in ("swiglu", "geglu") else 2
        mlp = mlp_mats * d * self.d_ff
        if self.layout == "dense":
            n += L * (attn + mlp)
        elif self.layout == "moe":
            e = self.moe.num_experts + self.moe.num_shared
            emlp = mlp_mats * d * self.moe.expert_width(self.d_ff)
            fd = self.first_dense
            n += L * attn + fd * mlp
            n += (L - fd) * (e * emlp + d * self.moe.num_experts)
        elif self.layout == "ssm":
            di = d * self.ssm.expand
            dtr = self.ssm.dt_rank or d // 16
            blk = d * 2 * di + di * (dtr + 2 * self.ssm.d_state) \
                + dtr * di + di * d + di * self.ssm.d_conv + di * self.ssm.d_state
            n += L * blk
        elif self.layout == "hybrid":
            di = d * self.ssm.expand
            nh = di // self.ssm.head_dim
            blk = d * (2 * di + 2 * self.ssm.d_state + nh) + di * d \
                + di * self.ssm.d_conv
            n += L * blk            # mamba2 blocks (no per-block MLP)
            n += attn + mlp         # one shared attention+MLP block
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: top-k + shared experts only)."""
        if self.layout != "moe":
            return self.param_count()
        d, L = self.d_model, self.num_layers
        mlp_mats = 3 if self.mlp_act in ("swiglu", "geglu") else 2
        mlp = mlp_mats * d * self.moe.expert_width(self.d_ff)
        e_all = self.moe.num_experts + self.moe.num_shared
        e_act = self.moe.top_k + self.moe.num_shared
        return self.param_count() - (L - self.first_dense) * (
            e_all - e_act) * mlp

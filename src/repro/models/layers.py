"""Shared building blocks: norms, MLP flavors, RoPE, linear dispatch.

`apply_linear` is the single matmul entry point for the whole zoo — it
dispatches on the weight node type, so a model runs dense (Array),
quantized (QuantizedTensor) or ITERA low-rank (LowRankQ) without any model
code changes. Kernel usage is controlled by `repro.models.linear_mode`:

  "auto"     — Pallas kernels on TPU, jnp reference math elsewhere
  "kernel"   — force Pallas (interpret=True off-TPU; used by kernel tests)
  "ref"      — force the pure-jnp path (used inside dry-runs: identical
               numerics, SPMD-friendly HLO)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.itera import LowRankQ
from repro.core.quant import QuantizedTensor
from repro.kernels import ops as kops

_LINEAR_MODE = "auto"


def set_linear_mode(mode: str) -> None:
    global _LINEAR_MODE
    assert mode in ("auto", "kernel", "ref")
    _LINEAR_MODE = mode


def get_linear_mode() -> str:
    return _LINEAR_MODE


def apply_linear(x: jax.Array, w, out_dtype=None, *,
                 reduce_tp: bool = False) -> jax.Array:
    """y = x @ w for w: Array | QuantizedTensor | LowRankQ.

    reduce_tp marks the tensor-parallel REDUCTION sites (wo, mlp down):
    under shard_map serving their input features are row-split across
    shards, so the local product is a partial sum. With a TP axis bound
    (runtime.shardctx.tp_axis) this computes the partial in f32, psums
    it, and casts ONCE after the reduce — the same single rounding the
    unsharded dot performs on its f32 accumulator, which is what keeps
    bf16 TP serving token-identical to the single-device engine (bf16
    partials rounded before the psum would drift). With no TP axis
    bound (every non-serving path, single-device serving) the flag is
    inert and this is the plain dispatch below.
    """
    out_dtype = out_dtype or x.dtype
    if reduce_tp:
        from repro.runtime import shardctx

        if shardctx.get_tp_axis() is not None:
            if isinstance(w, (LowRankQ, QuantizedTensor)):
                # compressed K-sites requantize activations over LOCAL
                # features — numerically close, not bit-equal (see
                # launch.sharding); still reduce in f32.
                y = apply_linear(x, w, out_dtype=jnp.float32)
            else:
                y = jnp.matmul(x, w.astype(x.dtype),
                               preferred_element_type=jnp.float32)
            return shardctx.psum_tp(y).astype(out_dtype)
    if isinstance(w, LowRankQ):
        if _LINEAR_MODE == "ref" or (_LINEAR_MODE == "auto" and not kops.on_tpu()):
            return kops.lrmm(x, w, use_kernel=False, out_dtype=out_dtype)
        return kops.lrmm(x, w, use_kernel=True, out_dtype=out_dtype)
    if isinstance(w, QuantizedTensor):
        if _LINEAR_MODE == "ref" or (_LINEAR_MODE == "auto" and not kops.on_tpu()):
            return kops.qmm(x, w, use_kernel=False, out_dtype=out_dtype)
        return kops.qmm(x, w, use_kernel=True, out_dtype=out_dtype)
    return jnp.asarray(x @ w.astype(x.dtype), out_dtype)


def weight_shape(w) -> tuple:
    """(K, N) of a linear node regardless of representation — the LOGICAL
    shape (QuantizedTensor.shape unpacks the halved last dim of
    packed-nibble W4 storage)."""
    if isinstance(w, LowRankQ):
        return (w.w1.shape[0], w.w2.shape[1])
    if isinstance(w, QuantizedTensor):
        return tuple(w.shape)
    return tuple(w.shape)


# ----------------------------------------------------------------- norms --
def rmsnorm(x, gamma, eps=1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * (
        1.0 + gamma.astype(x.dtype)
    )


def layernorm(x, gamma, beta, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * gamma.astype(x.dtype) + beta.astype(x.dtype)


def apply_norm(x, p, kind: str, eps: float):
    if kind == "layernorm":
        return layernorm(x, p["gamma"], p["beta"], eps)
    return rmsnorm(x, p["gamma"], eps)


def init_norm(kind: str, d: int, dtype):
    if kind == "layernorm":
        return {"gamma": jnp.ones((d,), dtype), "beta": jnp.zeros((d,), dtype)}
    return {"gamma": jnp.zeros((d,), dtype)}   # rmsnorm stores gamma-1


# ------------------------------------------------------------------ MLPs --
def mlp_apply(x, p, act: str):
    if act in ("swiglu", "geglu"):
        g = apply_linear(x, p["gate"])
        u = apply_linear(x, p["up"])
        h = (jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)) * u
    elif act == "relu2":  # squared ReLU (Nemotron-4)
        h = jnp.square(jax.nn.relu(apply_linear(x, p["up"])))
    else:  # gelu
        h = jax.nn.gelu(apply_linear(x, p["up"]))
    return apply_linear(h, p["down"], reduce_tp=True)


def mlp_init(key, d: int, d_ff: int, act: str, dtype):
    ks = jax.random.split(key, 3)
    std_in, std_out = d ** -0.5, d_ff ** -0.5
    p = {
        "up": jax.random.normal(ks[0], (d, d_ff), dtype) * std_in,
        "down": jax.random.normal(ks[1], (d_ff, d), dtype) * std_out,
    }
    if act in ("swiglu", "geglu"):
        p["gate"] = jax.random.normal(ks[2], (d, d_ff), dtype) * std_in
    return p


# ------------------------------------------------------------------ RoPE --
def rope_freqs(head_dim: int, theta: float, rotary_pct: float = 1.0):
    rot = int(head_dim * rotary_pct) // 2 * 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv, rot


def yarn_inv_freq(dim: int, theta: float, scaling):
    """YaRN rotary frequencies (DeepSeek-V2's `DeepseekV2YarnRotaryEmbedding`):
    frequency i keeps f_i = theta^(-2i/dim) below the correction range,
    takes f_i / factor above it, and blends linearly inside it. The range
    is [floor(d(beta_fast)), ceil(d(beta_slow))], clipped to [0, dim - 1],
    where d(b) = dim * ln(orig / (2 pi b)) / (2 ln theta) is the index
    whose wavelength fits b times into the original context."""
    import math

    def corr(rot):
        return dim * math.log(scaling.original_max_position_embeddings
                              / (rot * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(corr(scaling.beta_fast)), 0)
    hi = min(math.ceil(corr(scaling.beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    f = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp
    return f / scaling.factor * (1.0 - keep) + f * keep


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN attention-temperature factor 0.1 * mscale * ln(factor) + 1."""
    import math

    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x, positions, theta: float, rotary_pct: float = 1.0):
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    inv, rot = rope_freqs(hd, theta, rotary_pct)
    return rotate_pairs(x, positions, inv, rot)


def rotate_pairs(x, positions, inv, rot: int | None = None):
    """Rotate the interleaved pairs (2i, 2i+1) of x's first `rot` dims by
    positions * inv[i]. x: (..., S, H, Dh)."""
    rot = x.shape[-1] if rot is None else rot
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., S, rot/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    yr = jnp.stack([y1, y2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([yr.astype(x.dtype), xp], axis=-1)


def sinusoidal_emb(positions, d_model: int, dtype):
    half = d_model // 2
    inv = 1.0 / (10000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


def softcap(x, cap: float):
    return (cap * jnp.tanh(x / cap)) if cap > 0 else x

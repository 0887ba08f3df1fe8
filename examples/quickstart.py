"""Quickstart: ITERA-LLM in ~60 seconds on CPU.

1. Build a weight matrix with LLM-like structure (decaying spectrum +
   outliers) and show Algorithm 1 beating one-shot SVD+quant at W4.
2. Compress a whole (smoke-size) model through per-layer CompressionPlans
   — uniform quant / svd / itera, plus a mixed W4-attention / W8-MLP plan
   the legacy single-method config could not express.
3. Serve the compressed model through the InferenceEngine facade.
4. Run the fused cascade Pallas kernel against its oracle (compiled on a
   TPU, interpret mode elsewhere).

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.api import CompressionPlan, InferenceEngine, LayerPlan, SamplingParams
from repro.configs import get_config
from repro.core import (
    compress_params, itera_decompose, reconstruction_error, svd_decompose,
)
from repro.kernels import ops
from repro.models import init_params
from repro.models.transformer import forward


def llm_like(key, k, n):
    ku, kv, ko = jax.random.split(key, 3)
    u = jax.random.normal(ku, (k, min(k, n)))
    v = jax.random.normal(kv, (min(k, n), n))
    s = jnp.exp(-0.02 * jnp.arange(min(k, n)))
    return (u * s) @ v + jax.random.bernoulli(ko, 0.001, (k, n)) * 10.0


def main():
    key = jax.random.PRNGKey(0)

    print("== 1. Algorithm 1 vs one-shot SVD+quant (W4, rank 128) ==")
    w = llm_like(key, 512, 512)
    for rank in (64, 128, 256):
        e_it = float(reconstruction_error(w, itera_decompose(w, rank, 4)))
        e_sv = float(reconstruction_error(w, svd_decompose(w, rank, 4)))
        print(f"  rank {rank:3d}:  itera {e_it:.4f}   svd+quant {e_sv:.4f}"
              f"   ({100 * (e_sv - e_it) / e_sv:+.1f}% better)")

    print("== 2. Whole-model plans (opus-mt smoke) ==")
    cfg = get_config("opus-mt", smoke=True)
    params = init_params(key, cfg)
    toks = jax.random.randint(key, (2, 32), 0, cfg.vocab_size)
    h_ref, _ = forward(params, toks, cfg)

    plans = [CompressionPlan.uniform(params, method=m, weight_wl=4,
                                     rank_fraction=0.5)
             for m in ("quant", "svd", "itera")]
    itera_plan = plans[-1]
    # mixed precision: W4 attention, W8 MLP — a per-layer decision only a
    # plan (not the legacy uniform config) can express.
    plans.append(itera_plan.replace(
        label="itera_W4attn_W8mlp",
        layers=tuple(
            LayerPlan(lp.path, "itera", 4 if "attn" in lp.path else 8,
                      lp.rank)
            for lp in itera_plan.layers)))
    for plan in plans:
        cp, rep = compress_params(params, plan)
        h, _ = forward(cp, toks, cfg)
        dist = float(jnp.linalg.norm(h - h_ref) / jnp.linalg.norm(h_ref))
        print(f"  {plan.label:18s}: {rep.summary()}  output-dist={dist:.4f}")

    print("== 3. Serve the mixed plan through the engine facade ==")
    engine = InferenceEngine.build(cfg, plans[-1], params=params)
    res = engine.generate(toks[:, :16], SamplingParams(max_tokens=8))
    print(f"  generated {res.tokens.shape} "
          f"({res.tokens_per_second:.1f} tok/s): {res.tokens[0].tolist()}")

    print("== 4. Fused cascade kernel vs oracle ==")
    x = jax.random.normal(key, (64, 512))
    lr = itera_decompose(llm_like(key, 512, 512) / 22.0, 128, 6)
    y_k = ops.lrmm(x, lr, use_kernel=True)
    y_r = ops.lrmm(x, lr, use_kernel=False)
    print(f"  kernel vs oracle max|diff| = "
          f"{float(jnp.max(jnp.abs(y_k - y_r))):.2e}")
    print("done.")


if __name__ == "__main__":
    main()

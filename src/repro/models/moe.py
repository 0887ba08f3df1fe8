"""Mixture-of-Experts block: top-k routing with capacity-bounded
scatter/gather dispatch (collective-friendly under GSPMD), shared
(always-on) experts (DeepSeek-MoE), and an auxiliary load-balance loss —
the training and rectangular-decode form (`moe_apply`). The serving step
routes through `moe_routed` instead: valid rows only, dropless, experts
walked by the grouped kernel over pairs sorted by expert.

Dispatch strategy (see DESIGN.md §4): tokens are scattered into per-expert
buffers (E, C, D) whose positions come from a cumsum over the routing mask —
no (T, E, C) one-hot tensor is ever materialized, so the memory footprint is
O(T·E + E·C·D), and under a sharded T the scatter/gather lowers to the
all-to-all-style collectives real expert parallelism uses. Expert FLOPs are
the *active* FLOPs (E·C·D·F with C ≈ T·k·cf/E), not the dense E× blow-up.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.itera import LowRankQ
from repro.core.quant import QuantizedTensor
from repro.kernels import ops as kops
from repro.models.layers import (
    apply_linear, get_linear_mode, mlp_apply, mlp_init,
)
from repro.runtime.shardctx import get_mesh, maybe_shard


def moe_init(key, cfg, dtype):
    d, m = cfg.d_model, cfg.moe
    f = m.expert_width(cfg.d_ff)
    ks = jax.random.split(key, 4)
    e = m.num_experts
    mats = 3 if cfg.mlp_act in ("swiglu", "geglu") else 2
    names = ["up", "down", "gate"][:mats]
    shapes = {"up": (d, f), "down": (f, d), "gate": (d, f)}
    experts = {
        n: jax.random.normal(ks[0], (e, *shapes[n]), dtype)
        * (shapes[n][0] ** -0.5)
        for n in names
    }
    p = {"router": jax.random.normal(ks[1], (d, e), jnp.float32) * d ** -0.5,
         "experts": experts}
    if m.num_shared:
        p["shared"] = mlp_init(ks[2], d, f * m.num_shared, cfg.mlp_act, dtype)
    return p


def _expert_ffn(xb, experts, act):
    """xb: (E, C, D); experts: dict of (E, K, N) stacks."""
    def one(x, up, down, gate=None):
        p = {"up": up, "down": down}
        if gate is not None:
            p["gate"] = gate
        return mlp_apply(x, p, act)

    if "gate" in experts:
        return jax.vmap(one)(xb, experts["up"], experts["down"], experts["gate"])
    return jax.vmap(lambda x, u, dn: one(x, u, dn))(
        xb, experts["up"], experts["down"])


def moe_apply(params, x, cfg, *, capacity: int | None = None):
    """x: (B, S, D) -> (B, S, D), aux load-balance loss."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = m.num_experts, m.top_k
    if capacity is None:
        capacity = max(1, int(t * k * m.capacity_factor / e))
        if capacity > 512:  # round for clean sharding of the C dim
            capacity = -(-capacity // 512) * 512

    xt = maybe_shard(x.reshape(t, d), "tokens", None)
    logits = apply_linear(xt.astype(jnp.float32), params["router"],
                          out_dtype=jnp.float32)          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                    # (T, k)
    gate = _gate_weights(gate, m)

    # positions within each expert buffer via cumsum over the routing mask
    mask = jax.nn.one_hot(idx, e, dtype=jnp.int32).sum(1)  # (T, E) in {0..k}
    pos_in_e = jnp.cumsum(mask, axis=0) - mask             # (T, E) 0-based
    pos = jnp.take_along_axis(pos_in_e, idx, axis=1)       # (T, k)
    ok = pos < capacity

    # scatter token copies into (E*C [+1 dump row], D)
    tgt = jnp.where(ok, idx * capacity + pos, e * capacity)
    buf = jnp.zeros((e * capacity + 1, d), x.dtype)
    x_rep = maybe_shard(jnp.repeat(xt, k, axis=0), "tokens", None)
    buf = buf.at[tgt.reshape(-1)].add(x_rep)
    buf3 = buf[:-1].reshape(e, capacity, d)
    # expert-parallel when E divides the model axis, else C over batch only
    mesh = get_mesh()
    ep = mesh is not None and e % mesh.shape["model"] == 0
    buf3 = maybe_shard(buf3, "model" if ep else None, "batch", None)
    yb = _expert_ffn(buf3, params["experts"], cfg.mlp_act)  # (E, C, D)

    # gather back with gates
    flat = yb.reshape(e * capacity, d)
    flat = jnp.concatenate([flat, jnp.zeros((1, d), flat.dtype)], 0)
    picked = maybe_shard(flat[tgt.reshape(-1)].reshape(t, k, d),
                         "tokens", None, None)
    y = maybe_shard(jnp.einsum("tk,tkd->td", gate.astype(x.dtype), picked),
                    "tokens", None)

    if "shared" in params:
        y = y + mlp_apply(xt, params["shared"], cfg.mlp_act)

    # Switch-style load-balance aux loss
    frac_tokens = mask.astype(jnp.float32).mean(0) * e / k
    frac_prob = probs.mean(0) * e
    aux = jnp.mean(frac_tokens * frac_prob)
    return y.reshape(b, s, d), aux


def _gate_weights(gate, m):
    """The top-k gate weights as the model states them: renormalized to
    sum to one where `norm_topk_prob`, times `routed_scaling_factor`."""
    if m.norm_topk_prob:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    return gate * m.routed_scaling_factor


def _grouped(x, w, offsets):
    """Rows of x sorted by expert through their own expert's linear, one
    grouped product whose work follows the rows: `kernels.ops.lrgmm` for
    ITERA stacks (the grouped kernel on the TPU, its oracle elsewhere, by
    the same rule as `apply_linear`), `kernels.ops.qgmm` for quantized
    stacks, and `jax.lax.ragged_dot` for plain (E, K, N) stacks. Rows at
    or past offsets[E] are pad and their outputs are not used."""
    if isinstance(w, LowRankQ):
        mode = get_linear_mode()
        kernel = mode == "kernel" or (mode == "auto" and kops.on_tpu())
        return kops.lrgmm(x, w, offsets, use_kernel=kernel,
                          out_dtype=x.dtype)
    if isinstance(w, QuantizedTensor):
        return kops.qgmm(x, w, offsets, out_dtype=x.dtype)
    # bf16 operands are exact at the default precision, which the grouped
    # product's TPU kernel needs ("highest" is refused); others keep the
    # caller's precision
    prec = jax.lax.Precision.DEFAULT if x.dtype == jnp.bfloat16 else None
    return jax.lax.ragged_dot(x, w.astype(x.dtype), jnp.diff(offsets),
                              precision=prec)


def moe_routed(params, x, q_lens, cfg, token_budget: int | None = None):
    """The serving step's expert layer: x (B, W, D), row r valid in
    [:q_lens[r]] -> ((B, W, D), experts with at least one valid row).

    Only valid rows are routed: they are compacted into T slots, the
    router (float32)
    takes a softmax over the experts and a greedy top-k, and the gate
    weights follow `norm_topk_prob` and `routed_scaling_factor`. The T*k
    (row, expert) pairs are sorted by expert (pad slots to a sentinel
    past the last expert), the grouped kernel runs gate, up and down
    over each expert's pairs (`_grouped`), and every pair's output,
    weighted by its gate, is summed back into its row. Nothing is
    dropped: no capacity bounds a group. The shared experts run as one
    MLP over the compacted rows. Invalid rows come back zero.

    T is B*W, or, where the caller gives the step's token budget,
    max(budget, B) (at most B*W): the scheduler advances every decode
    row by one token whatever the budget, and prefill chunks and drafts
    take only what is left of it, so no step has more valid rows."""
    m = cfg.moe
    b, w, d = x.shape
    e, k = m.num_experts, m.top_k
    bw = b * w
    t = bw if token_budget is None else min(bw, max(token_budget, b))
    valid = (jnp.arange(w)[None, :] < q_lens[:, None]).reshape(bw)
    n = jnp.minimum(valid.sum(), t)
    dest = jnp.where(valid, jnp.cumsum(valid) - 1, t)
    src = jnp.zeros((t,), jnp.int32).at[dest].set(
        jnp.arange(bw, dtype=jnp.int32), mode="drop")
    tok_ok = jnp.arange(t) < n
    xt = x.reshape(bw, d)[src]                               # (T, D)

    with jax.named_scope("moe.route"):
        logits = jnp.matmul(xt.astype(jnp.float32),
                            params["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, k)                  # (T, k)
        gate = _gate_weights(gate, m)
        idx = jnp.where(tok_ok[:, None], idx, e).reshape(t * k)
        order = jnp.argsort(idx, stable=True)
        sizes = jnp.zeros((e + 1,), jnp.int32).at[idx].add(1)[:e]
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(sizes)]).astype(jnp.int32)
        tok = order // k                                     # pair -> row
        xs = xt[tok]                                         # (T*k, D)

    ex = params["experts"]
    hg = _grouped(xs, ex["gate"], offsets)
    hu = _grouped(xs, ex["up"], offsets)
    ys = _grouped((jax.nn.silu(hg) * hu).astype(x.dtype), ex["down"],
                  offsets)

    with jax.named_scope("moe.combine"):
        wp = gate.reshape(t * k)[order]
        ok = idx[order] < e
        contrib = jnp.where(ok[:, None], ys.astype(jnp.float32)
                            * wp[:, None], 0.0)
        yt = jnp.zeros((t, d), jnp.float32).at[tok].add(contrib)
        if "shared" in params:
            yt = yt + mlp_apply(xt, params["shared"],
                                cfg.mlp_act).astype(jnp.float32)
        yt = jnp.where(tok_ok[:, None], yt, 0.0).astype(x.dtype)
        y = jnp.zeros((bw, d), x.dtype).at[src].add(yt)
    return y.reshape(b, w, d), (sizes > 0).sum().astype(jnp.int32)

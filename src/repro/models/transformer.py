"""Decoder-only LM assembly for every assigned architecture.

Layouts
  dense  — scan over L identical (attn + MLP) blocks; local/global
           alternating archs (Gemma2) scan over *pairs* so each member of
           the pair keeps a static window;
  moe    — scan over L (attn + MoE) blocks; a model whose first
           `moe.first_dense` layers are dense (DeepSeek) scans those
           (params["dense_layers"]) first, then the MoE blocks;
  ssm    — scan over L Mamba1 blocks (attention-free);
  hybrid — Zamba2: scan over groups of `hybrid_period` Mamba2 blocks, with
           one *shared-weight* transformer block invoked after each group
           (fresh KV cache per invocation, shared parameters).

All layer stacks are scan-stacked (leading L dim) so the dry-run compiles
one body regardless of depth. `forward` returns hidden states; the loss is
sequence-chunked so (T, vocab) logits never materialize at once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mamba
from repro.models import moe as moe_mod
from repro.models.layers import (
    apply_linear, apply_norm, init_norm, mlp_apply, mlp_init, softcap,
    sinusoidal_emb,
)
from repro.runtime.shardctx import maybe_shard


# ------------------------------------------------------------------ init --
def _stack_init(fn, key, n):
    """vmap a per-layer init over n layer keys -> scan-stacked params."""
    return jax.vmap(fn)(jax.random.split(key, n))


def init_params(key, cfg):
    dtype = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    keys = jax.random.split(key, 8)
    p = {"embed": jax.random.normal(keys[0], (cfg.vocab_size, d), dtype) * 0.02,
         "final_norm": init_norm(cfg.norm, d, dtype)}
    if not cfg.tie_embeddings:
        p["lm_head"] = jax.random.normal(keys[1], (d, cfg.vocab_size),
                                         dtype) * d ** -0.5

    attn_init = attn.mla_init if cfg.is_mla else attn.attn_init

    def dense_block(k):
        ks = jax.random.split(k, 2)
        return {
            "ln1": init_norm(cfg.norm, d, dtype),
            "attn": attn_init(ks[0], cfg, dtype),
            "ln2": init_norm(cfg.norm, d, dtype),
            "mlp": mlp_init(ks[1], d, cfg.d_ff, cfg.mlp_act, dtype),
        }

    def moe_block(k):
        ks = jax.random.split(k, 2)
        return {
            "ln1": init_norm(cfg.norm, d, dtype),
            "attn": attn_init(ks[0], cfg, dtype),
            "ln2": init_norm(cfg.norm, d, dtype),
            "moe": moe_mod.moe_init(ks[1], cfg, dtype),
        }

    def mamba_block(k, version):
        init = mamba.mamba1_init if version == 1 else mamba.mamba2_init
        return {"ln": init_norm(cfg.norm, d, dtype),
                "mixer": init(k, cfg, dtype)}

    L = cfg.num_layers
    if cfg.layout == "dense":
        if cfg.local_global_period:
            assert L % 2 == 0
            p["layers"] = _stack_init(dense_block, keys[2], L)
        else:
            p["layers"] = _stack_init(dense_block, keys[2], L)
    elif cfg.layout == "moe":
        fd = cfg.first_dense
        if fd:
            p["dense_layers"] = _stack_init(dense_block, keys[4], fd)
        p["layers"] = _stack_init(moe_block, keys[2], L - fd)
    elif cfg.layout == "ssm":
        p["layers"] = _stack_init(
            functools.partial(mamba_block, version=cfg.ssm.version), keys[2], L)
    elif cfg.layout == "hybrid":
        assert L % cfg.hybrid_period == 0
        p["layers"] = _stack_init(
            functools.partial(mamba_block, version=cfg.ssm.version), keys[2], L)
        p["shared_block"] = dense_block(keys[3])
    else:
        raise ValueError(cfg.layout)
    return p


# --------------------------------------------------------------- forward --
def embed(params, inputs, cfg, pos0=0):
    """inputs: int tokens (B, S) or precomputed embeddings (B, S, D).
    pos0: absolute position of inputs[:, 0] — a scalar (rectangular
    decode passes the step) or a (B,) vector (continuous batching, where
    every row sits at its own position)."""
    dtype = jnp.dtype(cfg.dtype)
    if inputs.ndim == 3:  # modality-frontend stub: embeddings arrive directly
        h = inputs.astype(dtype)
    else:
        h = jnp.take(params["embed"], inputs, axis=0)
        if cfg.layout != "ssm":
            h = h * jnp.asarray(cfg.d_model ** 0.5, dtype)
    if cfg.pos_emb == "sinusoidal":
        pos = jnp.asarray(pos0)[..., None] + jnp.arange(h.shape[1])
        emb = sinusoidal_emb(pos, cfg.d_model, dtype)  # (S,D) or (B,S,D)
        h = h + (emb if emb.ndim == 3 else emb[None])
    return maybe_shard(h, "batch", "seq", None)


def _window_for_layer(cfg, which):
    if cfg.local_global_period:
        return cfg.local_window if which == "local" else None
    return cfg.attn_window


def _dense_body(cfg, h, lp, *, window, return_kv=False):
    hn = apply_norm(h, lp["ln1"], cfg.norm, cfg.norm_eps)
    if return_kv:
        a, kv = attn.attention(lp["attn"], hn, cfg, window=window,
                               return_kv=True)
    else:
        a = attn.attention(lp["attn"], hn, cfg, window=window)
        kv = None
    h = maybe_shard(h + a, "batch", "seq", None)
    hn = apply_norm(h, lp["ln2"], cfg.norm, cfg.norm_eps)
    if "moe" in lp:
        y, aux = moe_mod.moe_apply(lp["moe"], hn, cfg)
    else:
        y, aux = mlp_apply(hn, lp["mlp"], cfg.mlp_act), 0.0
    h = maybe_shard(h + y, "batch", "seq", None)
    return (h, aux, kv) if return_kv else (h, aux)


def _mamba_body(cfg, h, lp, *, engine, return_state=False):
    hn = apply_norm(h, lp["ln"], cfg.norm, cfg.norm_eps)
    apply = mamba.mamba1_apply if cfg.ssm.version == 1 else mamba.mamba2_apply
    y = apply(lp["mixer"], hn, cfg, engine=engine)
    return maybe_shard(h + y, "batch", "seq", None)


def _maybe_remat(cfg, fn):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        # save matmul outputs; recompute only cheap elementwise ops in the
        # backward pass — trades activation memory for ~25% less recompute
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def _layer_stacks(params) -> list:
    """The scan-stacked layer groups in order: a MoE model's leading
    dense layers ("dense_layers"), then "layers"."""
    return [params[k] for k in ("dense_layers", "layers") if k in params]


def _split_layers(tree, params):
    """A per-layer (L, ...) cache split as `_layer_stacks` groups it."""
    if "dense_layers" not in params:
        return [tree]
    fd = jax.tree_util.tree_leaves(params["dense_layers"])[0].shape[0]
    return [jax.tree_util.tree_map(lambda x: x[:fd], tree),
            jax.tree_util.tree_map(lambda x: x[fd:], tree)]


def _join_layers(parts):
    return jax.tree_util.tree_map(lambda *x: jnp.concatenate(x, 0), *parts)


def _rectangular(cfg):
    if cfg.is_mla:
        raise NotImplementedError(
            "latent attention (MLA) serves through InferenceEngine.serve "
            "only; the rectangular forward/prefill/decode paths hold "
            "per-head K and V")


def forward(params, inputs, cfg, *, ssm_engine="sequential"):
    """Returns (final hidden (B,S,D), aux_loss)."""
    _rectangular(cfg)
    h = embed(params, inputs, cfg)
    L = cfg.num_layers

    if cfg.layout in ("dense", "moe"):
        if cfg.local_global_period:
            pair = jax.tree_util.tree_map(
                lambda x: x.reshape(L // 2, 2, *x.shape[1:]), params["layers"])

            def body(carry, lp):
                h, aux = carry
                lp0 = jax.tree_util.tree_map(lambda x: x[0], lp)
                lp1 = jax.tree_util.tree_map(lambda x: x[1], lp)
                h, a0 = _dense_body(cfg, h, lp0, window=cfg.local_window)
                h, a1 = _dense_body(cfg, h, lp1, window=None)
                return (h, aux + a0 + a1), None

            (h, aux), _ = jax.lax.scan(_maybe_remat(cfg, body), (h, 0.0), pair)
        else:
            def body(carry, lp):
                h, aux = carry
                h, a = _dense_body(cfg, h, lp, window=cfg.attn_window)
                return (h, aux + a), None

            carry = (h, 0.0)
            for stack in _layer_stacks(params):
                carry, _ = jax.lax.scan(_maybe_remat(cfg, body), carry,
                                        stack)
            h, aux = carry
    elif cfg.layout == "ssm":
        def body(h, lp):
            return _mamba_body(cfg, h, lp, engine=ssm_engine), None

        h, _ = jax.lax.scan(_maybe_remat(cfg, body), h, params["layers"])
        aux = 0.0
    elif cfg.layout == "hybrid":
        p_per = cfg.hybrid_period
        groups = jax.tree_util.tree_map(
            lambda x: x.reshape(L // p_per, p_per, *x.shape[1:]),
            params["layers"])
        shared = params["shared_block"]

        def group_body(h, gp):
            def inner(h, lp):
                return _mamba_body(cfg, h, lp, engine=ssm_engine), None
            h, _ = jax.lax.scan(inner, h, gp)
            h, _ = _dense_body(cfg, h, shared, window=cfg.attn_window)
            return h, None

        h, _ = jax.lax.scan(_maybe_remat(cfg, group_body), h, groups)
        aux = 0.0
    else:
        raise ValueError(cfg.layout)

    return apply_norm(h, params["final_norm"], cfg.norm, cfg.norm_eps), aux


def lm_head_weight(params, cfg):
    return (params["embed"].T if cfg.tie_embeddings else params["lm_head"])


def logits_for(params, h, cfg):
    w = lm_head_weight(params, cfg)
    out = apply_linear(h, w, out_dtype=jnp.float32)
    return softcap(out, cfg.final_softcap)


# ------------------------------------------------------------------ loss --
def chunked_loss(params, h, labels, cfg):
    """Mean token cross-entropy, scanning over sequence chunks so the
    (B, S, V) logits tensor never exists whole."""
    b, s, d = h.shape
    c = min(cfg.loss_chunk, s)
    while s % c:
        c -= 1
    nc = s // c
    w = lm_head_weight(params, cfg)

    def body(acc, xs):
        hc, yc = xs                                   # (nc axis) (B,c,D),(B,c)
        logits = softcap(
            apply_linear(hc, w, out_dtype=jnp.float32), cfg.final_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(lse - gold), None

    hs = h.reshape(b, nc, c, d).swapaxes(0, 1)
    ys = labels.reshape(b, nc, c).swapaxes(0, 1)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ys))
    return total / (b * s)


def loss_fn(params, batch, cfg, *, aux_weight=0.01, ssm_engine="sequential"):
    inputs = batch.get("inputs_embeds", batch.get("tokens"))
    h, aux = forward(params, inputs, cfg, ssm_engine=ssm_engine)
    ce = chunked_loss(params, h, batch["labels"], cfg)
    return ce + aux_weight * aux, {"ce": ce, "aux": aux}


# ----------------------------------------------------------------- cache --
def init_cache(cfg, batch, max_len, dtype=None):
    """Decode cache pytree. Shapes are static given (cfg, batch, max_len)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    L = cfg.num_layers

    def stack(tree, n):
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n, *x.shape)), tree)

    if cfg.layout in ("dense", "moe"):
        if cfg.local_global_period:
            loc = attn.init_kv_cache(cfg, batch, max_len,
                                     window=cfg.local_window, dtype=dtype)
            glo = attn.init_kv_cache(cfg, batch, max_len, dtype=dtype)
            return {"local": stack(loc, L // 2), "global": stack(glo, L // 2)}
        kv = attn.init_kv_cache(cfg, batch, max_len, window=cfg.attn_window,
                                dtype=dtype)
        return {"kv": stack(kv, L)}
    if cfg.layout == "ssm":
        mc = (mamba.mamba1_init_cache if cfg.ssm.version == 1
              else mamba.mamba2_init_cache)(cfg, batch, dtype)
        return {"ssm": stack(mc, L)}
    if cfg.layout == "hybrid":
        g = L // cfg.hybrid_period
        mc = (mamba.mamba1_init_cache if cfg.ssm.version == 1
              else mamba.mamba2_init_cache)(cfg, batch, dtype)
        kv = attn.init_kv_cache(cfg, batch, max_len, window=cfg.attn_window,
                                dtype=dtype)
        return {"ssm": stack(mc, L), "shared_kv": stack(kv, g)}
    raise ValueError(cfg.layout)


def prefill(params, inputs, cfg, *, max_len=None, cache_dtype=None,
            ssm_engine="sequential", last_pos=None):
    """Process a prompt; return (last-position logits (B,1,V), decode cache).

    This is the `prefill_32k` serving entry point: one forward pass that
    also lays out every layer's KV / SSM state for subsequent decode.

    last_pos: optional *traced* scalar — the index whose logits to return
    (default: the final column). Length-bucketed prompts are right-padded
    to a power of two before jit, so the true last token is mid-sequence;
    passing its index as a traced value keeps the bucket's compilation
    shared across every real length inside it.
    """
    _rectangular(cfg)
    h = embed(params, inputs, cfg)
    L = cfg.num_layers
    s = h.shape[1]
    max_len = max_len or s
    cdt = cache_dtype or jnp.dtype(cfg.dtype)

    def dense_with_kv(h, lp, window):
        h2, aux, kv = _dense_body(cfg, h, lp, window=window, return_kv=True)
        kvc = attn.build_cache_from_kv(
            kv[0], kv[1], window=window, max_len=max_len, dtype=cdt,
            quantized=cfg.kv_cache_bits == 8)
        return h2, kvc

    if cfg.layout in ("dense", "moe"):
        if cfg.local_global_period:
            pair = jax.tree_util.tree_map(
                lambda x: x.reshape(L // 2, 2, *x.shape[1:]), params["layers"])

            def body(h, lp):
                lp0 = jax.tree_util.tree_map(lambda x: x[0], lp)
                lp1 = jax.tree_util.tree_map(lambda x: x[1], lp)
                h, cl = dense_with_kv(h, lp0, cfg.local_window)
                h, cg = dense_with_kv(h, lp1, None)
                return h, (cl, cg)

            h, (cl, cg) = jax.lax.scan(_maybe_remat(cfg, body), h, pair)
            cache = {"local": cl, "global": cg}
        else:
            def body(h, lp):
                return dense_with_kv(h, lp, cfg.attn_window)

            kvs = []
            for stack in _layer_stacks(params):
                h, kv = jax.lax.scan(_maybe_remat(cfg, body), h, stack)
                kvs.append(kv)
            cache = {"kv": _join_layers(kvs)}
    elif cfg.layout == "ssm":
        pre = (mamba.mamba1_prefill if cfg.ssm.version == 1
               else mamba.mamba2_prefill)

        def body(h, lp):
            hn = apply_norm(h, lp["ln"], cfg.norm, cfg.norm_eps)
            y, mc = pre(lp["mixer"], hn, cfg, engine=ssm_engine)
            return maybe_shard(h + y, "batch", "seq", None), mc

        h, mc = jax.lax.scan(_maybe_remat(cfg, body), h, params["layers"])
        cache = {"ssm": mc}
    elif cfg.layout == "hybrid":
        p_per = cfg.hybrid_period
        groups = jax.tree_util.tree_map(
            lambda x: x.reshape(L // p_per, p_per, *x.shape[1:]),
            params["layers"])
        shared = params["shared_block"]
        pre = (mamba.mamba1_prefill if cfg.ssm.version == 1
               else mamba.mamba2_prefill)

        def body(h, gp):
            def inner(h, lp):
                hn = apply_norm(h, lp["ln"], cfg.norm, cfg.norm_eps)
                y, mc = pre(lp["mixer"], hn, cfg, engine=ssm_engine)
                return maybe_shard(h + y, "batch", "seq", None), mc

            h, mcs = jax.lax.scan(inner, h, gp)
            h, kvc = dense_with_kv(h, shared, cfg.attn_window)
            return h, (mcs, kvc)

        h, (mcs, kv) = jax.lax.scan(_maybe_remat(cfg, body), h, groups)
        cache = {"ssm": jax.tree_util.tree_map(
            lambda x: x.reshape(L, *x.shape[2:]), mcs), "shared_kv": kv}
    else:
        raise ValueError(cfg.layout)

    h = apply_norm(h, params["final_norm"], cfg.norm, cfg.norm_eps)
    if last_pos is None:
        h1 = h[:, -1:]
    else:
        h1 = jax.lax.dynamic_slice_in_dim(h, last_pos, 1, axis=1)
    logits = logits_for(params, h1, cfg)
    return logits, cache


def unified_step(params, pool, block_tables, ctx_lens, q_lens, inputs, cfg,
                 verify_width: int = 0, *, token_budget: int | None = None):
    """ONE token-budget serving step over a blocked KV pool: every active
    row advances by a span of `q_lens[r]` tokens — a prefill chunk, a
    single decode token, or nothing — in a single forward pass.

    inputs: (B, W) tokens, row r valid in [:q_lens[r]]; block_tables:
    (B, MB) int32; ctx_lens: (B,) int32 tokens already in the pool per
    row (== the position of inputs[:, 0]); pool:
    runtime.kvblocks.init_paged_cache leaves (L, NB, Hk, bs, *), scanned
    over layers exactly like the monolithic cache. Returns
    (logits (B, 1, V) f32 at each row's LAST valid span position,
    updated pool, experts_hit) — the logits are exactly those that
    sample the row's next token when its span completes the prompt or
    decodes. Idle rows compute garbage the caller discards; shapes are
    static in (B, W, MB) so the one jitted step covers the whole serve
    loop regardless of admissions, evictions, or the prefill/decode mix
    (W is bucketed to a
    power of two by the driver, so at most O(log budget) shapes exist,
    and W == 1 — the decode-only steady state — is exactly the classic
    one-token paged decode). The row-major span layout keeps the KV
    reads per ROW (each row reads its block-table view once however wide
    its span is), which is what makes chunked prefill affordable at real
    model sizes.

    Attention per layer goes through `attn.span_attention_paged`, whose
    backend is cfg.paged_attn_impl: on TPU ("auto"/"kernel") the Pallas
    paged-attention kernel streams only each row's
    ceil((ctx+q)/block_size) valid blocks — O(ctx) HBM bytes per step —
    and dequantizes int8 KV in VMEM; "ref" (the CPU default) runs the
    jnp gather oracle the kernel is identity-tested against.

    verify_width > 0 is the multi-token speculative-verify mode
    (runtime/speculation.py): logits come back for span positions
    0..verify_width-1 PLUS each row's last-valid position appended —
    shape (B, verify_width + 1, V) — so one step both verifies a k-token
    draft span (positions 0..k-1 predict tokens 1..k) and still yields
    the last-position logits prefill-finishing rows sample from. The lm
    head runs on verify_width + 1 positions regardless of W, so wide
    prefill chunks pay nothing extra. verify_width must be <= W.

    Tensor parallelism: the step is shard_map-compatible. When
    api.engine wraps it with `shardctx.tp_axis("model")` bound, `cfg`
    is the PER-SHARD config (num_heads/num_kv_heads divided by the mesh
    model axis), params arrive column/row-sliced per
    launch.sharding._TP_RULES, and the pool arrives head-sliced
    (kvblocks.pool_pspecs). Attention and MLP then compute partial
    results over local heads / hidden columns, and exactly one
    `shardctx.psum_tp` fires per attention/MLP boundary — inside the wo
    and down projections (`apply_linear(..., reduce_tp=True)`), which
    reduce their f32 partials BEFORE the single cast to the residual
    dtype, keeping bf16 TP bit-identical to the unsharded step. 2L
    psums per step, the only collectives. With no TP axis bound the
    reduce_tp flag is inert and this is the single-device step
    unchanged.

    Latent attention (`cfg.is_mla`) runs `attn.mla_span_attention_paged`
    over the latent pool. MoE layers route through `moe.moe_routed`:
    valid rows only (from q_lens), dropless, compacted into
    `token_budget` slots where the caller knows the step's budget.
    A model whose first layers are dense scans them first, over its own
    group of the pool (`kvblocks.init_paged_cache`).
    The third output is the number of experts that got at least one
    valid row, summed over the MoE layers (int32 scalar; 0 without MoE).
    """
    from repro.runtime.kvblocks import check_paged_support

    check_paged_support(cfg)
    h = embed(params, inputs, cfg, pos0=ctx_lens)
    span_attn = (attn.mla_span_attention_paged if cfg.is_mla
                 else attn.span_attention_paged)

    def body(carry, xs):
        h, hits = carry
        lp, pl = xs
        hn = apply_norm(h, lp["ln1"], cfg.norm, cfg.norm_eps)
        a, pl = span_attn(lp["attn"], hn, pl, block_tables, ctx_lens,
                          q_lens, cfg)
        h = h + a
        hn = apply_norm(h, lp["ln2"], cfg.norm, cfg.norm_eps)
        if "moe" in lp:
            y, hit = moe_mod.moe_routed(lp["moe"], hn, q_lens, cfg,
                                        token_budget)
            hits = hits + hit
        else:
            y = mlp_apply(hn, lp["mlp"], cfg.mlp_act)
        h = h + y
        return (h, hits), pl

    carry = (h, jnp.zeros((), jnp.int32))
    if "dense_layers" in params:
        carry, pool0 = jax.lax.scan(body, carry, (params["dense_layers"],
                                                  pool["dense_layers"]))
        carry, pool1 = jax.lax.scan(body, carry, (params["layers"],
                                                  pool["layers"]))
        pool = {"dense_layers": pool0, "layers": pool1}
    else:
        carry, pool = jax.lax.scan(body, carry, (params["layers"], pool))
    h, hits = carry
    h = apply_norm(h, params["final_norm"], cfg.norm, cfg.norm_eps)
    last = jnp.maximum(q_lens - 1, 0)[:, None, None]      # (B, 1, 1)
    h1 = jnp.take_along_axis(h, jnp.broadcast_to(
        last, (h.shape[0], 1, h.shape[2])), axis=1)       # (B, 1, D)
    if verify_width:
        if verify_width > h.shape[1]:
            raise ValueError(f"verify_width {verify_width} exceeds span "
                             f"width {h.shape[1]}")
        h1 = jnp.concatenate([h[:, :verify_width], h1], axis=1)
    return logits_for(params, h1, cfg), pool, hits


def serve_step(params, pool, block_tables, step_buf, prev, recent,
               stop_seqs, cfg, *, sample: bool = False, stop: bool = False,
               token_budget: int | None = None):
    """One fused serving dispatch: `unified_step` plus the logits→token
    path (sampling) and device stop evaluation, all in one jit.

    step_buf: (B, W + 3 + runtime.sampling.SAMP_COLS) int32 — the
    host-built span tokens (B, W), three scheduling columns (ctx_lens,
    q_lens, use_prev), then the packed per-row sampling/stop metadata
    (see runtime/sampling.py), so the hot loop still uploads ONE array
    per step. Decode rows' first token column is spliced from `prev`
    (the previous step's device-resident sampled tokens) so token
    values never round-trip through the host. `recent` is the per-row
    ring of the last S emitted tokens (device-resident, carried across
    steps like `prev`); `stop_seqs` is the (B, NS, S) right-aligned
    stop-sequence buffer (refreshed on admission, like block tables).

    `sample` / `stop` are STATIC: the engine traces one variant per
    (any-row-samples, any-stop-criteria) pair for a serve call, so an
    all-greedy, no-stop serve runs a program with no sort, no PRNG, and
    no ring update — exactly the previous greedy step. Within a sampled
    variant, rows with temperature <= 0 still take the raw-logits
    argmax (bit-identical to greedy; see sampling.sample_tokens).

    Returns (toks (B, 1) int32, finished (B,) int32, recent, pool).
    `finished` flags rows whose emission this step completed the
    request (eos / stop sequence / max_tokens); the engine reads it off
    the already-pipelined readback — no extra host sync. A MoE model's
    toks are (B, `step_token_cols`) = (B, 2): column 1 carries the
    step's `experts_hit` (every row the same), read back with the tokens
    at no extra sync.
    token_budget: the step's static token budget (MoE routing slots).
    """
    from repro.runtime import sampling as smp

    meta = step_buf[:, -(3 + smp.SAMP_COLS):]
    tokens = step_buf[:, :-(3 + smp.SAMP_COLS)]
    ctx_lens, q_lens, use_prev = meta[:, 0], meta[:, 1], meta[:, 2]
    tokens = tokens.at[:, 0].set(
        jnp.where(use_prev.astype(bool), prev[:, 0], tokens[:, 0]))
    logits, pool, hits = unified_step(params, pool, block_tables, ctx_lens,
                                      q_lens, tokens, cfg,
                                      token_budget=token_budget)
    last = logits[:, -1]
    if sample:
        sp = smp.unpack_meta(step_buf)
        keys = smp.row_keys(sp["seed"], sp["rid"], sp["counter"])
        toks = smp.sample_tokens(last, sp["temperature"], sp["top_k"],
                                 sp["top_p"], keys)[:, None]
    else:
        toks = jnp.argmax(last, axis=-1)[:, None].astype(jnp.int32)
    if stop:
        sp = smp.unpack_meta(step_buf)
        recent = smp.push_recent(recent, toks)
        fin = smp.finished_mask(toks[:, 0], recent, sp, stop_seqs)
    else:
        fin = jnp.zeros((toks.shape[0],), jnp.int32)
    if step_token_cols(cfg) > 1:
        toks = jnp.concatenate(
            [toks, jnp.broadcast_to(hits, toks.shape)], axis=1)
    return toks, fin, recent, pool


def step_budget(cfg, token_budget: int | None) -> int | None:
    """The static token budget `serve_step` is compiled for: the call's
    budget where MoE layers size their routing slots from it, else None
    (a dense step does not read it)."""
    return token_budget if cfg.layout == "moe" else None


def step_token_cols(cfg) -> int:
    """Columns of `serve_step`'s tokens: the sampled token, then, for a
    model with MoE layers, the step's `experts_hit`."""
    return 2 if cfg.layout == "moe" else 1


def decode_step(params, cache, inputs, pos, cfg):
    """One decode step. inputs: (B, 1) tokens or (B, 1, D) embeds.
    Returns (logits (B, 1, V) f32, new cache)."""
    _rectangular(cfg)
    h = embed(params, inputs, cfg, pos0=pos)
    L = cfg.num_layers

    def dense_step(h, lp, kvc, window):
        hn = apply_norm(h, lp["ln1"], cfg.norm, cfg.norm_eps)
        a, kvc = attn.decode_attention(lp["attn"], hn, kvc, pos, cfg,
                                       window=window)
        h = h + a
        hn = apply_norm(h, lp["ln2"], cfg.norm, cfg.norm_eps)
        if "moe" in lp:
            y, _ = moe_mod.moe_apply(lp["moe"], hn, cfg)
        else:
            y = mlp_apply(hn, lp["mlp"], cfg.mlp_act)
        return h + y, kvc

    def mamba_step(h, lp, mc):
        hn = apply_norm(h, lp["ln"], cfg.norm, cfg.norm_eps)
        step = mamba.mamba1_step if cfg.ssm.version == 1 else mamba.mamba2_step
        y, mc = step(lp["mixer"], hn, mc, cfg)
        return h + y, mc

    if cfg.layout in ("dense", "moe"):
        if cfg.local_global_period:
            pair = jax.tree_util.tree_map(
                lambda x: x.reshape(L // 2, 2, *x.shape[1:]), params["layers"])

            def body(h, xs):
                lp, cl, cg = xs
                lp0 = jax.tree_util.tree_map(lambda x: x[0], lp)
                lp1 = jax.tree_util.tree_map(lambda x: x[1], lp)
                h, cl = dense_step(h, lp0, cl, cfg.local_window)
                h, cg = dense_step(h, lp1, cg, None)
                return h, (cl, cg)

            h, (cl, cg) = jax.lax.scan(body, h,
                                       (pair, cache["local"], cache["global"]))
            cache = {"local": cl, "global": cg}
        else:
            def body(h, xs):
                lp, kvc = xs
                h, kvc = dense_step(h, lp, kvc, cfg.attn_window)
                return h, kvc

            kvs = []
            for stack, kvc in zip(_layer_stacks(params),
                                  _split_layers(cache["kv"], params)):
                h, kv = jax.lax.scan(body, h, (stack, kvc))
                kvs.append(kv)
            cache = {"kv": _join_layers(kvs)}
    elif cfg.layout == "ssm":
        def body(h, xs):
            lp, mc = xs
            h, mc = mamba_step(h, lp, mc)
            return h, mc

        h, mc = jax.lax.scan(body, h, (params["layers"], cache["ssm"]))
        cache = {"ssm": mc}
    elif cfg.layout == "hybrid":
        p_per = cfg.hybrid_period
        groups = jax.tree_util.tree_map(
            lambda x: x.reshape(L // p_per, p_per, *x.shape[1:]),
            params["layers"])
        ssm_groups = jax.tree_util.tree_map(
            lambda x: x.reshape(L // p_per, p_per, *x.shape[1:]), cache["ssm"])
        shared = params["shared_block"]

        def body(h, xs):
            gp, mcs, kvc = xs

            def inner(h, ys):
                lp, mc = ys
                h, mc = mamba_step(h, lp, mc)
                return h, mc

            h, mcs = jax.lax.scan(inner, h, (gp, mcs))
            h, kvc = dense_step(h, shared, kvc, cfg.attn_window)
            return h, (mcs, kvc)

        h, (mcs, kv) = jax.lax.scan(body, h,
                                    (groups, ssm_groups, cache["shared_kv"]))
        cache = {"ssm": jax.tree_util.tree_map(
            lambda x: x.reshape(L, *x.shape[2:]), mcs), "shared_kv": kv}
    else:
        raise ValueError(cfg.layout)

    h = apply_norm(h, params["final_norm"], cfg.norm, cfg.norm_eps)
    return logits_for(params, h, cfg), cache

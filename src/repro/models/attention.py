"""Attention for the whole zoo: GQA + RoPE, sliding windows, local/global
alternation, logit soft-capping, chunked (flash-style) prefill, and decode
with (optionally rolling, optionally int8-quantized) KV caches.

Implementations:
  * "full"    — plain masked einsum; right choice for short sequences.
  * "chunked" — python-unrolled q-block loop; each q block attends only to
    the kv prefix (or window) it can actually see, so the compiled FLOPs are
    triangular (≈S²/2) instead of rectangular (S²). This is the pure-JAX
    flash-attention analog used by the 32k prefill dry-run cells.

Serving attention over the blocked KV pool (`span_attention_paged`) has
its own backend pair selected by `cfg.paged_attn_impl`: the Pallas
paged-attention kernel (`kernels/paged_attention.py` — streams only
valid blocks, dequantizes int8 KV in VMEM) and the jnp gather oracle
(`_span_attend_gather`) it is identity-tested against.

GQA: KV is stored at num_kv_heads and broadcast to the query heads at
compute time (group-repeat), so cache memory stays at Hk while the einsum
runs at H. Head axes shard over the "model" mesh axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models.layers import (
    apply_linear, apply_rope, rmsnorm, rotate_pairs, softcap, yarn_inv_freq,
    yarn_mscale,
)

NEG = -2.3819763e38  # large negative for masking in f32


def attn_init(key, cfg, dtype):
    d, h, hk, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    return {
        "wq": jax.random.normal(ks[0], (d, h * hd), dtype) * s,
        "wk": jax.random.normal(ks[1], (d, hk * hd), dtype) * s,
        "wv": jax.random.normal(ks[2], (d, hk * hd), dtype) * s,
        "wo": jax.random.normal(ks[3], (h * hd, d), dtype) * (h * hd) ** -0.5,
    }


def mla_init(key, cfg, dtype):
    """Multi-head latent attention (DeepSeek-V2, `q_lora_rank` null):
    wq (d, H*(dn+dr)); wkv_a (d, C+dr) -> the latent c (C, then
    `kv_norm`) and one rope key shared by every head; per-head
    w_uk (H, dn, C) and w_uv (H, C, dv), the two halves of kv_b_proj,
    kept as plain arrays for absorption; wo (H*dv, d)."""
    d, h = cfg.d_model, cfg.num_heads
    dn, dr, c, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.kv_lora_rank, cfg.v_head_dim)
    ks = jax.random.split(key, 5)
    s = d ** -0.5
    return {
        "wq": jax.random.normal(ks[0], (d, h * (dn + dr)), dtype) * s,
        "wkv_a": jax.random.normal(ks[1], (d, c + dr), dtype) * s,
        "kv_norm": {"gamma": jnp.zeros((c,), dtype)},
        "w_uk": jax.random.normal(ks[2], (h, dn, c), dtype) * c ** -0.5,
        "w_uv": jax.random.normal(ks[3], (h, c, dv), dtype) * c ** -0.5,
        "wo": jax.random.normal(ks[4], (h * dv, d), dtype) * (h * dv) ** -0.5,
    }


def mla_rope_freqs(cfg):
    """Rotary frequencies of the rope dims: YaRN where configured."""
    dr = cfg.qk_rope_head_dim
    if cfg.rope_scaling is not None:
        return yarn_inv_freq(dr, cfg.rope_theta, cfg.rope_scaling)
    return 1.0 / (cfg.rope_theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32)
                                     / dr))


def mla_softmax_scale(cfg) -> float:
    """(dn + dr)^-0.5, times YaRN's mscale(mscale_all_dim)^2 where
    configured (DeepSeek-V2-Lite: 192^-0.5 * 1.26080^2 = 0.114722)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def _group_q(q, hk):
    """(B, S, H, Dh) -> (B, S, Hk, G, Dh): group q heads by kv head.

    GQA runs *grouped* — K/V are never repeated to H heads, so cache-sized
    tensors never blow up by the group factor (critical for the 32k/500k
    decode cells)."""
    b, s, h, d = q.shape
    return q.reshape(b, s, hk, h // hk, d)


def _scores(q, k, cap):
    """q: (B, Sq, Hk, G, Dh); k: (B, Sk, Hk, Dh) -> (B, Hk, G, Sq, Sk)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s * (q.shape[-1] ** -0.5)
    return softcap(s, cap)


def _attend_block(q, k, v, mask, cap):
    """q grouped (B,Sq,Hk,G,Dh); k/v (B,Sk,Hk,Dh); mask (...,Sq,Sk)."""
    s = jnp.where(mask, _scores(q, k, cap), NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    b, sq, hk, g, d = o.shape
    return o.reshape(b, sq, hk * g, d)


def _causal_mask(q_pos, k_pos, window):
    m = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    return m


def attention(params, x, cfg, *, window=None, positions=None,
              return_kv=False):
    """Causal self-attention for training / prefill. x: (B, S, D).

    return_kv=True additionally returns the (pre-expansion, post-RoPE)
    (k, v) pair at Hk heads — prefill uses it to populate the decode
    cache. In that mode, when `cfg.kv_cache_bits == 8`, attention runs
    over the *fake-quantized* K/V (dequantize(quantize(k))) — exactly the
    values decode will later read back from the int8 cache — so prefill
    logits agree bit-for-bit with chunked prefill through the paged pool
    (`span_attention_paged`), which stores each chunk quantized before
    the next chunk attends to it. The returned (k, v) stay full
    precision; `build_cache_from_kv` quantizes them once, yielding the
    identical codes and scales.
    """
    b, s, _ = x.shape
    h, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if positions is None:
        positions = jnp.arange(s)

    q = apply_linear(x, params["wq"]).reshape(b, s, h, hd)
    k = apply_linear(x, params["wk"]).reshape(b, s, hk, hd)
    v = apply_linear(x, params["wv"]).reshape(b, s, hk, hd)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_pct)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rotary_pct)
    kv = (k, v)
    if return_kv and getattr(cfg, "kv_cache_bits", 16) == 8:
        k, v = _fake_quant_kv(k), _fake_quant_kv(v)
    qg = _group_q(q, hk)

    impl = cfg.attn_impl
    if impl == "auto":
        impl = "chunked" if s > 2048 else "full"

    if impl == "full":
        mask = _causal_mask(positions, positions, window)[None, None, None]
        o = _attend_block(qg, k, v, mask, cfg.logit_softcap)
    else:
        o = _chunked_causal(qg, k, v, positions, window, cfg)
    y = apply_linear(o.reshape(b, s, h * hd), params["wo"])
    return (y, kv) if return_kv else y


def _chunked_causal(q, k, v, positions, window, cfg):
    """Flash-style q-block loop with static (python) block skipping.

    For q block i only kv blocks [lo_i, i] are materialized, where lo_i is 0
    (causal) or the first block inside the sliding window — compiled FLOPs
    are triangular / banded, not rectangular.

    q is grouped (B, S, Hk, G, Dh); k/v stay at (B, S, Hk, Dh).
    """
    b, s = q.shape[:2]
    c = min(cfg.attn_chunk, s)
    nb = (s + c - 1) // c
    outs = []
    for i in range(nb):
        q_sl = slice(i * c, min((i + 1) * c, s))
        lo = 0
        if window is not None:
            lo = max(0, (i * c - window) // c)
        k_sl = slice(lo * c, min((i + 1) * c, s))
        mask = _causal_mask(positions[q_sl], positions[k_sl],
                            window)[None, None, None]
        outs.append(
            _attend_block(q[:, q_sl], k[:, k_sl], v[:, k_sl], mask,
                          cfg.logit_softcap)
        )
    return jnp.concatenate(outs, axis=1)


# ------------------------------------------------------------------ cache --
def build_cache_from_kv(k, v, *, window=None, max_len=None, dtype=None,
                        quantized=False):
    """Lay prefill (k, v) (B, S, Hk, Dh) out as a decode cache.

    Non-rolling: slot i holds position i (cache sized max_len >= S).
    Rolling (window w): the last w positions land at slot p % w, matching
    decode_attention's rolling write. quantized=True stores int8 codes +
    per-(token, head) scales (cfg.kv_cache_bits == 8).
    """
    b, s, hk, hd = k.shape
    dtype = dtype or k.dtype
    parts = {"k": k, "v": v}
    if quantized:
        kq, ks = _quant_kv(k)
        vq, vs = _quant_kv(v)
        parts = {"k": kq, "v": vq, "ks": ks, "vs": vs}

    def layout(x, fill_dtype):
        if window:
            size = min(window, max_len or s)
            take = min(size, s)
            tail = x[:, -take:]
            slots = ((s - take) + jnp.arange(take)) % size
            init = (jnp.ones if x.shape[-1] == 1 else jnp.zeros)(
                (b, size, hk, x.shape[-1]), fill_dtype)
            return init.at[:, slots].set(tail.astype(fill_dtype))
        size = max_len or s
        pad = size - s
        out = jnp.pad(x.astype(fill_dtype),
                      ((0, 0), (0, pad), (0, 0), (0, 0)),
                      constant_values=1 if x.shape[-1] == 1 else 0)
        return out

    if quantized:
        return {
            "k": layout(parts["k"], jnp.int8),
            "v": layout(parts["v"], jnp.int8),
            "ks": layout(parts["ks"], jnp.float32),
            "vs": layout(parts["vs"], jnp.float32),
        }
    return {"k": layout(parts["k"], dtype), "v": layout(parts["v"], dtype)}


def init_kv_cache(cfg, batch, max_len, *, window=None, dtype=None):
    """Cache for one attention site. Rolling when a window bounds it.

    cfg.kv_cache_bits == 8 stores int8 codes + per-(token, head) fp scales
    (~2x less HBM traffic per decode step — §Perf)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    size = min(window, max_len) if window else max_len
    hk, hd = cfg.num_kv_heads, cfg.head_dim
    if getattr(cfg, "kv_cache_bits", 16) == 8:
        return {
            "k": jnp.zeros((batch, size, hk, hd), jnp.int8),
            "v": jnp.zeros((batch, size, hk, hd), jnp.int8),
            "ks": jnp.ones((batch, size, hk, 1), jnp.float32),
            "vs": jnp.ones((batch, size, hk, 1), jnp.float32),
        }
    return {
        "k": jnp.zeros((batch, size, hk, hd), dtype),
        "v": jnp.zeros((batch, size, hk, hd), dtype),
    }


def _quant_kv(x):
    """Per-(token, head) symmetric int8 quantization of K/V rows."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127,
                 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _fake_quant_kv(x):
    """quantize->dequantize round trip: the values an int8 KV cache will
    hand back at decode time, in x's dtype."""
    q, scale = _quant_kv(x)
    return (q.astype(jnp.float32) * scale).astype(x.dtype)


def _paged_impl(cfg) -> str:
    """Resolve cfg.paged_attn_impl: "auto" follows the matmul-kernel
    dispatch rule (`kernels.ops.on_tpu`) — compiled Pallas on TPU, the
    jnp gather oracle on CPU (interpret-mode Pallas inside the big jitted
    serving step would bloat the HLO; the oracle is the numerics
    reference either way)."""
    impl = getattr(cfg, "paged_attn_impl", "auto")
    if impl == "auto":
        return "kernel" if kops.on_tpu() else "ref"
    if impl not in ("kernel", "ref"):
        raise ValueError(f"paged_attn_impl must be auto|kernel|ref, "
                         f"got {impl!r}")
    return impl


def _span_attend_gather(q, pool, block_table, pos, cfg):
    """The jnp oracle: gather the FULL logical pool view
    block_table -> (B, MB*bs, Hk, Dh) (dequantized whole in jnp when the
    pool is int8) and run one masked softmax over it. O(MB*bs) HBM bytes
    and a dense materialization regardless of ctx_lens — the cost the
    Pallas kernel exists to delete; kept as the selectable reference."""
    b, w = q.shape[:2]
    hk, bs = pool["k"].shape[1:3]
    mb = block_table.shape[1]

    def view(leaf):
        # (NB, Hk, bs, *) -> this row's logical (B, MB*bs, Hk, *) view
        g = leaf[block_table].transpose(0, 1, 3, 2, 4)
        return g.reshape(b, mb * bs, hk, leaf.shape[-1]).astype(q.dtype)

    ck, cv = view(pool["k"]), view(pool["v"])
    if "ks" in pool:
        ck, cv = ck * view(pool["ks"]), cv * view(pool["vs"])

    # (B, W, S): query (r, i) sees slots at positions <= ctx_lens[r] + i
    valid = jnp.arange(mb * bs)[None, None, :] <= pos[:, :, None]
    qg = _group_q(q, hk)                                  # (B,W,Hk,G,Dh)
    s = _scores(qg, ck, cfg.logit_softcap)                # (B,Hk,G,W,S)
    s = jnp.where(valid[:, None, None, :, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(cv.dtype), cv)
    return o.reshape(b, w, cfg.num_heads, cfg.head_dim)


def span_attention_paged(params, x, pool, block_table, ctx_lens, q_lens,
                         cfg, *, impl=None):
    """Variable-width query spans against a blocked (paged) KV pool — the
    serving primitive behind `transformer.unified_step`, generalizing
    one-token-per-row paged decode to each row advancing by a span of
    `q_lens[r]` new tokens: a prefill chunk, a single decode token
    (q_lens == 1), or nothing (q_lens == 0, idle/pad row). The unified
    step packs its token budget flat — one buffer row per TOKEN, a
    span's rows repeating their sequence's block table with increasing
    positions and width 1 — so the same math serves both layouts.

    x: (B, W, D) hidden, row r valid in [:q_lens[r]]; pool: ONE layer's
    blocks {"k","v"[,"ks","vs"]} with leaves (NB, Hk, bs, *);
    block_table: (B, MB) int32 physical block ids in logical order,
    padded with the reserved trash block 0; ctx_lens: (B,) int32 tokens
    already in the pool per row == the absolute position of x[:, 0]
    (per-row RoPE / mask).

    Span token (r, i) sits at position p = ctx_lens[r] + i. Its K/V is
    scattered to (block_table[r, p // bs], :, p % bs) *first*, then
    attention runs over the row's block-table view under the causal mask
    `slot <= p` — so queries see the pool prefix AND the earlier tokens
    of their own span, however the span is laid out (in-step causality
    falls out of write-then-attend; different sequences can never see
    each other — they read through disjoint block tables). Pad slots and
    idle rows write into trash block 0 and read garbage the caller
    discards — no control flow inside the jitted step, static in
    (B, W, MB).

    impl: None -> cfg.paged_attn_impl (see `_paged_impl`). "kernel" runs
    the Pallas paged-attention kernel (`kernels.paged_attention`):
    streams ONLY the ceil((ctx+q)/bs) valid blocks per row and
    dequantizes int8 K/V tiles in VMEM. "ref" runs the jnp gather oracle
    (`_span_attend_gather`): materializes the full (B, MB*bs, Hk, Dh)
    logical view — the numerics reference the kernel is tested against.
    """
    from repro.runtime.kvblocks import span_slots

    b, w, _ = x.shape
    h, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    bs = pool["k"].shape[2]

    q = apply_linear(x, params["wq"]).reshape(b, w, h, hd)
    k = apply_linear(x, params["wk"]).reshape(b, w, hk, hd)
    v = apply_linear(x, params["wv"]).reshape(b, w, hk, hd)
    pos = ctx_lens[:, None] + jnp.arange(w)[None, :]            # (B, W)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, pos, cfg.rope_theta, cfg.rotary_pct)
        k = apply_rope(k, pos, cfg.rope_theta, cfg.rotary_pct)

    blk, off = span_slots(block_table, ctx_lens, q_lens, w, bs)  # (B, W)
    # (B, W, Hk, *) rows land at pool[blk, :, off]: the advanced indices
    # straddle the head axis, so their (B, W) dims lead the update
    if "ks" in pool:
        kq, ks1 = _quant_kv(k)
        vq, vs1 = _quant_kv(v)
        new = {"k": kq, "v": vq, "ks": ks1, "vs": vs1}
    else:
        new = {"k": k, "v": v}
    pool = {key: pool[key].at[blk, :, off].set(val.astype(pool[key].dtype))
            for key, val in new.items()}

    impl = impl or _paged_impl(cfg)
    if impl == "kernel":
        from repro.kernels.paged_attention import paged_attention

        o = paged_attention(q, pool, block_table, ctx_lens, q_lens,
                            logit_softcap=cfg.logit_softcap,
                            interpret=not kops.on_tpu())
    else:
        o = _span_attend_gather(q, pool, block_table, pos, cfg)
    y = apply_linear(o.reshape(b, w, h * hd), params["wo"], reduce_tp=True)
    return y, pool


def decode_attention(params, x1, cache, pos, cfg, *, window=None):
    """One-token decode. x1: (B, 1, D); pos: scalar int32 current position.

    Returns (y (B,1,D), updated cache). The cache is rolling (mod window)
    when `window` is set, so SWA archs decode 500k-token contexts with an
    O(window) cache.
    """
    b = x1.shape[0]
    h, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    size = cache["k"].shape[1]

    q = apply_linear(x1, params["wq"]).reshape(b, 1, h, hd)
    k = apply_linear(x1, params["wk"]).reshape(b, 1, hk, hd)
    v = apply_linear(x1, params["wv"]).reshape(b, 1, hk, hd)
    if cfg.pos_emb == "rope":
        p1 = jnp.full((1,), pos, jnp.int32)
        q = apply_rope(q, p1, cfg.rope_theta, cfg.rotary_pct)
        k = apply_rope(k, p1, cfg.rope_theta, cfg.rotary_pct)

    slot = jnp.mod(pos, size) if window else jnp.minimum(pos, size - 1)
    quant = "ks" in cache
    if quant:
        kq, ks1 = _quant_kv(k)
        vq, vs1 = _quant_kv(v)
        cache = {
            "k": jax.lax.dynamic_update_slice(cache["k"], kq,
                                              (0, slot, 0, 0)),
            "v": jax.lax.dynamic_update_slice(cache["v"], vq,
                                              (0, slot, 0, 0)),
            "ks": jax.lax.dynamic_update_slice(cache["ks"], ks1,
                                               (0, slot, 0, 0)),
            "vs": jax.lax.dynamic_update_slice(cache["vs"], vs1,
                                               (0, slot, 0, 0)),
        }
        ck = cache["k"].astype(q.dtype) * cache["ks"].astype(q.dtype)
        cv = cache["v"].astype(q.dtype) * cache["vs"].astype(q.dtype)
    else:
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))

    # positions held in each physical slot (rolling-aware)
    idx = jnp.arange(size)
    if window:
        n_wraps = (pos + 1 + size - 1) // size
        slot_pos = jnp.where(idx <= slot, idx + (n_wraps - 1) * size,
                             idx + (n_wraps - 2) * size)
        valid = (slot_pos >= 0) & (slot_pos <= pos) & (slot_pos > pos - size)
    else:
        slot_pos = idx
        valid = idx <= jnp.minimum(pos, size - 1)

    qg = _group_q(q, hk)                               # (B,1,Hk,G,Dh)
    kx = ck.astype(q.dtype)
    vx = cv.astype(q.dtype)
    s = _scores(qg, kx, cfg.logit_softcap)             # (B,Hk,G,1,size)
    s = jnp.where(valid[None, None, None, None, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(vx.dtype), vx)
    y = apply_linear(o.reshape(b, 1, h * hd), params["wo"])
    if quant:
        return y, cache
    return y, {"k": ck, "v": cv}


def _mla_attend_gather(q, latent, block_table, pos, scale, v_width):
    """The jnp oracle of the latent mode: gather each row's whole logical
    latent view (B, MB*bs, Dp), score every head's absorbed query against
    it under the causal mask, and take the first `v_width` columns as V."""
    b, w = q.shape[:2]
    bs = latent.shape[2]
    mb = block_table.shape[1]
    view = latent[block_table][:, :, 0].reshape(b, mb * bs, -1)
    view = view.astype(q.dtype)
    valid = jnp.arange(mb * bs)[None, None, :] <= pos[:, :, None]
    s = jnp.einsum("bwhd,bkd->bhwk", q, view,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhwk,bkc->bwhc", p.astype(view.dtype),
                      view[..., :v_width])


def mla_span_attention_paged(params, x, pool, block_table, ctx_lens, q_lens,
                             cfg, *, impl=None):
    """`span_attention_paged` for multi-head latent attention, in absorbed
    form: one form serves prefill chunks and decode alike.

    Each position caches one latent entry [norm(c), rope(k_pe)]
    (C + dr wide, zero-padded to the pool leaf's lane width) in
    pool["latent"] (NB, 1, bs, Dp) — no per-head K or V. Head h's query
    is [q_nope_h @ W_UK_h^T, rope(q_pe_h)], so its score against a
    position is the dot with that position's entry (every head shares
    the one latent "head", G = H); its output is the softmax-weighted
    sum of the entries' first C columns (the latent itself is V), times
    W_UV_h, then wo. Scale: `mla_softmax_scale`. Rotations act on the
    interleaved pairs (2i, 2i+1) of the rope dims, which is DeepSeek's
    de-interleave-then-rotate-half up to the order of dims in the dot.
    """
    from repro.runtime.kvblocks import span_slots

    b, w, _ = x.shape
    h = cfg.num_heads
    dn, dr, c = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    latent = pool["latent"]
    bs, dp = latent.shape[2], latent.shape[3]

    q = apply_linear(x, params["wq"]).reshape(b, w, h, dn + dr)
    kv = apply_linear(x, params["wkv_a"])                     # (B, W, C+dr)
    ckv = rmsnorm(kv[..., :c], params["kv_norm"]["gamma"], cfg.norm_eps)
    pos = ctx_lens[:, None] + jnp.arange(w)[None, :]            # (B, W)
    inv = mla_rope_freqs(cfg)
    q_pe = rotate_pairs(q[..., dn:], pos, inv)
    k_pe = rotate_pairs(kv[..., None, c:], pos, inv)[:, :, 0]
    with jax.named_scope("mla.absorb"):
        q_lat = jnp.einsum("bwhn,hnc->bwhc", q[..., :dn],
                           params["w_uk"].astype(q.dtype))
    pad = dp - (c + dr)
    qf = jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype),
                          jnp.zeros((b, w, h, pad), q_lat.dtype)], -1)
    entry = jnp.concatenate([ckv, k_pe.astype(ckv.dtype),
                             jnp.zeros((b, w, pad), ckv.dtype)], -1)

    blk, off = span_slots(block_table, ctx_lens, q_lens, w, bs)
    latent = latent.at[blk, 0, off].set(entry.astype(latent.dtype))
    scale = mla_softmax_scale(cfg)
    impl = impl or _paged_impl(cfg)
    if impl == "kernel":
        from repro.kernels.paged_attention import latent_paged_attention

        o = latent_paged_attention(qf, latent, block_table, ctx_lens,
                                   q_lens, scale=scale, v_width=c,
                                   interpret=not kops.on_tpu())
    else:
        o = _mla_attend_gather(qf, latent, block_table, pos, scale, c)
    with jax.named_scope("mla.absorb"):
        o = jnp.einsum("bwhc,hcv->bwhv", o.astype(q.dtype),
                       params["w_uv"].astype(q.dtype))
    y = apply_linear(o.reshape(b, w, h * cfg.v_head_dim), params["wo"],
                     reduce_tp=True)
    return y, {"latent": latent}

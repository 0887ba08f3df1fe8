"""Plain reference of the served model, in float32 `jax.numpy`.

A decoder-only pre-norm transformer as the configuration files describe
it: token embedding scaled by sqrt(d_model) (rounded to the model dtype),
sinusoidal or interleaved-pair rotary positions, causal grouped-query
attention, a SwiGLU or tanh-GELU MLP, RMSNorm (stored as gamma - 1) or
LayerNorm, and an untied output head. Every linear is the uniform ITERA
plan's math: the input row quantized to `act_wl` bits (one scale per
row), an exact integer product with the W1 codes, the per-rank scales
folded in, the intermediate requantized per row, an exact integer product
with the W2 codes.

It imports nothing of the program and takes nothing the program made: the
weights are regenerated from the seed by `synth`, one layer at a time,
and a whole sequence (prompt plus served tokens) runs at once with no
cache, no paging and no batching across requests. Float work runs at
"highest" matmul precision. Integer products run on bf16 operands with
f32 accumulation, which is exact for codes of at most 8 bits at these
widths (|sum| < 2**24).

`lower` names a float type that every activation is rounded to between
operations: the control computes the same model one precision step down.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

import synth

HI = jax.lax.Precision.HIGHEST


def _rowquant(x, qm):
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = jnp.where(absmax > 0, absmax / qm, 1.0)
    return jnp.clip(jnp.round(x / s), -qm, qm), s


def _int_dot(a, b):
    return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def _codes(v, packed):
    return synth.unpack(v) if packed else v


def itera_linear(x, arrays, shp, act_wl):
    v1, s1, v2, s2 = arrays
    qm = 2 ** (act_wl - 1) - 1
    xq, sx = _rowquant(x, qm)
    t = _int_dot(xq, _codes(v1, shp["w1_packed"])) * sx * s1 * s2.reshape(
        1, -1)
    tq, st = _rowquant(t, qm)
    return _int_dot(tq, _codes(v2, shp["w2_packed"])) * st


def _norm(x, p, cfg):
    eps = cfg["norm_eps"]
    if cfg["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return ((x - mu) * jax.lax.rsqrt(var + eps) * p["gamma"]
                + p["beta"])
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + p["gamma"])


def _rope(x, pos, theta):
    """x: (S, H, Dh); rotates pairs (2i, 2i+1)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _sinusoid(pos, d):
    half = d // 2
    inv = 1.0 / (10000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _layer_weights(key, cfg, layer):
    raw = {}
    for site, (_, _, stacked) in synth.sites(cfg).items():
        if stacked:
            raw[site] = synth.site_arrays(key, synth.site_shapes(cfg, site),
                                          site, layer)
    norms = {"ln1": synth.norm_params(cfg), "ln2": synth.norm_params(cfg)}
    return raw, norms


def _rounder(lower):
    """Round to the `lower` float type and back (saturating), or nothing."""
    if lower is None:
        return lambda x: x
    big = float(jnp.finfo(lower).max)
    return lambda x: jnp.clip(x, -big, big).astype(lower).astype(
        jnp.float32)


def _block(cfg, raw, norms, rnd, h):
    """One layer over one whole sequence h: (S, d) f32."""
    lin = lambda x, s: rnd(itera_linear(                       # noqa: E731
        x, raw[s], synth.site_shapes(cfg, s), cfg["plan"]["act_wl"]))
    S = h.shape[0]
    H, Hk, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    pos = jnp.arange(S)
    x = rnd(_norm(h, norms["ln1"], cfg))
    q = lin(x, "attn/wq").reshape(S, H, hd)
    k = lin(x, "attn/wk").reshape(S, Hk, hd)
    v = lin(x, "attn/wv").reshape(S, Hk, hd)
    if cfg["pos_emb"] == "rope":
        q = rnd(_rope(q, pos, cfg["rope_theta"]))
        k = rnd(_rope(k, pos, cfg["rope_theta"]))
    g = H // Hk
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, kk, precision=HI) * hd ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = rnd(jax.nn.softmax(s, axis=-1))
    o = rnd(jnp.einsum("hqk,khd->qhd", p, vv, precision=HI))
    h = rnd(h + lin(o.reshape(S, H * hd), "attn/wo"))
    x = rnd(_norm(h, norms["ln2"], cfg))
    if cfg["mlp_act"] == "swiglu":
        m = rnd(jax.nn.silu(lin(x, "mlp/gate")) * lin(x, "mlp/up"))
    else:
        m = rnd(jax.nn.gelu(lin(x, "mlp/up"), approximate=True))
    return rnd(h + lin(m, "mlp/down"))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(cfg_json, lower, key, layer, hs):
    """One layer over every sequence: hs (n, S, d) f32."""
    cfg = json.loads(cfg_json)
    raw, norms = _layer_weights(key, cfg, layer)
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda h: _block(cfg, raw, norms,
                                         _rounder(lower), h))(hs)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _embed(cfg_json, lower, key, tokens):
    cfg = json.loads(cfg_json)
    table = synth.embed_table(key, cfg)
    scale = jnp.asarray(cfg["d_model"] ** 0.5, jnp.dtype(cfg["dtype"]))
    h = table[tokens].astype(jnp.float32) * scale.astype(jnp.float32)
    if cfg["pos_emb"] == "sinusoidal":
        h = h + _sinusoid(jnp.arange(tokens.shape[0]), cfg["d_model"])
    return _rounder(lower)(h)


@functools.partial(jax.jit, static_argnums=(0,))
def _head(cfg_json, key, h):
    """Final norm and output head: (S, d) -> (S, V) f32 logits."""
    cfg = json.loads(cfg_json)
    shp = synth.site_shapes(cfg, "lm_head")
    arrays = synth.site_arrays(key, shp, "lm_head", 0)
    with jax.default_matmul_precision("highest"):
        x = _norm(h, synth.norm_params(cfg), cfg)
        return itera_linear(x, arrays, shp, cfg["plan"]["act_wl"])


def hidden_states(seed: int, cfg: dict, seqs: list[np.ndarray], *,
                  lower=None) -> list:
    """Last-layer hidden states (S_i, d) of each whole sequence, one
    layer at a time across all sequences (each padded to a common
    length; padding sits after the real tokens, which causal attention
    never lets them see)."""
    cj = json.dumps(cfg, sort_keys=True)
    lo = jnp.dtype(lower) if lower else None
    key = synth.seed_key(seed)
    width = max(len(s) for s in seqs)
    hs = jnp.stack([_embed(cj, lo, key, jnp.asarray(
        np.pad(s, (0, width - len(s))))) for s in seqs])
    for layer in range(cfg["num_layers"]):
        hs = _layer(cj, lo, key, jnp.int32(layer), hs)
    return [h[:len(s)] for h, s in zip(hs, seqs)]


def logits(seed: int, cfg: dict, h):
    return _head(json.dumps(cfg, sort_keys=True), synth.seed_key(seed), h)

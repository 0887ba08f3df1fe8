"""Plain reference of the served model, in float32 `jax.numpy`: what
every model family shares.

A decoder-only model as the configuration files describe it: token
embedding scaled by sqrt(d_model) (rounded to the model dtype),
sinusoidal or interleaved-pair rotary positions, RMSNorm (stored as
gamma - 1) or LayerNorm, and an untied output head; the layers between
are the family's (`families/<name>.py`), run one at a time across
sequences by `hidden_states`, and its output head is the site the family
names to `logits`. Every linear is its plan method's math
(`methods/<name>.py` `linear`), built from the row quantization
and the exact integer products here.

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

import methods
import synth

HI = jax.lax.Precision.HIGHEST


def rowquant(x, qm):
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = jnp.where(absmax > 0, absmax / qm, 1.0)
    return jnp.clip(jnp.round(x / s), -qm, qm), s


def int_dot(a, b):
    return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def codes(v, packed):
    return synth.unpack(v) if packed else v


def linear(x, arrays, shp, act_wl):
    """One site's linear under its plan method (`synth.shapes`)."""
    return methods.load(shp["method"]).linear(x, arrays, shp, act_wl)


def norm(x, p, cfg):
    eps = cfg["norm_eps"]
    if cfg["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return ((x - mu) * jax.lax.rsqrt(var + eps) * p["gamma"]
                + p["beta"])
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + p["gamma"])


def rope(x, pos, theta):
    """x: (S, H, Dh); rotates pairs (2i, 2i+1)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def sinusoid(pos, d):
    half = d // 2
    inv = 1.0 / (10000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def rounder(lower):
    """Round to the `lower` float type and back (saturating), or nothing."""
    if lower is None:
        return lambda x: x
    big = float(jnp.finfo(lower).max)
    return lambda x: jnp.clip(x, -big, big).astype(lower).astype(
        jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _embed(cfg_json, lower, key, tokens):
    cfg = json.loads(cfg_json)
    table = synth.embed_table(key, cfg)
    scale = jnp.asarray(cfg["d_model"] ** 0.5, jnp.dtype(cfg["dtype"]))
    h = table[tokens].astype(jnp.float32) * scale.astype(jnp.float32)
    if cfg["pos_emb"] == "sinusoidal":
        h = h + sinusoid(jnp.arange(tokens.shape[0]), cfg["d_model"])
    return rounder(lower)(h)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _head(cfg_json, site, shp_json, key, h):
    """Final norm and the output head `site`: (S, d) -> (S, V) f32."""
    cfg, shp = json.loads(cfg_json), json.loads(shp_json)
    arrays = synth.site_arrays(key, shp, site, 0)
    with jax.default_matmul_precision("highest"):
        x = norm(h, synth.norm_params(cfg), cfg)
        return linear(x, arrays, shp, cfg["plan"]["act_wl"])


def hidden_states(layer_fn, seed: int, cfg: dict, seqs: list[np.ndarray],
                  lower=None) -> list:
    """Last-layer hidden states (S_i, d) of each whole sequence, one
    layer at a time across all sequences (each padded to a common
    length; padding sits after the real tokens, which causal attention
    never lets them see). `layer_fn(cfg_json, lower, key, layer, hs)` is
    the family's layer over every sequence, hs (n, S, d) f32."""
    cj = json.dumps(cfg, sort_keys=True)
    lo = jnp.dtype(lower) if lower else None
    key = synth.seed_key(seed)
    width = max(len(s) for s in seqs)
    hs = jnp.stack([_embed(cj, lo, key, jnp.asarray(
        np.pad(s, (0, width - len(s))))) for s in seqs])
    for layer in range(cfg["num_layers"]):
        hs = layer_fn(cj, lo, key, jnp.int32(layer), hs)
    return [h[:len(s)] for h, s in zip(hs, seqs)]


def logits(seed: int, cfg: dict, h, site: str, shp: dict):
    """Logits of hidden states h through the family's output head: the
    final norm, then `site` of shapes `shp` (one layer, unstacked)."""
    return _head(json.dumps(cfg, sort_keys=True), site,
                 json.dumps(shp, sort_keys=True), synth.seed_key(seed), h)

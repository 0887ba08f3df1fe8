"""Dense decoder-only family: every layer one pre-norm block of causal
grouped-query attention (sinusoidal or interleaved-pair rotary
positions) and a SwiGLU or tanh-GELU MLP, each linear under the
configuration's plan method (`synth.shapes`). The program's
`models/transformer.py` layout with `layout="dense"`.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

import reference
import synth
from work import ITEMSIZE, linears


def program_config(config: dict):
    from repro.configs.base import ModelConfig

    return ModelConfig(name=config["name"], **config["model"])


# ------------------------------------------------------------ weights --
def sites(cfg: dict) -> dict:
    """{site path: (K, N, stacked)} of every compressed linear."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    h, hk, f = cfg["num_heads"], cfg["num_kv_heads"], cfg["d_ff"]
    out = {"attn/wq": (d, h * hd, True), "attn/wk": (d, hk * hd, True),
           "attn/wv": (d, hk * hd, True), "attn/wo": (h * hd, d, True),
           "mlp/up": (d, f, True), "mlp/down": (f, d, True)}
    if cfg["mlp_act"] == "swiglu":
        out["mlp/gate"] = (d, f, True)
    out["lm_head"] = (d, cfg["vocab_size"], False)
    return out


def site_shapes(cfg: dict, site: str) -> dict:
    k, n, _ = sites(cfg)[site]
    return synth.shapes(cfg["plan"], k, n)


def make_raw(key, cfg: dict) -> dict:
    """Every weight as plain arrays: "embed", the norms, and per site its
    `synth.site_arrays`, with a leading layer axis on stacked sites."""
    L = cfg["num_layers"]
    out = {"embed": synth.embed_table(key, cfg),
           "final_norm": synth.norm_params(cfg),
           "ln1": synth.norm_params(cfg, (L,)),
           "ln2": synth.norm_params(cfg, (L,))}
    for site, (_, _, stacked) in sites(cfg).items():
        shp = site_shapes(cfg, site)
        out[site] = (synth.stacked_arrays(key, shp, site, L) if stacked
                     else synth.site_arrays(key, shp, site, 0))
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _make_raw_jit(key, cfg_json: str):
    return make_raw(key, json.loads(cfg_json))


def program_tree(seed: int, cfg: dict):
    """The served parameter tree, made on the device in one jitted call,
    in the tree layout the program's transformer reads."""
    raw = _make_raw_jit(synth.seed_key(seed), json.dumps(cfg, sort_keys=True))
    node = lambda site: synth.node(                           # noqa: E731
        raw[site], site_shapes(cfg, site), cfg["plan"])
    layers = {"attn": {k: node(f"attn/{k}") for k in ("wq", "wk", "wv",
                                                        "wo")},
              "ln1": raw["ln1"], "ln2": raw["ln2"],
              "mlp": {k: node(f"mlp/{k}") for k in ("gate", "up", "down")
                      if f"mlp/{k}" in raw}}
    return {"embed": raw["embed"], "final_norm": raw["final_norm"],
            "layers": layers, "lm_head": node("lm_head")}


# ---------------------------------------------------------- reference --
def _layer_weights(key, cfg, layer):
    raw = {site: synth.site_arrays(key, site_shapes(cfg, site), site, layer)
           for site, (_, _, stacked) in sites(cfg).items() if stacked}
    norms = {"ln1": synth.norm_params(cfg), "ln2": synth.norm_params(cfg)}
    return raw, norms


def _block(cfg, raw, norms, rnd, h):
    """One layer over one whole sequence h: (S, d) f32."""
    lin = lambda x, s: rnd(reference.linear(                   # noqa: E731
        x, raw[s], site_shapes(cfg, s), cfg["plan"]["act_wl"]))
    S = h.shape[0]
    H, Hk, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    pos = jnp.arange(S)
    x = rnd(reference.norm(h, norms["ln1"], cfg))
    q = lin(x, "attn/wq").reshape(S, H, hd)
    k = lin(x, "attn/wk").reshape(S, Hk, hd)
    v = lin(x, "attn/wv").reshape(S, Hk, hd)
    if cfg["pos_emb"] == "rope":
        q = rnd(reference.rope(q, pos, cfg["rope_theta"]))
        k = rnd(reference.rope(k, pos, cfg["rope_theta"]))
    g = H // Hk
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, kk, precision=reference.HI) * hd ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = rnd(jax.nn.softmax(s, axis=-1))
    o = rnd(jnp.einsum("hqk,khd->qhd", p, vv, precision=reference.HI))
    h = rnd(h + lin(o.reshape(S, H * hd), "attn/wo"))
    x = rnd(reference.norm(h, norms["ln2"], cfg))
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
                                         reference.rounder(lower), h))(hs)


def hidden_states(seed: int, cfg: dict, seqs, lower=None) -> list:
    return reference.hidden_states(_layer, seed, cfg, seqs, lower)


def logits(seed: int, cfg: dict, h):
    return reference.logits(seed, cfg, h, "lm_head",
                            site_shapes(cfg, "lm_head"))


# --------------------------------------------------------------- work --
def attention(cfg: dict, tok) -> dict:
    """Float operations and HBM bytes of `paged_attention` over the
    window: QK and PV over each row's visible positions; K and V of every
    position read once per decode row, each prompt's positions read at
    least once; queries read and outputs written once."""
    h, hk, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    b = ITEMSIZE[cfg["dtype"]]
    visible = sum(p * (p + 1) // 2 for p in tok.prompt_lens) + sum(
        q + 1 for q in tok.decode_positions)
    read = sum(tok.prompt_lens) + sum(q + 1 for q in tok.decode_positions)
    L = cfg["num_layers"]
    ops = L * 4 * h * hd * visible
    byt = L * (2 * hk * hd * b * read + 2 * h * hd * b * tok.rows)
    return {"ops": ops, "bytes": byt}


def work(cfg: dict, tok, steps: int, seed: int) -> dict:
    """The linears' kernels (every stacked site on each valid row of
    every layer, the output head on each served token), then
    `paged_attention`. Dense work depends on counts alone, not on the
    rows' tokens or the seed."""
    L = cfg["num_layers"]
    out = linears(cfg, [
        (site_shapes(cfg, s), L if stacked else 1,
         tok.rows if stacked else tok.emitted)
        for s, (_, _, stacked) in sites(cfg).items()], steps)
    out["paged_attention"] = {**attention(cfg, tok), "peak": "bf16_flops"}
    return out

"""Weights made from `--seed`, directly in the serving format of a uniform
packed ITERA plan: per linear site a rank-R pair of W4 factor codes with
one f32 scale per rank vector, W1 (K, R) packed along R and W2 (R, N)
packed along N wherever that axis is whole 256-column tiles (else an int8
carrier), the layout named `int4-halves-256`: byte j of a 256-column tile
holds column j in its low nibble and column j + 128 in its high nibble.

The served work depends only on shapes, word lengths, ranks and packing,
so the codes are random (uniform over the W4 range [-7, 7]) rather than
decomposed from a trained matrix. Scales are set so that each factor
has unit gain on unit-variance input, which keeps the residual stream
O(1) and the logits meaningful.

`make_raw` builds the whole tree as plain arrays in one jitted call on
the device. `site_arrays` regenerates one layer of one site alone, bit
for bit the same, which is how the reference gets its weights without
taking anything the program holds. Nothing here imports the program;
`to_program_tree` (the one place that does) wraps the arrays in the
program's node types.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

W4_QMAX = 7
PACK_TILE = 256
# variance of a code drawn uniformly from the 15 values [-7, 7]
_CODE_VAR = (15 ** 2 - 1) / 12.0


def rank_for(k: int, n: int, fraction: float, multiple: int = 64) -> int:
    """Rank of a (k, n) site under a uniform `rank_fraction` plan: the
    fraction of min(k, n), floored to a multiple of 64 once
    min(k, n) >= 256."""
    full = min(k, n)
    r = int(round(fraction * full))
    if full >= 4 * multiple:
        r = max(multiple, (r // multiple) * multiple)
    return max(1, min(r, full))


def packs(dim: int) -> bool:
    return dim % PACK_TILE == 0


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
    r = rank_for(k, n, cfg["plan"]["rank_fraction"])
    return {"k": k, "n": n, "r": r, "w1_packed": packs(r),
            "w2_packed": packs(n)}


def pack(codes):
    """int8 codes (..., n), n a multiple of 256 -> packed bytes (..., n/2)."""
    *lead, n = codes.shape
    t = codes.reshape(*lead, n // PACK_TILE, 2, PACK_TILE // 2)
    lo, hi = t[..., 0, :], t[..., 1, :]
    return ((lo & 0x0F) | ((hi & 0x0F) << 4)).astype(jnp.int8).reshape(
        *lead, n // 2)


def unpack(packed):
    """Inverse of `pack`: sign-extended int8 codes (..., 2 * c)."""
    *lead, c = packed.shape
    p = packed.astype(jnp.int32)
    lo = ((p << 28) >> 28).astype(jnp.int8)
    hi = ((p << 24) >> 28).astype(jnp.int8)
    half = PACK_TILE // 2
    t = jnp.stack([lo.reshape(*lead, c // half, half),
                   hi.reshape(*lead, c // half, half)], axis=-2)
    return t.reshape(*lead, 2 * c)


def _factor(key, rows, cols, scale_axis, fan_in, packed):
    """One W4 factor: codes (rows, cols) and a scale per rank vector."""
    kc, ks = jax.random.split(key)
    codes = jax.random.randint(kc, (rows, cols), -W4_QMAX, W4_QMAX + 1,
                               jnp.int8)
    n_scales = cols if scale_axis == 0 else rows
    jitter = jax.random.uniform(ks, (n_scales,), jnp.float32, 0.75, 1.25)
    s = jitter / jnp.sqrt(fan_in * _CODE_VAR)
    s = s[None, :] if scale_axis == 0 else s[:, None]
    return (pack(codes) if packed else codes), s.astype(jnp.float32)


def _site_key(key, site: str, layer: int | jax.Array):
    sid = sum(ord(c) * 31 ** i for i, c in enumerate(site)) % (2 ** 31)
    return jax.random.fold_in(jax.random.fold_in(key, sid), layer)


def site_arrays(key, shp: dict, site: str, layer):
    """(w1 values, w1 scale, w2 values, w2 scale) of one layer of a site,
    exactly as the program holds them (packed bytes where packed)."""
    k1, k2 = jax.random.split(_site_key(key, site, layer))
    v1, s1 = _factor(k1, shp["k"], shp["r"], 0, shp["k"], shp["w1_packed"])
    v2, s2 = _factor(k2, shp["r"], shp["n"], 1, shp["r"], shp["w2_packed"])
    return v1, s1, v2, s2


def seed_key(seed: int):
    return jax.random.PRNGKey(seed)


def norm_params(cfg: dict, lead=()) -> dict:
    dt = jnp.dtype(cfg["dtype"])
    d = cfg["d_model"]
    if cfg["norm"] == "layernorm":
        return {"gamma": jnp.ones((*lead, d), dt),
                "beta": jnp.zeros((*lead, d), dt)}
    return {"gamma": jnp.zeros((*lead, d), dt)}    # stored as gamma - 1


def embed_table(key, cfg: dict):
    d = cfg["d_model"]
    e = jax.random.normal(jax.random.fold_in(key, 7), (cfg["vocab_size"], d),
                          jnp.float32) * d ** -0.5
    return e.astype(jnp.dtype(cfg["dtype"]))


def make_raw(key, cfg: dict) -> dict:
    """Every weight as plain arrays: "embed", the norms, and per site
    (v1, s1, v2, s2), with a leading layer axis on stacked sites."""
    L = cfg["num_layers"]
    out = {"embed": embed_table(key, cfg), "final_norm": norm_params(cfg),
           "ln1": norm_params(cfg, (L,)), "ln2": norm_params(cfg, (L,))}
    for site, (_, _, stacked) in sites(cfg).items():
        shp = site_shapes(cfg, site)
        if stacked:
            out[site] = jax.vmap(
                lambda l, s=site, p=shp: site_arrays(key, p, s, l))(
                    jnp.arange(L))
        else:
            out[site] = site_arrays(key, shp, site, 0)
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _make_raw_jit(key, cfg_json: str):
    return make_raw(key, json.loads(cfg_json))


def build_program_tree(seed: int, cfg: dict):
    """The served parameter tree, made on the device in one jitted call."""
    raw = _make_raw_jit(seed_key(seed), json.dumps(cfg, sort_keys=True))
    return to_program_tree(raw, cfg)


def to_program_tree(raw: dict, cfg: dict):
    """Wrap the raw arrays in the program's LowRankQ / QuantizedTensor
    nodes, in the tree layout its transformer reads."""
    from repro.core.itera import LowRankQ
    from repro.core.quant import QuantizedTensor

    act_wl, wl = cfg["plan"]["act_wl"], cfg["plan"]["weight_wl"]

    def node(site):
        v1, s1, v2, s2 = raw[site]
        shp = site_shapes(cfg, site)
        return LowRankQ(
            QuantizedTensor(v1, s1, wl, 0, shp["w1_packed"], act_wl),
            QuantizedTensor(v2, s2, wl, 1, shp["w2_packed"], act_wl))

    layers = {"attn": {k: node(f"attn/{k}") for k in ("wq", "wk", "wv",
                                                        "wo")},
              "ln1": raw["ln1"], "ln2": raw["ln2"],
              "mlp": {k: node(f"mlp/{k}") for k in ("gate", "up", "down")
                      if f"mlp/{k}" in raw}}
    return {"embed": raw["embed"], "final_norm": raw["final_norm"],
            "layers": layers, "lm_head": node("lm_head")}

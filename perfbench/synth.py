"""Weights made from `--seed`, directly in the serving format of the
configuration's plan, W4 codes in the layout named `int4-halves-256`
(byte j of a 256-column tile holds column j in its low nibble and column
j + 128 in its high nibble) wherever the packed axis is whole 256-column
tiles, else in an int8 carrier. The plan's method (`methods/<name>.py`)
decides a site's form: its shapes, the code matrices and scales it
draws, and the program's node that holds them.

The served work depends only on shapes, word lengths, ranks and packing,
so the codes are random (uniform over the W4 range [-7, 7]) rather than
decomposed from a trained matrix. Scales are set so that each factor
has unit gain on unit-variance input, which keeps the residual stream
O(1) and the logits meaningful.

This module holds what every model family shares; a family
(`families/<name>.py`) lists its sites and builds its whole tree in one
jitted call on the device. `site_arrays` regenerates one layer of one
site alone, bit for bit the same, which is how the reference gets its
weights without taking anything the program holds. Nothing here imports
the program; a method's `node` wraps a site's arrays in the program's
node type.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import methods

W4_QMAX = 7
PACK_TILE = 256
# variance of a code drawn uniformly from the 15 values [-7, 7]
_CODE_VAR = (15 ** 2 - 1) / 12.0


def packs(dim: int) -> bool:
    return dim % PACK_TILE == 0


def shapes(plan: dict, k: int, n: int) -> dict:
    """What the plan's method makes of a (K, N) site."""
    return methods.load(plan["method"]).shapes(plan, k, n)


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


def factor(key, rows, cols, scale_axis, fan_in, packed):
    """One W4 code matrix (rows, cols) and its f32 scales, one per column
    (scale_axis 0) or per row (scale_axis 1)."""
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
    """One layer of a site exactly as the program holds it (packed bytes
    where packed), as its plan method draws it."""
    return methods.load(shp["method"]).arrays(_site_key(key, site, layer),
                                              shp)


def stacked_arrays(key, shp: dict, site: str, num_layers: int):
    """`site_arrays` of every layer, with a leading layer axis, made one
    layer at a time: the random bits of a whole quantized matrix in every
    layer at once would not fit on the chip."""
    return jax.lax.map(lambda layer: site_arrays(key, shp, site, layer),
                       jnp.arange(num_layers))


def node(arrays, shp: dict, plan: dict):
    """One site's arrays in the program's node type."""
    return methods.load(shp["method"]).node(arrays, shp, plan)


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

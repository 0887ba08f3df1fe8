"""Plan method "itera": a rank-R pair of W4 factors with one f32 scale
per rank vector, W1 (K, R) packed along R and W2 (R, N) along N where
that axis is whole pack tiles, served by `lowrank_qmm`. The reference
quantizes the input row to `act_wl` bits (one scale per row), takes an
exact integer product with the W1 codes, folds in the per-rank scales,
requantizes the intermediate per row and takes an exact integer product
with the W2 codes."""
from __future__ import annotations

import jax

import reference
import synth

KERNEL = "lowrank_qmm"


def rank_for(k: int, n: int, fraction: float, multiple: int = 64) -> int:
    """Rank of a (k, n) site under a uniform `rank_fraction` plan: the
    fraction of min(k, n), floored to a multiple of 64 once
    min(k, n) >= 256."""
    full = min(k, n)
    r = int(round(fraction * full))
    if full >= 4 * multiple:
        r = max(multiple, (r // multiple) * multiple)
    return max(1, min(r, full))


def shapes(plan: dict, k: int, n: int) -> dict:
    r = rank_for(k, n, plan["rank_fraction"])
    return {"method": "itera", "k": k, "n": n, "r": r,
            "w1_packed": synth.packs(r), "w2_packed": synth.packs(n)}


def arrays(key, shp: dict):
    """(w1 values, w1 scale, w2 values, w2 scale)."""
    k1, k2 = jax.random.split(key)
    v1, s1 = synth.factor(k1, shp["k"], shp["r"], 0, shp["k"],
                          shp["w1_packed"])
    v2, s2 = synth.factor(k2, shp["r"], shp["n"], 1, shp["r"],
                          shp["w2_packed"])
    return v1, s1, v2, s2


def node(arrays, shp: dict, plan: dict):
    from repro.core.itera import LowRankQ
    from repro.core.quant import QuantizedTensor

    act_wl, wl = plan["act_wl"], plan["weight_wl"]
    v1, s1, v2, s2 = arrays
    return LowRankQ(
        QuantizedTensor(v1, s1, wl, 0, shp["w1_packed"], act_wl),
        QuantizedTensor(v2, s2, wl, 1, shp["w2_packed"], act_wl))


def linear(x, arrays, shp: dict, act_wl: int):
    v1, s1, v2, s2 = arrays
    qm = 2 ** (act_wl - 1) - 1
    xq, sx = reference.rowquant(x, qm)
    t = reference.int_dot(xq, reference.codes(v1, shp["w1_packed"])) \
        * sx * s1 * s2.reshape(1, -1)
    tq, st = reference.rowquant(t, qm)
    return reference.int_dot(tq, reference.codes(v2, shp["w2_packed"])) \
        * st


def weight_bytes(shp: dict) -> int:
    k, n, r = shp["k"], shp["n"], shp["r"]
    w1 = k * r // 2 if shp["w1_packed"] else k * r
    w2 = r * n // 2 if shp["w2_packed"] else r * n
    return w1 + w2 + 4 * 2 * r


def row_macs(shp: dict) -> int:
    return shp["r"] * (shp["k"] + shp["n"])

"""Plan method "quant": one W4 code matrix (K, N) packed along N where N
is whole pack tiles, with one f32 scale per output column, served by
`quant_matmul`: the tree `compress_params` emits for
`LayerPlan(method="quant", wl=4)` with `pack=True`. The reference
quantizes the input row to `act_wl` bits (one scale per row), takes an
exact integer product with the codes and folds in the row and column
scales."""
from __future__ import annotations

import reference
import synth

KERNEL = "quant_matmul"


def shapes(plan: dict, k: int, n: int) -> dict:
    return {"method": "quant", "k": k, "n": n, "packed": synth.packs(n)}


def arrays(key, shp: dict):
    """(values, scale)."""
    return synth.factor(key, shp["k"], shp["n"], 0, shp["k"], shp["packed"])


def node(arrays, shp: dict, plan: dict):
    from repro.core.quant import QuantizedTensor

    v, s = arrays
    return QuantizedTensor(v, s, plan["weight_wl"], 0, shp["packed"],
                           plan["act_wl"])


def linear(x, arrays, shp: dict, act_wl: int):
    v, s = arrays
    xq, sx = reference.rowquant(x, 2 ** (act_wl - 1) - 1)
    return reference.int_dot(xq, reference.codes(v, shp["packed"])) * sx * s


def weight_bytes(shp: dict) -> int:
    k, n = shp["k"], shp["n"]
    return (k * n // 2 if shp["packed"] else k * n) + 4 * n


def row_macs(shp: dict) -> int:
    return shp["k"] * shp["n"]

"""Required work of the served step, counted from the plan's shapes and
from valid tokens only: never the padded (rows, span) rectangle the step
computes, and never a kernel's own byte model. The same counts hold
whatever implements a kernel.

Per request with a P-token prompt and n served tokens, the step runs the
linears on P + n - 1 rows (the prompt, then each served token but the
last as the next decode input) and the output head on n rows (one per
served token). A row at position p attends to p + 1 positions.

Work summed over a window before max(ops / peak, bytes / bandwidth) is
taken can only read low against the per-step form, never high.
"""
from __future__ import annotations

import dataclasses

import synth

_ITEMSIZE = {"bfloat16": 2, "float32": 4, "float16": 2}


@dataclasses.dataclass
class Tokens:
    """Valid rows a window processed: `prompt_lens` of the prompts it
    prefilled and `decode_positions` of the served tokens fed back as
    decode inputs; `emitted` served tokens (output-head rows)."""

    prompt_lens: list
    decode_positions: list
    emitted: int

    @property
    def rows(self) -> int:
        return sum(self.prompt_lens) + len(self.decode_positions)


def lowrank_weight_bytes(shp: dict) -> int:
    k, r, n = shp["k"], shp["r"], shp["n"]
    w1 = k * r // 2 if shp["w1_packed"] else k * r
    w2 = r * n // 2 if shp["w2_packed"] else r * n
    return w1 + w2 + 4 * 2 * r


def lowrank(cfg: dict, tok: Tokens, steps: int) -> dict:
    """Int8 operations and HBM bytes every `lowrank_qmm` call of the
    window needs: each site's factors read once per step, each valid row
    read once (int8 codes plus a row scale) and written once."""
    out_b = _ITEMSIZE[cfg["dtype"]]
    ops = byt = 0
    for site, (_, _, stacked) in synth.sites(cfg).items():
        shp = synth.site_shapes(cfg, site)
        layers = cfg["num_layers"] if stacked else 1
        rows = tok.rows if stacked else tok.emitted
        ops += layers * 2 * rows * shp["r"] * (shp["k"] + shp["n"])
        byt += layers * (steps * lowrank_weight_bytes(shp)
                         + rows * (shp["k"] + 4 + shp["n"] * out_b))
    return {"ops": ops, "bytes": byt}


def attention(cfg: dict, tok: Tokens) -> dict:
    """Float operations and HBM bytes of `paged_attention` over the
    window: QK and PV over each row's visible positions; K and V of every
    position read once per decode row, each prompt's positions read at
    least once; queries read and outputs written once."""
    h, hk, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    b = _ITEMSIZE[cfg["dtype"]]
    visible = sum(p * (p + 1) // 2 for p in tok.prompt_lens) + sum(
        q + 1 for q in tok.decode_positions)
    read = sum(tok.prompt_lens) + sum(q + 1 for q in tok.decode_positions)
    L = cfg["num_layers"]
    ops = L * 4 * h * hd * visible
    byt = L * (2 * hk * hd * b * read + 2 * h * hd * b * tok.rows)
    return {"ops": ops, "bytes": byt}


def least_seconds(ops: float, byt: float, op_peak: float,
                  bw: float) -> float:
    return max(ops / op_peak, byt / bw)

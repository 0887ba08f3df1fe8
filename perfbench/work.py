"""Required work of the served step, counted from the plan's shapes and
from valid tokens only: never the padded (rows, span) rectangle the step
computes, and never a kernel's own byte model. The same counts hold
whatever implements a kernel. A model family (`families/<name>.py`)
gives its work by kernel from the helpers here.

Per request with a P-token prompt and n served tokens, the step runs the
linears on P + n - 1 rows (the prompt, then each served token but the
last as the next decode input) and the output head on n rows (one per
served token). A row at position p attends to p + 1 positions.

Work summed over a window before max(ops / peak, bytes / bandwidth) is
taken can only read low against the per-step form, never high.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import families
import methods

ITEMSIZE = {"bfloat16": 2, "float32": 4, "float16": 2}


@dataclasses.dataclass
class Rows:
    """One request's rows in a window: `ids` its whole sequence (prompt,
    then served tokens), `positions` of the rows the window processed
    (every prompt position, each served token fed back as a decode
    input), and `steps` the serving step each of them ran in, numbered
    from 0 in the window's order. A step is known by the host time at
    which its tokens were read back, so a prompt's rows are all placed in
    the step that ran its last chunk."""

    ids: np.ndarray
    positions: list
    steps: list


@dataclasses.dataclass
class Tokens:
    """Valid rows a window processed: `prompt_lens` of the prompts it
    prefilled and `decode_positions` of the served tokens fed back as
    decode inputs; `emitted` served tokens (output-head rows). `requests`
    holds the same rows by request (`Rows`), with token ids and steps,
    for work that depends on what the rows hold, such as routing."""

    prompt_lens: list
    decode_positions: list
    emitted: int
    requests: list = dataclasses.field(default_factory=list)

    @property
    def rows(self) -> int:
        return sum(self.prompt_lens) + len(self.decode_positions)


def linears(cfg: dict, sites, steps: int) -> dict:
    """Int8 operations and HBM bytes every linear kernel call of the
    window needs, by the kernel each site's plan method names: each
    site's codes read once per step, each valid row read once (int8 codes
    plus a row scale) and written once. `sites` holds (shapes, layers,
    rows) per site: `synth.shapes`, the layers that hold it and the valid
    rows each of them takes."""
    out_b = ITEMSIZE[cfg["dtype"]]
    out = {}
    for shp, layers, rows in sites:
        m = methods.load(shp["method"])
        w = out.setdefault(m.KERNEL,
                           {"ops": 0, "bytes": 0, "peak": "int8_ops"})
        w["ops"] += layers * 2 * rows * m.row_macs(shp)
        w["bytes"] += layers * (steps * m.weight_bytes(shp)
                                + rows * (shp["k"] + 4 + shp["n"] * out_b))
    return out


def required(ctx) -> dict:
    """{kernel: {"ops", "bytes", "peak"}} of the traced window, from the
    configuration's family, which gets the window's rows and the seed its
    weights were made from."""
    cfg = ctx["cfg"]
    return families.load(cfg["family"]).work(
        cfg, ctx["trace_tokens"], ctx["trace"].step_count, ctx["seed"])


def least_seconds(ops: float, byt: float, op_peak: float,
                  bw: float) -> float:
    return max(ops / op_peak, byt / bw)


def roofline(ctx, kernel: str):
    """Least time of the traced window's required `kernel` work at the
    chip's peak for it or HBM bandwidth, whichever binds, over the device
    time of the events of that name, in %; None where it did not run."""
    t = ctx["trace"].kernel_s(kernel)
    got = required(ctx).get(kernel)
    if not t or got is None:
        return None
    pk = ctx["peaks"]
    least = least_seconds(got["ops"], got["bytes"], pk[got["peak"]],
                          pk["hbm_bytes_per_s"])
    return 100.0 * least / t

"""The one traffic generator. A traffic mix is a JSON file of parameters
under `traffic/`; this module turns it and a seed into requests.

Every seed gets the same requests but for their token ids: the prompt
and output lengths are fixed quantile points of the mix's distributions,
paired and ordered by the mix's own `layout_seed`, and `--seed` draws
only the token ids. So two seeds do the same work in the same order, and
the spread between runs measures the system, not the draw (with a
window of one 16-request burst, which requests decode while long prompts
still prefill would otherwise decide the tail of the gap between
tokens).

Two kinds of mix:

* `bursts` — a closed loop of bursts: each burst is one `serve` call of
  `burst_size` requests, issued when the previous one returns. Each burst
  holds the quantile points of one burst, the longest prompt paired with
  the longest output and the rest paired at random; so every call has the
  same longest request, the same block-table width and the same compiled
  programs.
* `job` — one `serve` call of `requests` requests (an offline job), in
  blocks of `block` requests that each hold the quantile points of one
  block, paired and ordered as a burst's. A window that sees the first
  part of the job then sees the same work on every seed.

Lengths: `{"dist": "lognormal", "median", "sigma", "min", "max"}`,
`{"dist": "uniform", "min", "max"}`, or for outputs
`{"dist": "ratio", "low", "high", "min", "max"}`: the prompt length times
a factor from U(low, high), clipped.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _quantiles(spec: dict, n: int) -> np.ndarray:
    qs = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        nd = NormalDist()
        v = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(q))
             for q in qs]
    elif spec["dist"] == "uniform":
        v = [spec["min"] + q * (spec["max"] - spec["min"]) for q in qs]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def _outputs(spec: dict, prompts: np.ndarray, rng) -> np.ndarray:
    """Output lengths for prompts already in request order; the longest
    prompt (index 0 before shuffling, see `_sizes`) gets the largest."""
    n = len(prompts)
    if spec["dist"] == "ratio":
        ratios = spec["low"] + (np.arange(n) + 0.5) / n * (
            spec["high"] - spec["low"])
        ratios = ratios[::-1].copy()
        ratios[1:] = rng.permutation(ratios[1:])
        out = np.rint(prompts * ratios)
        return np.clip(out, spec["min"], spec["max"]).astype(np.int64)
    out = _quantiles(spec, n)[::-1].copy()
    out[1:] = rng.permutation(out[1:])
    return out


def _sizes(mix: dict, n: int, rng, cap) -> list[tuple[int, int]]:
    """n (prompt, output) pairs: the quantile points, the longest prompt
    with the longest output, the others paired and ordered at random;
    outputs cut so that prompt plus output stay within `cap` positions."""
    prompts = _quantiles(mix["prompt"], n)[::-1].copy()
    prompts[1:] = rng.permutation(prompts[1:])
    outputs = _outputs(mix["output"], prompts, rng)
    if cap is not None:
        outputs = np.minimum(outputs, cap - prompts)
        if outputs.min() < 1:
            raise ValueError(f"a prompt of the mix fills the position cap "
                             f"{cap}")
    order = rng.permutation(n)
    return [(int(prompts[i]), int(outputs[i])) for i in order]


@dataclasses.dataclass
class Req:
    prompt: np.ndarray      # int32 token ids
    max_tokens: int


def tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(1, vocab, size=n).astype(np.int32)


def _layout(mix: dict, k: int):
    """Pairing and order of the k-th burst or block."""
    return np.random.default_rng([mix["layout_seed"], k])


def bursts(mix: dict, seed: int, vocab: int, cap=None):
    """Endless bursts (lists of Req) of a `bursts` mix."""
    rng = np.random.default_rng([seed, 1])
    k = 0
    while True:
        yield [Req(tokens(rng, p, vocab), o)
               for p, o in _sizes(mix, mix["burst_size"], _layout(mix, k),
                                  cap)]
        k += 1


def job(mix: dict, seed: int, vocab: int, cap=None) -> list[Req]:
    rng = np.random.default_rng([seed, 2])
    return [Req(tokens(rng, p, vocab), o)
            for k in range(mix["requests"] // mix["block"])
            for p, o in _sizes(mix, mix["block"], _layout(mix, k), cap)]


def longest(mix: dict, cap=None) -> tuple[int, int]:
    """(prompt, output) of the longest request any seed produces."""
    n = mix["burst_size"] if mix["kind"] == "bursts" else mix["block"]
    p = int(_quantiles(mix["prompt"], n).max())
    o = mix["output"]
    if o["dist"] == "ratio":
        out = int(np.clip(np.rint(p * (o["low"] + (n - 0.5) / n
                                      * (o["high"] - o["low"]))),
                          o["min"], o["max"]))
    else:
        out = int(_quantiles(o, n).max())
    if cap is not None:
        out = min(out, cap - p)
    return p, out


def rows(mix: dict) -> int:
    """Requests that one serve call holds."""
    return mix["burst_size"] if mix["kind"] == "bursts" else mix["requests"]

"""What decides `correct`: served tokens held against the plain reference.

After the window closes and the program's state is freed, a sample of
the finished requests drawn from the seed (the longest finished request
always among them) is run once each through the plain reference of the
configuration's family (`families/<name>.py`), over its prompt followed
by its served tokens. At every served position the reading is
the gap by which the served token's reference logit lies below the
reference's best logit there: 0 where the program picked the reference's
own greedy token. A run is correct when every served request is well
formed (as many tokens as it asked for, all in the vocabulary) and each
number compared stays within the cell's limit (`limits/<cell>.json`).

The control is the same reference computed one precision step down
(`readings(..., lower=...)`): at each position it picks its own best
token, and the gap of that token under the reference is read the same
way and judged by the same limits (`judge`).
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

import families

HERE = os.path.dirname(os.path.abspath(__file__))


def load_limits(cell: str) -> dict:
    with open(os.path.join(HERE, "limits", f"{cell}.json")) as f:
        return json.load(f)


def well_formed(outputs, max_tokens, vocab: int) -> bool:
    o = np.asarray(outputs)
    return (o.size == max_tokens and o.ndim == 1
            and (o.size == 0 or (o.min() >= 0 and o.max() < vocab)))


def sample(finished: list, n: int, seed: int) -> list:
    """Up to n of the finished (prompt, outputs) pairs, drawn from the
    seed, with the longest one always in."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0]) + len(finished[i][1])))
    rng = np.random.default_rng([seed, 3])
    rest = rng.permutation(order[1:])[:max(n - 1, 0)]
    return [finished[i] for i in [order[0], *rest]]


@jax.jit
def _position_reads(lg, pos, tok):
    """Best logit and the logit of `tok` at each of `pos` (fixed length)."""
    rows = lg[pos]
    return rows.max(-1), jnp.take_along_axis(rows, tok[:, None], 1)[:, 0], \
        rows.argmax(-1)


def _served_positions(prompt, outputs, width):
    n = len(outputs)
    pos = np.zeros(width, np.int32)
    tok = np.zeros(width, np.int32)
    pos[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    tok[:n] = outputs
    return pos, tok


def _pad_width(seqs) -> int:
    w = max(len(s) for s in seqs)
    return -(-w // 128) * 128


def readings(seed: int, cfg: dict, items: list, lower=None) -> dict:
    """{"served": {...}} of max and mean gap over every served position of
    `items` ((prompt, outputs) pairs); with `lower`, also {"lower": {...}}:
    the control, the reference with every activation rounded to that
    float type, read at the same positions."""
    fam = families.load(cfg["family"])
    seqs = [np.concatenate([p, o[:-1]]).astype(np.int32) for p, o in items]
    width = _pad_width(seqs)
    seqs_p = [np.pad(s, (0, width - len(s))) for s in seqs]
    hs = fam.hidden_states(seed, cfg, seqs_p)
    ref = []
    for h, (p, o) in zip(hs, items):
        pos, tok = _served_positions(p, o, width)
        best, got, _ = _position_reads(fam.logits(seed, cfg, h),
                                       pos, tok)
        ref.append((np.asarray(best)[:len(o)], np.asarray(got)[:len(o)]))
    out = {"served": _summary([b - g for b, g in ref])}
    if lower is not None:
        hc = fam.hidden_states(seed, cfg, seqs_p, lower=lower)
        gaps = []
        for h_ref, h_c, (p, o), (best, _) in zip(hs, hc, items, ref):
            pos, tok = _served_positions(p, o, width)
            _, _, pick = _position_reads(fam.logits(seed, cfg, h_c),
                                         pos, tok)
            _, got, _ = _position_reads(fam.logits(seed, cfg, h_ref),
                                        pos, pick)
            gaps.append(best - np.asarray(got)[:len(o)])
        out["lower"] = _summary(gaps)
    return out


def judge(limits: dict, reading: dict) -> tuple[dict, bool]:
    """Each number the cell's limits name, beside its limit, and whether
    every one stays within it."""
    compared = {k: {"value": reading[k], "limit": v["limit"]}
                for k, v in limits.items()}
    return compared, all(c["value"] <= c["limit"]
                         for c in compared.values())


def _summary(gaps: list) -> dict:
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"max_gap": float(g.max()) if g.size else 0.0,
            "mean_gap": float(g.mean()) if g.size else 0.0,
            "tokens": int(g.size),
            "differ": int((g > 0).sum())}

"""Whole-model post-training compression driver (paper Fig. 2 pipeline).

Walks a parameter pytree, replaces every eligible 2-D linear weight with a
compressed representation, and returns accounting used by the DSE and the
Pareto benchmarks:

  * storage bits  -> compression ratio vs FP32 (ratio 4 == plain 8-bit)
  * NOps per batch row -> the paper's "number of operations" metric

Storage accounting is RESIDENT-honest: per-layer bits are computed from
the device arrays the compressed node actually holds (`storage_bits()`),
so packed W4 counts 4 bits/weight because the bytes really are halved,
while W6 — which stays in its int8 carrier (no byte-aligned packing) —
counts a full 8, and skipped params count at their actual dtype itemsize.
Nothing is priced at a word length that is not physically resident.

Methods (paper §VIII-C):
  quant  — fixed-point WxAy quantization only                  (baseline)
  svd    — one-shot truncated SVD then quantization            (baseline)
  itera  — Algorithm 1 iterative quantized decomposition       (ours)
  itera + per-layer ranks from SRA                              (ours, best)

`compress_params` executes an `api.plan.CompressionPlan` — per-layer
method / word length / rank, mixed precision across layers. The legacy
`CompressionConfig` (one global method/wl) is kept as a thin shim that
lowers to a uniform plan, so every existing call site keeps working; the
returned `CompressionReport` records the executed plan as provenance.

The compressed pytree stores `QuantizedTensor` / `LowRankQ` nodes in place
of raw arrays; `repro.models.layers.apply_linear` dispatches on the node
type, so any model in the zoo runs compressed without code changes.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.itera import LowRankQ, itera_decompose, svd_decompose
from repro.core.quant import QuantizedTensor, pack_weights, quantize

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    """Uniform-compression shim: one global method/wl, per-layer rank
    override. Lowered to a per-layer `CompressionPlan` by `compress_params`
    (see `to_plan`); new code should build plans directly."""

    method: str = "quant"              # none | quant | svd | itera
    weight_wl: int = 8
    act_wl: int = 8
    pack: bool = True                  # pack W4 weights two-nibbles-per-byte
    rank_fraction: float = 0.5         # uniform rank = frac · min(K, N)
    ranks: dict | None = None          # per-layer override (path -> rank), e.g. from SRA
    min_rank: int = 1
    include: str = r".*"               # regex over pytree paths
    # w_uk|w_uv: latent attention's absorbed kv_b_proj halves stay plain
    exclude: str = r"(embed|router|norm|scale|bias|ln|pos|w_uk|w_uv)"
    min_dim: int = 32                  # skip tiny matrices (router heads etc.)
    power_iters: int = 24

    rank_multiple: int = 64            # shard- & MXU-aligned ranks

    def rank_for(self, path: str, shape) -> int:
        full = min(int(shape[0]), int(shape[1]))
        if self.ranks and path in self.ranks:
            r = int(self.ranks[path])
        else:
            r = int(round(self.rank_fraction * full))
        if full >= 4 * self.rank_multiple:  # align big matrices for TP/MXU
            r = max(self.rank_multiple,
                    (r // self.rank_multiple) * self.rank_multiple)
        return max(self.min_rank, min(r, full))

    def to_plan(self, params):
        from repro.api.plan import CompressionPlan

        return CompressionPlan.from_config(params, self)


@dataclasses.dataclass
class LayerReport:
    path: str
    shape: tuple
    method: str
    rank: int | None
    bits: int                  # RESIDENT bits: what the device arrays occupy
    fp32_bits: int
    nops_per_row: int
    dense_nops_per_row: int
    wl: int = 8
    packed: bool = False       # any factor stored packed-nibble in HBM


@dataclasses.dataclass
class CompressionReport:
    layers: list
    skipped_params: int        # element count of params left uncompressed
    plan: Any = None           # the executed api.plan.CompressionPlan
    skipped_bits: int = 0      # actual bits of those params (dtype itemsize)

    @property
    def total_bits(self) -> int:
        return sum(l.bits for l in self.layers) + self.skipped_bits

    @property
    def total_fp32_bits(self) -> int:
        # skipped params are untouched by compression, so they enter both
        # sides of the total at their actual size — counting them at 32
        # bits regardless of dtype skewed totals for bf16 models.
        return sum(l.fp32_bits for l in self.layers) + self.skipped_bits

    @property
    def compression_ratio(self) -> float:
        """Normalized to FP32 over the *compressed* layers only, matching the
        paper's linear-layer focus (ratio 4 == W8)."""
        comp = sum(l.bits for l in self.layers)
        return sum(l.fp32_bits for l in self.layers) / max(comp, 1)

    @property
    def nops_per_row(self) -> int:
        return sum(l.nops_per_row for l in self.layers)

    @property
    def dense_nops_per_row(self) -> int:
        return sum(l.dense_nops_per_row for l in self.layers)

    def summary(self) -> str:
        return (
            f"layers={len(self.layers)} "
            f"packed={sum(1 for l in self.layers if l.packed)} "
            f"ratio={self.compression_ratio:.2f}x "
            f"NOps={self.nops_per_row/1e6:.2f}M/row "
            f"(dense {self.dense_nops_per_row/1e6:.2f}M/row, "
            f"{100*(1-self.nops_per_row/max(self.dense_nops_per_row,1)):.1f}% saved)"
        )


def path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_leaves_by_path(params) -> dict:
    """{path: leaf} for every leaf in the tree (plan validation helper)."""
    return {path_str(p): leaf
            for p, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}


def eligible_linears(
    params, cfg: CompressionConfig
) -> list[tuple[str, Array]]:
    """(path, leaf) for every 2-D weight the config selects."""
    inc, exc = re.compile(cfg.include), re.compile(cfg.exclude, re.I)
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        p = path_str(path)
        if not hasattr(leaf, "ndim") or leaf.ndim < 2:
            continue
        if min(leaf.shape[-2:]) < cfg.min_dim:
            continue
        if not inc.search(p) or exc.search(p):
            continue
        out.append((p, leaf))
    return out


def shape_spectra(params, alpha: float = 2.0,
                  selector: CompressionConfig | None = None):
    """Impose a power-law singular-value spectrum (s_i ∝ i^-alpha) on every
    weight the selector picks, preserving each matrix's singular vectors
    and Frobenius norm.

    Proxy conditioning, not compression: the repo's random-init proxies
    have near-FLAT spectra (Marchenko–Pastur), so truncating ANY rank
    discards components as informative as those kept — low-rank error is
    maximally adversarial and nothing like the trained weights the paper
    compresses, whose spectra decay (the premise that makes rank
    truncation work at all). Benchmarks that measure rank-truncation
    quality trade-offs — e.g. the self-speculative draft's acceptance
    rate — shape the proxy first so the trade-off is measured in the
    decaying-spectrum regime the technique targets. Exact-identity tests
    must NOT depend on this (they hold either way).

    Runs on host (numpy SVD) at build time; batched leaves (L, K, N) are
    shaped per matrix. Leaves the selector excludes (embeddings, norms,
    biases) pass through untouched, shapes and dtypes are preserved.
    """
    if alpha < 0:
        raise ValueError(f"alpha must be >= 0, got {alpha}")
    sel = selector if selector is not None else CompressionConfig()
    targets = {}
    for p, w in eligible_linears(params, sel):
        wn = np.asarray(w, np.float64)
        u, s, vt = np.linalg.svd(wn, full_matrices=False)
        t = np.arange(1, s.shape[-1] + 1, dtype=np.float64) ** -alpha
        t = t * (np.linalg.norm(s, axis=-1, keepdims=True)
                 / np.linalg.norm(t))
        targets[p] = jnp.asarray(((u * t[..., None, :]) @ vt)
                                 .astype(np.asarray(w).dtype))
    return jax.tree_util.tree_map_with_path(
        lambda p, x: targets.get(path_str(p), x), params)


def _runtime_format(node, act_wl: int, pack: bool):
    """Stamp the plan's runtime knobs onto a compressed node: the
    activation word length its matmul quantizes to, and — for W4 whose
    last dim is whole pack tiles (`quant.packable`) — the packed-nibble
    HBM layout. Packing is exact (codes unchanged), so
    packed and carrier trees are token-identical."""
    def one(q: QuantizedTensor) -> QuantizedTensor:
        q = dataclasses.replace(q, act_wl=act_wl)
        return pack_weights(q) if pack else q

    if isinstance(node, LowRankQ):
        return LowRankQ(one(node.w1), one(node.w2))
    return one(node)


def _node_bits(node) -> tuple[int, bool]:
    """(resident storage bits, any-factor-packed) straight from the node's
    device arrays — the honest accounting, never an assumed word length."""
    if isinstance(node, LowRankQ):
        return (node.storage_bits(), node.w1.packed or node.w2.packed)
    return node.storage_bits(), node.packed


def _compress_matrix(w: Array, lp, power_iters: int, *,
                     act_wl: int = 8, pack: bool = True):
    """Compress one (..., K, N) weight per its LayerPlan -> (node,
    LayerReport). Leading stack dims (scan-stacked layers, expert stacks,
    layers x experts) are handled by vmapping once per leading dim."""
    k, n = int(w.shape[-2]), int(w.shape[-1])
    rank = min(int(lp.rank), min(k, n)) if lp.rank is not None else None
    if lp.method == "quant":
        fn = lambda m: quantize(m, lp.wl, axis=0)               # noqa: E731
    elif lp.method == "svd":
        fn = lambda m: svd_decompose(m, rank, lp.wl)            # noqa: E731
    elif lp.method == "itera":
        fn = lambda m: itera_decompose(                         # noqa: E731
            m, rank, lp.wl, power_iters=power_iters)
    else:
        raise ValueError(lp.method)
    mult = 1
    for _ in range(w.ndim - 2):
        fn = jax.vmap(fn)
    for d in w.shape[:-2]:
        mult *= int(d)
    node = _runtime_format(fn(w), act_wl, pack)
    bits, packed = _node_bits(node)
    return node, _report_for(lp.path, (k, n), lp.method, lp.wl, rank,
                             mult=mult, bits=bits, packed=packed)


def _report_for(path, kn, method, wl, rank, mult, bits, packed):
    k, n = kn
    fp32 = 32 * k * n * mult
    if method == "quant":
        nops, rank_out = k * n * mult, None
    else:
        nops, rank_out = rank * (k + n) * mult, rank
    return LayerReport(
        path=path, shape=(mult, k, n) if mult > 1 else (k, n),
        method=method, rank=rank_out, bits=bits, fp32_bits=fp32,
        nops_per_row=nops, dense_nops_per_row=k * n * mult, wl=wl,
        packed=packed,
    )


def compress_params(params, spec):
    """Execute a compression spec over a parameter pytree.

    spec: an `api.plan.CompressionPlan` (per-layer method/wl/rank, mixed
    precision across layers) or a legacy `CompressionConfig` (lowered to a
    uniform plan first). Returns (compressed pytree, CompressionReport);
    the report's `.plan` is the executed plan.
    """
    from repro.api.plan import CompressionPlan

    if not isinstance(spec, CompressionPlan):
        if spec.method == "none":
            leaves = jax.tree_util.tree_leaves(params)
            return params, CompressionReport(
                [], sum(int(l.size) for l in leaves),
                plan=CompressionPlan(label="none", act_wl=spec.act_wl),
                skipped_bits=sum(_leaf_bits(l) for l in leaves))
        plan = spec.to_plan(params)
    else:
        plan = spec.validate(params)

    targets = {lp.path: lp for lp in plan.active_layers()}
    reports: list[LayerReport] = []
    skipped = 0
    skipped_bits = 0

    def visit(path, leaf):
        nonlocal skipped, skipped_bits
        p = path_str(path)
        if p in targets:
            node, rep = _compress_matrix(leaf, targets[p], plan.power_iters,
                                         act_wl=plan.act_wl, pack=plan.pack)
            reports.append(rep)
            return node
        if hasattr(leaf, "size"):
            skipped += int(leaf.size)
            skipped_bits += _leaf_bits(leaf)
        return leaf

    new_params = jax.tree_util.tree_map_with_path(visit, params)
    return new_params, CompressionReport(reports, skipped, plan=plan,
                                         skipped_bits=skipped_bits)


def _leaf_bits(leaf) -> int:
    """Actual storage bits of an uncompressed leaf: size x dtype itemsize
    (a bf16 embedding is 16 bits/param, not an assumed 32)."""
    if not hasattr(leaf, "size"):
        return 0
    itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", 4)
    return int(leaf.size) * int(itemsize) * 8


def sra_eval_closure(
    params,
    cfg: CompressionConfig,
    quality_fn: Callable[[Any], float],
):
    """Bridge to core.sra: returns (eval_fn(ranks)->acc, layer_paths, max_ranks).

    `quality_fn(compressed_params) -> float` runs the calibration set.
    """
    targets = eligible_linears(params, cfg)
    paths = [p for p, _ in targets]
    max_ranks = [int(min(w.shape[-2:])) for _, w in targets]

    def eval_fn(ranks):
        rmap = dict(zip(paths, [int(r) for r in ranks]))
        c = dataclasses.replace(cfg, ranks=rmap)
        cp, _ = compress_params(params, c)
        return float(quality_fn(cp))

    return eval_fn, paths, max_ranks

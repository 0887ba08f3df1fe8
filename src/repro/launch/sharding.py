"""Parameter / batch / cache sharding rules (logical -> PartitionSpec).

Rules are (path-regex, trailing-dim logical names). The first match wins;
leading scan-stack dims get None. Logical names:

  data   — FSDP-style weight sharding axis (within-pod)
  model  — tensor-parallel axis
  batch  — activation batch axis: ("pod","data") on multi-pod meshes
  expert — expert-parallel: "model" when num_experts divides it, else None

GQA note: kv-head dims whose size doesn't divide the model axis rely on
GSPMD's implicit padding (musicgen H=24, gemma2 kv=8); the waste shows up
honestly in the roofline's MODEL_FLOPS/HLO_FLOPS ratio.
"""
from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.runtime.shardctx import resolve_axis

# (regex over "/"-joined path, spec names for the TRAILING dims)
_RULES = [
    (r"embed$", ("model", None)),
    (r"lm_head$", ("data", "model")),
    (r"/(wq|wk|wv)(/values)?$", ("data", "model")),
    (r"/wo(/values)?$", ("model", "data")),
    (r"/(gate|up)(/values)?$", ("data", "model")),
    (r"/down(/values)?$", ("model", "data")),
    (r"router(/values)?$", (None, None)),
    # mamba1
    (r"/in_proj(/values)?$", ("data", "model")),
    (r"/dt_in(/values)?$", ("model", None)),
    (r"/bc_proj(/values)?$", ("model", None)),
    (r"/dt_proj(/values)?$", (None, "model")),
    (r"/out_proj(/values)?$", ("model", "data")),
    (r"/conv_w$", ("model", None)),
    (r"/A_log$", ("model", None)),      # trimmed to ndim for mamba2 (nh,)
    (r"/(D|dt_bias)$", ("model",)),
    # mamba2
    (r"/zx_proj(/values)?$", ("data", "model")),
    (r"/bc_in(/values)?$", ("data", None)),
    (r"/dt_lin(/values)?$", ("data", "model")),
    # low-rank factors (ITERA): w1 R-dim over model, w2 N-dim over model;
    # the (B, R) intermediate all-gathers (R << N — the collective win).
    (r"/w1/values$", ("data", "model")),
    (r"/w1/scale$", (None, "model")),
    (r"/w2/values$", (None, "model")),
    (r"/w2/scale$", (None, None)),
    # quantized dense scales: per-output-column -> follow the N dim
    (r"/(wq|wk|wv|gate|up|lm_head)/scale$", (None, "model")),
    (r"/(wo|down|out_proj|in_proj|zx_proj|dt_lin)/scale$", (None, "data")),
]

_EXPERT_RULES_EP = [
    (r"experts/(up|gate)(/values)?$", ("model", "data", None)),
    (r"experts/down(/values)?$", ("model", None, "data")),
    (r"experts/\w+/scale$", ("model", None, None)),
]
_EXPERT_RULES_TP = [
    (r"experts/(up|gate)(/values)?$", (None, "data", "model")),
    (r"experts/down(/values)?$", (None, "model", "data")),
    (r"experts/(up|gate)/scale$", (None, None, "model")),
    (r"experts/down/scale$", (None, None, "data")),
]


def path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _resolve(names, mesh, ndim):
    names = list(names)
    if len(names) > ndim:            # e.g. A_log rule on mamba2's (nh,)
        names = names[-ndim:]
    names = [None] * (ndim - len(names)) + names
    phys = []
    for n in names:
        ax = resolve_axis(n, mesh)
        phys.append(ax)
    return P(*phys)


def _divisible(dim, axis, mesh):
    if axis is None:
        return True
    size = (np.prod([mesh.shape[a] for a in axis]) if isinstance(axis, tuple)
            else mesh.shape[axis])
    return dim % size == 0


def spec_for(path: str, leaf, mesh, cfg=None) -> P:
    """PartitionSpec for one param leaf."""
    ndim = getattr(leaf, "ndim", 0)
    if ndim == 0:
        return P()
    rules = list(_RULES)
    if cfg is not None and cfg.moe is not None:
        ep = cfg.moe.num_experts % mesh.shape["model"] == 0
        rules = (_EXPERT_RULES_EP if ep else _EXPERT_RULES_TP) + rules
    for pat, names in rules:
        if re.search(pat, path):
            spec = _resolve(names, mesh, ndim)
            # drop any axis that does not divide (replicate instead),
            # except GQA head dims where GSPMD padding is intended.
            fixed = []
            for dim, ax in zip(leaf.shape, list(spec) + [None] * ndim):
                fixed.append(ax if _divisible(dim, ax, mesh) or _is_head_dim(
                    path, ax) else None)
            return P(*fixed[:ndim])
    return P(*([None] * ndim))


def _is_head_dim(path: str, axis) -> bool:
    return axis == "model" and re.search(r"/(wq|wk|wv|wo)", path) is not None


def param_shardings(params, mesh, cfg=None):
    """NamedSharding pytree mirroring `params`."""
    def visit(path, leaf):
        return NamedSharding(mesh, spec_for(path_str(path), leaf, mesh, cfg))

    return jax.tree_util.tree_map_with_path(visit, params)


def batch_shardings(batch, mesh, *, shard_batch_dim=True):
    def visit(leaf):
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        names = ["batch" if shard_batch_dim else None] + \
            [None] * (leaf.ndim - 1)
        if shard_batch_dim and not _divisible(
                leaf.shape[0], resolve_axis("batch", mesh), mesh):
            names[0] = None
        return NamedSharding(mesh, _resolve(names, mesh, leaf.ndim))

    return jax.tree_util.tree_map(visit, batch)


def cache_shardings(cache, mesh, *, batch: int):
    """Decode-cache shardings. Leaf layouts (leading L/G stack dim):
      kv k/v   (L, B, S, Hk, hd)   -> (None, batch, None, model, None)
      ssm h    (L, B, ..., ...)    -> (None, batch, model-ish...)
    When B doesn't divide the batch axes (long_500k B=1), shard the
    *sequence* dim of KV caches over "data" instead (SP decode)."""
    data_ok = _divisible(batch, resolve_axis("batch", mesh), mesh)

    def visit(path, leaf):
        p = path_str(path)
        nd = leaf.ndim
        if nd == 5:                 # stacked kv cache (L, B, S, Hk, hd)
            L, B, S, Hk, hd = leaf.shape
            spec = [None, None, None, None, None]
            if data_ok:
                spec[1] = resolve_axis("batch", mesh)
            # model axis: kv heads when they divide, else the sequence dim
            # (GSPMD then computes decode softmax as a flash-decode-style
            # sharded partial reduction). in_shardings demand exact
            # divisibility — no padding on inputs.
            if _divisible(Hk, "model", mesh):
                spec[3] = "model"
                if not data_ok and _divisible(S, resolve_axis("data", mesh),
                                              mesh):
                    spec[2] = resolve_axis("data", mesh)   # SP decode
            else:
                ax = ("model" if data_ok
                      else tuple(a for a in ("data", "model")
                                 if a in mesh.axis_names))
                if _divisible(S, ax, mesh):
                    spec[2] = ax
            return NamedSharding(mesh, P(*spec))
        if "conv" in p and nd == 4:                   # (L, B, k-1, di)
            names = [None, "batch" if data_ok else None, None, "model"]
        elif nd >= 3:                                 # ssm state (L, B, ...)
            names = [None, "batch" if data_ok else None, "model"] + \
                [None] * (nd - 3)
            if not _divisible(leaf.shape[2], resolve_axis("model", mesh),
                              mesh):
                names[2] = None
        else:
            names = [None] * nd
        return NamedSharding(mesh, _resolve(names, mesh, nd))

    return jax.tree_util.tree_map_with_path(visit, cache)


# ---------------------------------------------------------------------------
# shard_map tensor parallelism (serving).
#
# These rules are deliberately DIFFERENT from the GSPMD `_RULES` above:
# shard_map hands each device a literal array slice, so there is no
# implicit padding (GQA heads must divide exactly — `check_tp_geometry`
# raises instead) and the slice axis must keep per-shard compute
# *numerically* equal to a column/row block of the reference matmul.
# N-sites (wq/wk/wv/gate/up: replicated input, sliced output columns)
# are bit-exact per shard. K-sites (wo/down: sliced input features,
# full output) produce partial sums the layer boundary psums restore.
# For ITERA low-rank cascades that means w1 must NOT be R-sharded here
# (the GSPMD rules R-shard it and let the compiler all-gather the
# (B, R) intermediate): on an N-site the whole w1 is replicated and
# only w2's output columns are sliced — bit-exact, the cascade's
# intermediate activation quantization sees identical tensors on every
# shard. On a K-site w1's input rows are sliced; its per-column scale
# (1, R) stays replicated and w2 is replicated, and the cascade's
# activation requant then runs over local features only — numerically
# close but not bit-equal, which is why the TP identity tests compress
# N-sites only.

_TP_N = r"/(wq|wk|wv|gate|up)"
_TP_K = r"/(wo|down)"

# (regex, action): "col" slices the last dim, "row" the second-to-last,
# "rep" replicates. First match wins.
_TP_RULES = [
    (_TP_N + r"/w1/(values|scale)$", "rep"),
    (_TP_N + r"/w2/values$", "col"),
    (_TP_N + r"/w2/scale$", "rep"),       # (R, 1) per-rank-row scale
    (_TP_K + r"/w1/values$", "row"),
    (_TP_K + r"/w1/scale$", "rep"),       # (1, R) per-column scale
    (_TP_K + r"/w2/(values|scale)$", "rep"),
    (_TP_N + r"(/values|/scale)?$", "col"),
    (_TP_K + r"/values$", "row"),
    (_TP_K + r"/scale$", "rep"),          # (1, N) per-output-column scale
    (_TP_K + r"$", "row"),
]


def check_tp_geometry(cfg, tp: int) -> None:
    """Raise unless `cfg` shards cleanly over a model axis of size `tp`.

    shard_map cannot pad the way GSPMD does, so every sharded dimension
    must divide exactly; the error names the ModelConfig field to fix."""
    if tp <= 1:
        return
    if cfg.layout != "dense":
        raise NotImplementedError(
            f"tensor-parallel serving supports layout='dense' only, got "
            f"layout={cfg.layout!r}")
    bad = [f"ModelConfig.{name}={val}" for name, val in
           (("num_heads", cfg.num_heads), ("num_kv_heads", cfg.num_kv_heads),
            ("d_ff", cfg.d_ff)) if val % tp]
    if bad:
        raise ValueError(
            f"model geometry does not divide the tensor-parallel axis "
            f"(tp={tp}): {', '.join(bad)}. shard_map slices arrays "
            f"literally — there is no GSPMD padding — so attention/KV "
            f"heads and the MLP hidden dim must each be a multiple of "
            f"the mesh 'model' axis size.")


def tp_local_config(cfg, tp: int):
    """The per-shard ModelConfig the shard_map body runs with: each
    shard owns num_heads/tp query heads and num_kv_heads/tp KV heads.
    head_dim is a concrete field after __post_init__, so it survives
    the replace; d_model/d_ff are untouched (the weight slices carry
    the hidden-dim split)."""
    import dataclasses
    if tp <= 1:
        return cfg
    return dataclasses.replace(cfg, num_heads=cfg.num_heads // tp,
                               num_kv_heads=cfg.num_kv_heads // tp)


def tp_spec_for(path: str, leaf, tp: int) -> P:
    """shard_map PartitionSpec for one param leaf under `tp`-way TP."""
    ndim = getattr(leaf, "ndim", 0)
    if tp <= 1 or ndim < 2:
        return P(*([None] * ndim))
    action = "rep"
    for pat, act in _TP_RULES:
        if re.search(pat, path):
            action = act
            break
    if action == "rep":
        return P(*([None] * ndim))
    dim = ndim - 1 if action == "col" else ndim - 2
    if leaf.shape[dim] % tp:
        raise ValueError(
            f"TP cannot slice {path}: dim {dim} has size {leaf.shape[dim]}"
            f", not divisible by tp={tp} (packed sub-8-bit leaves halve "
            f"the packed axis — geometry must divide after packing)")
    spec = [None] * ndim
    spec[dim] = "model"
    return P(*spec)


def _check_packed_slices(params, tp: int) -> None:
    """Refuse column slices that cut packed W4 pack tiles.

    Packed bytes hold whole 256-column tiles (core.quant.PACK_TILE), so a
    column slice of a packed leaf is the packed form of its own logical
    columns only when every shard holds whole tiles; otherwise a shard's
    bytes would carry another shard's columns in their high nibbles."""
    from repro.core.quant import PACK_TILE, QuantizedTensor

    def visit(path, node):
        if isinstance(node, QuantizedTensor) and node.packed:
            p = path_str(path) + "/values"
            sliced = tp_spec_for(p, node.values, tp)[-1] == "model"
            cols = node.shape[-1] // tp
            if sliced and cols % PACK_TILE:
                raise ValueError(
                    f"TP cannot column-slice packed {p}: each of the {tp} "
                    f"shards would hold {cols} columns, not whole "
                    f"{PACK_TILE}-column pack tiles — compress that site "
                    f"unpacked (plan pack=False) or leave it out of the "
                    f"plan")
        return node

    jax.tree_util.tree_map_with_path(
        visit, params, is_leaf=lambda x: isinstance(x, QuantizedTensor))


def tp_param_specs(params, tp: int):
    """PartitionSpec pytree (shard_map in_specs) for the serving params."""
    _check_packed_slices(params, tp)
    return jax.tree_util.tree_map_with_path(
        lambda p, l: tp_spec_for(path_str(p), l, tp), params)


def tp_param_shardings(params, mesh):
    """NamedSharding pytree placing params for the TP serving step, so
    shard_map finds every leaf pre-sliced (no per-dispatch resharding)."""
    tp = mesh.shape["model"]
    specs = tp_param_specs(params, tp)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


def opt_shardings(opt_state, params, mesh, cfg=None, *, zero1=True):
    """Optimizer-state shardings.

    fp32 m/v mirror the param spec (plus ZeRO-1: the first replicated,
    divisible dim gets sharded over 'data'). 8-bit state leaves are
    (nblocks, 256) block tables -> shard dim0 over 'data' when divisible.
    """
    from repro.optim.adamw import zero1_pspec

    pspecs = {
        path_str(p): spec_for(path_str(p), l, mesh, cfg)
        for p, l in jax.tree_util.tree_flatten_with_path(params)[0]
    }

    def visit(path, leaf):
        ps = path_str(path)
        if getattr(leaf, "ndim", 0) == 0:
            return NamedSharding(mesh, P())
        m = re.match(r"^(m|v)/(.+)$", ps)
        if not m:
            return NamedSharding(mesh, P(*([None] * leaf.ndim)))
        base = m.group(2)
        if base.endswith(("/q", "/scale", "/off")):
            d0 = "data" if _divisible(leaf.shape[0],
                                      resolve_axis("data", mesh), mesh) \
                else None
            return NamedSharding(
                mesh, P(d0, *([None] * (leaf.ndim - 1))))
        spec = pspecs.get(base, P(*([None] * leaf.ndim)))
        if zero1:
            spec = zero1_pspec(spec, leaf.shape, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(visit, opt_state)

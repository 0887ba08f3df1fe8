"""Logical-axis sharding context.

Model code never mentions meshes: it calls `maybe_shard(x, "batch", None,
"model")` with *logical* axis names. When a mesh is installed (launcher /
dry-run) the names resolve to physical mesh axes and become
with_sharding_constraint; with no mesh installed (unit tests, CPU smoke
runs) the call is a no-op.

Logical -> physical:
  batch  -> ("pod", "data") on a multi-pod mesh, ("data",) single-pod
  model  -> ("model",)
  data   -> ("data",)
  None   -> unsharded

Two distinct mechanisms live here, and they are never active together:

  * the GSPMD context (`use_mesh` + `maybe_shard`) — whole-array
    programs, the compiler partitions; used by training and `generate`.
  * the shard_map tensor-parallel context (`tp_axis` + `psum_tp`) —
    per-shard programs for the serving step: `api.engine` wraps
    `transformer.unified_step` in shard_map and binds the mesh axis the
    layer boundaries must all-reduce over; model code calls `psum_tp` at
    exactly the attention-output and MLP-output boundaries, which is the
    identity when no TP axis is bound (single-device serving, training,
    unit tests). Inside a shard_map body the GSPMD mesh must NOT be
    installed — `maybe_shard` constraints are meaningless over manual
    axes — so the serve loop leaves `_MESH` unset on the TP path.
"""
from __future__ import annotations

import contextlib

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_MESH: jax.sharding.Mesh | None = None
_TP_AXIS: str | None = None


def set_mesh(mesh) -> None:
    global _MESH
    _MESH = mesh


def get_mesh():
    return _MESH


@contextlib.contextmanager
def use_mesh(mesh):
    global _MESH
    prev = _MESH
    _MESH = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _MESH = prev


@contextlib.contextmanager
def tp_axis(name: str):
    """Bind `name` (a shard_map mesh axis, normally "model") as the
    tensor-parallel all-reduce axis while the wrapped model code traces.
    The binding is consulted at trace time, so it must wrap the *body*
    passed to shard_map — the psums it enables become part of the jaxpr
    and survive jit caching."""
    global _TP_AXIS
    prev = _TP_AXIS
    _TP_AXIS = name
    try:
        yield
    finally:
        _TP_AXIS = prev


def get_tp_axis() -> str | None:
    return _TP_AXIS


def psum_tp(x):
    """All-reduce a tensor-parallel partial sum over the bound TP axis.

    This is THE collective of the sharded serving step: with attention
    heads and MLP hidden dims column/row-split per shard, each layer's
    wo and down projections produce partial sums over the local slice,
    and one psum per boundary (2L per step) restores the replicated
    residual stream. Identity when no TP axis is bound, so model code
    calls it unconditionally."""
    if _TP_AXIS is None:
        return x
    return jax.lax.psum(x, _TP_AXIS)


def tp_shard_map(fn, mesh, *, in_specs, out_specs):
    """jax.shard_map with varying-manual-axes checking off.

    The serving body's outputs are replicated by construction (every
    shard computes identical logits after the boundary psums), but the
    static checker cannot always prove that through the pool
    scatter/gather, so it is disabled — the TP identity tests in
    tests/test_tp_serving.py are the real check."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def resolve_axis(name, mesh):
    if name is None:
        return None
    if name == "batch":
        return ("pod", "data") if "pod" in mesh.axis_names else "data"
    if name == "seq":        # sequence parallelism rides the model axis
        return "model"
    if name == "tokens":     # flattened (batch*seq) dim: all axes merged
        return tuple(a for a in ("pod", "data", "model")
                     if a in mesh.axis_names)
    if name in mesh.axis_names:
        return name
    return None


def logical_spec(names, mesh) -> P:
    return P(*(resolve_axis(n, mesh) for n in names))


def _axis_size(axis, mesh):
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def maybe_shard(x, *names):
    """Logical sharding constraint; axes that don't divide the dim are
    dropped (no silent GSPMD padding on activations)."""
    if _MESH is None:
        return x
    axes = [resolve_axis(n, _MESH) for n in names]
    axes = [a if a is not None and d % _axis_size(a, _MESH) == 0 else None
            for a, d in zip(axes, x.shape)]
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_MESH, P(*axes)))

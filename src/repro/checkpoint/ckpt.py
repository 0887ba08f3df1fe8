"""Checkpointing: atomic manifest-committed saves, async (off the critical
path), keep-last-k GC, and *elastic* restore — a checkpoint written on one
mesh can resume on any mesh whose axis sizes divide the global shapes.

Layout:
  <dir>/step_000123.tmp/       (written)
  <dir>/step_000123/           (atomic rename = commit)
    manifest.json              step, keys, shapes, dtypes
    arrays.npz                 flattened pytree, path-keyed

Restore never trusts a directory without a manifest (a crash mid-save
leaves only *.tmp, which is garbage-collected on the next save).
"""
from __future__ import annotations

import json
import os
import re
import shutil
import threading

import jax
import jax.numpy as jnp
import numpy as np

# importing LowRankQ registers the pytree node compressed checkpoints hold
from repro.core.itera import LowRankQ      # noqa: F401
from repro.core.quant import PACK_LAYOUT, QuantizedTensor

_SEP = "|"


def _flatten(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        key = _SEP.join(_part(p) for p in path)
        out[key] = np.asarray(leaf)
    return out


def _part(p):
    if hasattr(p, "key"):
        return f"k:{p.key}"
    if hasattr(p, "idx"):
        return f"i:{p.idx}"
    if hasattr(p, "name"):
        return f"a:{p.name}"
    return f"x:{p}"


def _quant_formats(tree) -> dict:
    """{path: {wl, axis, packed, pack_layout, act_wl}} for every
    QuantizedTensor node.

    The codes/scales land in arrays.npz like any leaf; this records the
    *layout* aux alongside them so the manifest is self-describing and
    restore can refuse a tree built with the wrong residency (a packed-W4
    checkpoint restored into a carrier-layout tree, or vice versa, would
    otherwise only surface as a confusing shape error). `pack_layout`
    names the nibble order of packed codes (core.quant.PACK_LAYOUT):
    packed bytes of another layout have the same shape and would restore
    into silently wrong weights."""
    fmts = {}

    def visit(path, leaf):
        if isinstance(leaf, QuantizedTensor):
            fmts[_SEP.join(_part(p) for p in path)] = {
                "wl": int(leaf.wl), "axis": int(leaf.axis),
                "packed": bool(leaf.packed),
                "pack_layout": PACK_LAYOUT if leaf.packed else None,
                "act_wl": int(leaf.act_wl),
            }
        return leaf

    jax.tree_util.tree_map_with_path(
        visit, tree, is_leaf=lambda x: isinstance(x, QuantizedTensor))
    return fmts


def save(ckpt_dir: str, step: int, tree, *, keep: int = 3,
         async_save: bool = False):
    """Write a checkpoint. async_save=True returns a join()able thread."""
    host_tree = jax.tree_util.tree_map(np.asarray, tree)  # snapshot now

    def _write():
        os.makedirs(ckpt_dir, exist_ok=True)
        name = f"step_{step:08d}"
        tmp = os.path.join(ckpt_dir, name + ".tmp")
        final = os.path.join(ckpt_dir, name)
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        arrays = _flatten(host_tree)
        np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
        manifest = {
            "step": step,
            "keys": sorted(arrays),
            "shapes": {k: list(v.shape) for k, v in arrays.items()},
            "dtypes": {k: str(v.dtype) for k, v in arrays.items()},
            "quant_formats": _quant_formats(host_tree),
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)           # atomic commit
        _gc(ckpt_dir, keep)

    if async_save:
        t = threading.Thread(target=_write, daemon=True)
        t.start()
        return t
    _write()
    return None


def _gc(ckpt_dir: str, keep: int):
    steps = list_steps(ckpt_dir)
    for s in steps[:-keep] if keep > 0 else []:
        shutil.rmtree(os.path.join(ckpt_dir, f"step_{s:08d}"),
                      ignore_errors=True)
    for d in os.listdir(ckpt_dir):                 # crashed partial saves
        if d.endswith(".tmp"):
            shutil.rmtree(os.path.join(ckpt_dir, d), ignore_errors=True)


def list_steps(ckpt_dir: str):
    if not os.path.isdir(ckpt_dir):
        return []
    out = []
    for d in os.listdir(ckpt_dir):
        m = re.fullmatch(r"step_(\d+)", d)
        if m and os.path.exists(os.path.join(ckpt_dir, d, "manifest.json")):
            out.append(int(m.group(1)))
    return sorted(out)


def latest_step(ckpt_dir: str):
    steps = list_steps(ckpt_dir)
    return steps[-1] if steps else None


def restore(ckpt_dir: str, like, step: int | None = None, *,
            shardings=None):
    """Restore into the structure of `like` (a pytree or ShapeDtypeStructs).

    shardings: optional pytree of NamedSharding matching `like` — this is
    the elastic-resume path: arrays are device_put with the *new* mesh's
    shardings regardless of what mesh wrote them.
    """
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
    path = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(path, "arrays.npz"))

    flat = jax.tree_util.tree_flatten_with_path(like)
    paths = [(_SEP.join(_part(p) for p in path), leaf)
             for path, leaf in flat[0]]
    missing = [k for k, _ in paths if k not in manifest["keys"]]
    if missing:
        raise KeyError(f"checkpoint at step {step} missing keys: "
                       f"{missing[:5]}{'...' if len(missing) > 5 else ''}")

    # layout guard: quantized nodes must agree on the fields that shape
    # or order the stored arrays (wl, axis, packed, pack_layout) —
    # restoring a packed checkpoint into a carrier tree (or the reverse)
    # is a plan mismatch, not an elastic-resume case, and packed bytes
    # saved before pack_layout was recorded use an older nibble order.
    # act_wl is runtime-only aux (it never changes resident bytes), so
    # differing act_wl restores fine and `like`'s value wins.
    saved_fmts = manifest.get("quant_formats")
    if saved_fmts is not None:
        want_fmts = _quant_formats(like)
        layout = ("wl", "axis", "packed", "pack_layout")
        for key in sorted(set(saved_fmts) & set(want_fmts)):
            got = {f: saved_fmts[key].get(f) for f in layout}
            want = {f: want_fmts[key].get(f) for f in layout}
            if got != want:
                raise ValueError(
                    f"{key}: checkpoint quant layout {got} != expected "
                    f"{want} — rebuild `like` with the plan this "
                    f"checkpoint was compressed under")

    shard_flat = (jax.tree_util.tree_leaves(shardings)
                  if shardings is not None else [None] * len(paths))
    leaves = []
    for (key, leaf), sh in zip(paths, shard_flat):
        arr = data[key]
        want = tuple(leaf.shape)
        if tuple(arr.shape) != want:
            raise ValueError(f"{key}: checkpoint shape {arr.shape} != "
                             f"expected {want}")
        arr = arr.astype(leaf.dtype)
        leaves.append(jax.device_put(arr, sh) if sh is not None
                      else jnp.asarray(arr))
    return jax.tree_util.tree_unflatten(flat[1], leaves), step

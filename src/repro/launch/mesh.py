"""Production mesh definitions.

Single pod : (data=16, model=16)            = 256 chips (TPU v5e pod)
Multi-pod  : (pod=2, data=16, model=16)     = 512 chips

`make_production_mesh` is a function (never a module-level constant) so
importing this module touches no jax device state — device counts are
locked on first backend init, and only launch/dryrun.py (which sets
XLA_FLAGS before any import) may build the 512-way host-platform mesh.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """jax.make_mesh with Auto axis types (GSPMD propagation decides
    every sharding the annotations leave open)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 4, *, pod: int = 0):
    """Small mesh for subprocess sharding tests (host-platform devices)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def make_serving_mesh(model: int, *, devices=None):
    """A (data=1, model=N) mesh for tensor-parallel serving — the shape
    `InferenceEngine.build(mesh=...)` shard-maps the unified step over.

    Unlike `make_mesh`, this uses the FIRST `model` devices rather than
    all of them, so a --xla_force_host_platform_device_count=8 test
    process can build 1/2/4-way serving meshes side by side. N == 1 is
    deliberately legal: it runs the same shard_map path (psum over one
    device is the identity), so every mesh size exercises one code
    path."""
    import numpy as np

    devices = list(jax.devices()) if devices is None else list(devices)
    if model < 1:
        raise ValueError(f"model axis must be >= 1, got {model}")
    if len(devices) < model:
        raise ValueError(
            f"serving mesh needs {model} devices, have {len(devices)}: on "
            f"CPU run under XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={model} (tests/test_tp_serving.py does exactly this)")
    arr = np.asarray(devices[:model], dtype=object).reshape(1, model)
    return jax.sharding.Mesh(arr, ("data", "model"))


# TPU v5e hardware constants (per chip) for the roofline.
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
PEAK_OPS_INT8 = 394e12        # OP/s
HBM_BW = 819e9                # B/s
ICI_BW_PER_LINK = 50e9        # B/s per link
ICI_LINKS = 4                 # v5e: 4 ICI links per chip (2D torus x2 dirs)
VMEM_BYTES = 16 * 2 ** 20     # compiler's default scoped VMEM per kernel
HBM_BYTES = 16 * 2 ** 30      # 16 GiB HBM per v5e chip
PCIE_BW = 16e9                # B/s host<->device (PCIe gen3 x16 effective)
DISPATCH_S = 30e-6            # fixed host->device launch latency per dispatch

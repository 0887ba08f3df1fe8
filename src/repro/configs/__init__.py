"""Architecture registry: the 10 assigned architectures (+ the paper's own
OPUS-MT proxy), each selectable via --arch <id>, and the per-arch input
shapes that define the 40 dry-run cells.

Shapes (LM family — seq_len x global_batch):
  train_4k     4,096 x 256   train_step
  prefill_32k  32,768 x 32   prefill (one pass, returns cache + last logits)
  decode_32k   32,768 x 128  serve_step (1 new token, KV cache of seq_len)
  long_500k    524,288 x 1   serve_step; only sub-quadratic archs (SSM /
                             hybrid / bounded-window) — see DESIGN.md §5.
"""
from __future__ import annotations

import dataclasses
import importlib

from repro.configs.base import ModelConfig, MoEConfig, RopeScaling, SSMConfig

_MODULES = {
    "mixtral-8x22b": "repro.configs.mixtral_8x22b",
    "deepseek-moe-16b": "repro.configs.deepseek_moe_16b",
    "musicgen-medium": "repro.configs.musicgen_medium",
    "nemotron-4-340b": "repro.configs.nemotron_4_340b",
    "stablelm-12b": "repro.configs.stablelm_12b",
    "phi3-medium-14b": "repro.configs.phi3_medium_14b",
    "gemma2-9b": "repro.configs.gemma2_9b",
    "zamba2-2.7b": "repro.configs.zamba2_2p7b",
    "chameleon-34b": "repro.configs.chameleon_34b",
    "falcon-mamba-7b": "repro.configs.falcon_mamba_7b",
    "opus-mt": "repro.configs.opus_mt",
    "deepseek-v2-lite": "repro.configs.deepseek_v2_lite",
}

# the dry-run architectures; opus-mt (the paper's proxy) and
# deepseek-v2-lite (latent attention: serve() only) are served, not dry-run
ARCH_IDS = [k for k in _MODULES if k not in ("opus-mt", "deepseek-v2-lite")]


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(_MODULES[arch])
    return mod.smoke() if smoke else mod.full()


def shape_applicable(arch: str, shape: str) -> tuple[bool, str]:
    """Whether a (arch x shape) cell runs, and why not if it doesn't."""
    cfg = get_config(arch)
    if shape == "long_500k" and not cfg.supports_long_context:
        return False, ("pure full-attention arch: no sub-quadratic path for a "
                       "512k-token decode cache (DESIGN.md §5)")
    return True, ""


def cells(include_skipped: bool = False):
    """All (arch, shape) dry-run cells in a stable order."""
    out = []
    for a in ARCH_IDS:
        for s in SHAPES:
            ok, why = shape_applicable(a, s)
            if ok or include_skipped:
                out.append((a, s, ok, why))
    return out


__all__ = [
    "ModelConfig", "MoEConfig", "RopeScaling", "SSMConfig", "ShapeSpec",
    "SHAPES",
    "ARCH_IDS", "get_config", "shape_applicable", "cells",
]

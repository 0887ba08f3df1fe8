"""Compile rehearsals for a described TPU v5e chip, at opus-mt width.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is only described, and refuses what the chip would
refuse — block shapes the tiling cannot take, kernels that overrun their
scoped VMEM, programs that do not fit. Interpret-mode tests cannot see
any of that. Nothing here runs on a device.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.itera import LowRankQ
from repro.core.quant import QuantizedTensor
from repro.kernels import lowrank_qmm as lr_mod
from repro.kernels import ops
from repro.kernels import quant_matmul as qm_mod

D, D_FF, RANK = 512, 2048, 256          # opus-mt width, rank_fraction 0.5
HK, DH = 8, 64                          # KV heads, head dim


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(compiled) -> set:
    """Names of the Pallas kernels in a compiled program."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return set(re.findall(
        r"%(quant_matmul|lowrank_qmm|paged_attention)\.\d+ = ", text))


def _within_modeled_vmem(monkeypatch, limit: int) -> None:
    """Compile the kernels under a scoped-VMEM limit of exactly `limit`
    bytes, so a pass shows the VMEM model is an upper bound of what the
    compiler allocates."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(pltpu, "CompilerParams", functools.partial(
        pltpu.CompilerParams, vmem_limit_bytes=limit))
    jax.clear_caches()


@pytest.mark.parametrize("m", [8, 256], ids=["decode", "prefill"])
@pytest.mark.parametrize("packed", [False, True], ids=["carrier", "packed"])
@pytest.mark.parametrize("kn", [(D, D_FF), (D_FF, D)], ids=["up", "down"])
def test_quant_matmul_compiles(one_chip, monkeypatch, m, packed, kn):
    k, n = kn
    bm, bk, bn = ops.choose_blocks(m, k, n, packed_n=packed)
    _within_modeled_vmem(monkeypatch, qm_mod.vmem_bytes(
        bm, bk, bn, w_packed=packed))

    def f(x, v, s):
        w = QuantizedTensor(v, s, 4, 0, packed=packed)
        return ops.qmm(x, w, interpret=False)

    compiled = jax.jit(f).lower(
        _spec(one_chip, (m, k), jnp.float32),
        _spec(one_chip, (k, n // 2 if packed else n), jnp.int8),
        _spec(one_chip, (1, n), jnp.float32)).compile()
    assert _kernels(compiled) == {"quant_matmul"}


@pytest.mark.parametrize("m", [8, 256], ids=["decode", "prefill"])
@pytest.mark.parametrize("packed", [False, True], ids=["carrier", "packed"])
@pytest.mark.parametrize("kn", [(D, D_FF), (D_FF, D)], ids=["up", "down"])
def test_lowrank_qmm_compiles(one_chip, monkeypatch, m, packed, kn):
    k, n = kn
    bm, bk, bn = ops.choose_blocks(m, k, n, RANK, packed_n=packed,
                                   packed_r=packed)
    _within_modeled_vmem(monkeypatch, lr_mod.vmem_bytes(
        bm, bk, bn, RANK, w1_packed=packed, w2_packed=packed))

    def f(x, v1, s1, v2, s2):
        lr = LowRankQ(QuantizedTensor(v1, s1, 4, 0, packed=packed),
                      QuantizedTensor(v2, s2, 4, 1, packed=packed))
        return ops.lrmm(x, lr, interpret=False)

    half = 2 if packed else 1
    compiled = jax.jit(f).lower(
        _spec(one_chip, (m, k), jnp.float32),
        _spec(one_chip, (k, RANK // half), jnp.int8),
        _spec(one_chip, (1, RANK), jnp.float32),
        _spec(one_chip, (RANK, n // half), jnp.int8),
        _spec(one_chip, (RANK, 1), jnp.float32)).compile()
    assert _kernels(compiled) == {"lowrank_qmm"}


@pytest.mark.parametrize("kernel", ["qmm", "lrmm"])
def test_int8_kernels_compile_at_highest_precision(one_chip, kernel):
    """Integer dots pin their precision, so the matmul kernels still
    compile where f32 matmuls are set to full precision (the reference
    comparison in chip_smoke.py runs that way); Mosaic refuses an f32
    contraction over int8 operands."""
    m, k, n = 8, D, D_FF

    def f(x, v1, s1, v2, s2):
        if kernel == "qmm":
            return ops.qmm(x, QuantizedTensor(v2, s2.reshape(1, n), 4, 0,
                                              packed=True), interpret=False)
        lr = LowRankQ(QuantizedTensor(v1, s1, 4, 0, packed=True),
                      QuantizedTensor(v2, s2, 4, 1, packed=True))
        return ops.lrmm(x, lr, interpret=False)

    kk = k if kernel == "qmm" else RANK
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(f).lower(
            _spec(one_chip, (m, k), jnp.float32),
            _spec(one_chip, (k, RANK // 2), jnp.int8),
            _spec(one_chip, (1, RANK), jnp.float32),
            _spec(one_chip, (kk, n // 2), jnp.int8),
            _spec(one_chip, (n if kernel == "qmm" else RANK, 1),
                  jnp.float32)).compile()
    assert _kernels(compiled) == {"quant_matmul" if kernel == "qmm"
                                  else "lowrank_qmm"}


@pytest.mark.parametrize("block_size", [8, 16])
@pytest.mark.parametrize("kv", ["float32", "int8"])
def test_paged_attention_compiles(one_chip, kv, block_size):
    """The serving span width (256) over a pool the size one full serve
    of eight 512-token prompts allocates, at the block sizes the serve
    CLI (16) and the serving benchmark (8) use."""
    from repro.kernels.paged_attention import paged_attention

    b, w, mb = 8, 256, 544 // block_size
    nb = b * mb + 1
    pool = {"k": _spec(one_chip, (nb, HK, block_size, DH), jnp.dtype(kv)),
            "v": _spec(one_chip, (nb, HK, block_size, DH), jnp.dtype(kv))}
    if kv == "int8":
        for key in ("ks", "vs"):
            pool[key] = _spec(one_chip, (nb, HK, block_size, 1), jnp.float32)
    i32 = functools.partial(_spec, one_chip, dtype=jnp.int32)
    compiled = jax.jit(
        lambda *a: paged_attention(*a, interpret=False)).lower(
        _spec(one_chip, (b, w, HK, DH), jnp.float32), pool,
        i32((b, mb)), i32((b,)), i32((b,))).compile()
    assert _kernels(compiled) == {"paged_attention"}


@pytest.mark.parametrize("tp", [1, 4])
def test_itera_w4_serving_step_compiles(topo, monkeypatch, tp):
    """The whole fused serving step of full-width opus-mt under a packed
    ITERA-W4 plan, at the widest span bucket (chunk_tokens 256): the
    plan's linears are packed cascades and attention reads the paged
    pool, all through compiled kernels. On one chip the plan covers
    every linear; on a (1, 4) serving mesh it covers the MLP up
    projection, the N-sliced site whose packed shards keep whole pack
    tiles, and the step shard-maps over the four chips with the pool's
    head axis split. The kernel-or-reference choice follows the platform
    predicate, which the test points at the described chips."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.api import CompressionPlan, InferenceEngine
    from repro.configs import get_config
    from repro.core.compress import compress_params
    from repro.launch import sharding as shd
    from repro.launch.mesh import make_serving_mesh
    from repro.models import init_params
    from repro.runtime import kvblocks
    from repro.runtime import sampling as smp

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    mesh = make_serving_mesh(tp, devices=topo.devices) if tp > 1 else None
    cfg = get_config("opus-mt")
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    plan = CompressionPlan.uniform(params, method="itera", weight_wl=4,
                                   rank_fraction=0.5,
                                   **({"include": r"/up$"} if mesh else {}))
    params = jax.eval_shape(lambda p: compress_params(p, plan)[0], params)
    cap, mb, bs, w = 8, 34, 16, 256
    pool = jax.eval_shape(
        lambda: kvblocks.init_paged_cache(cfg, cap * mb + 1, bs))

    def sharding(spec):
        if mesh is None:
            return SingleDeviceSharding(topo.devices[0])
        return NamedSharding(mesh, spec)

    def place(tree, specs):
        return jax.tree_util.tree_map(
            lambda s, ps: _spec(sharding(ps), s.shape, s.dtype), tree, specs)

    params = place(params, shd.tp_param_specs(params, tp))
    pool = place(pool, kvblocks.pool_pspecs(cfg))
    i32 = functools.partial(_spec, sharding(P()), dtype=jnp.int32)
    engine = InferenceEngine(cfg, params, mesh=mesh)
    compiled = engine._unified_fn(True, False).lower(
        params, pool, i32((cap, mb)), i32((cap, w + 3 + smp.SAMP_COLS)),
        i32((cap, 1)), i32((cap, 1)), i32((cap, 1, 1))).compile()
    assert _kernels(compiled) == {"lowrank_qmm", "paged_attention"}
    assert pool["k"].sharding.shard_shape(pool["k"].shape)[2] == \
        cfg.num_kv_heads // tp

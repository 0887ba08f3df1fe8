"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles,
in interpret mode (the kernel body executes on CPU exactly as written)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.itera import LowRankQ, itera_decompose, svd_decompose
from repro.core.quant import pack_weights, quant_linear_ref, quantize
from repro.kernels import ops, ref
from repro.kernels.lowrank_qmm import lowrank_qmm, vmem_bytes as lr_vmem
from repro.kernels.quant_matmul import quant_matmul, vmem_bytes as qm_vmem


def _pack_lr(lr: LowRankQ) -> LowRankQ:
    return LowRankQ(pack_weights(lr.w1), pack_weights(lr.w2))

SHAPES_QMM = [
    (8, 128, 128),       # minimal aligned
    (48, 192, 320),      # nothing divides the defaults -> padding path
    (256, 512, 512),     # the paper's workload (M=K=N=512 with batch 256)
    (1, 96, 640),        # decode-like M=1
    (130, 1024, 256),    # M just over a block
]


@pytest.mark.parametrize("m,k,n", SHAPES_QMM)
@pytest.mark.parametrize("wl", [4, 8])
def test_quant_matmul_vs_oracle(m, k, n, wl):
    key = jax.random.PRNGKey(m * 7 + k + n + wl)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32) / np.sqrt(k)
    wq = quantize(w, wl, axis=0)
    y_kernel = ops.qmm(x, wq, use_kernel=True, interpret=True)
    y_oracle = ops.qmm(x, wq, use_kernel=False)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_oracle),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_out_dtypes(dtype):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (16, 256), jnp.float32)
    wq = quantize(jax.random.normal(key, (256, 128)) * 0.1, 8, axis=0)
    y = ops.qmm(x, wq, use_kernel=True, interpret=True, out_dtype=dtype)
    assert y.dtype == dtype


SHAPES_LR = [
    (8, 128, 128, 16),
    (48, 192, 320, 96),     # all-padding path
    (256, 512, 512, 128),   # paper Fig. 10 workload (rank 128)
    (1, 256, 512, 32),      # decode-like
    (64, 1024, 768, 200),   # rank not 128-aligned
]


@pytest.mark.parametrize("m,k,n,r", SHAPES_LR)
@pytest.mark.parametrize("wl", [4, 6, 8])
def test_lowrank_qmm_vs_oracle(m, k, n, r, wl):
    key = jax.random.PRNGKey(m + k + n + r + wl)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32) / np.sqrt(k)
    lr = svd_decompose(w, r, wl)
    y_kernel = ops.lrmm(x, lr, use_kernel=True, interpret=True, fused=True)
    y_oracle = ops.lrmm(x, lr, use_kernel=False)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_oracle),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("fused", [True, False])
def test_cascade_vs_single_engine_same_math(fused):
    """Single (unfused) and Cascade (fused) schedules agree bit-for-bit."""
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (32, 256), jnp.float32)
    w = jax.random.normal(key, (256, 384), jnp.float32) * 0.05
    lr = itera_decompose(w, 64, 6)
    y = ops.lrmm(x, lr, use_kernel=True, interpret=True, fused=fused)
    y_ref = ops.lrmm(x, lr, use_kernel=False)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_lowrank_error_vs_exact_small():
    """End-to-end quantized cascade stays close to the fp product."""
    key = jax.random.PRNGKey(9)
    x = jax.random.normal(key, (64, 512), jnp.float32)
    w = jax.random.normal(key, (512, 512), jnp.float32) / 22.6
    lr = itera_decompose(w, 256, 8)
    y = ops.lrmm(x, lr, use_kernel=True, interpret=True)
    y_exact = x @ (lr.w1.dequant() @ lr.w2.dequant())
    rel = float(jnp.linalg.norm(y - y_exact) / jnp.linalg.norm(y_exact))
    assert rel < 0.03


def test_batched_leading_dims():
    """ops wrappers accept (..., K) activations."""
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (2, 5, 96), jnp.float32)
    wq = quantize(jax.random.normal(key, (96, 64)) * 0.1, 8, axis=0)
    y = ops.qmm(x, wq, use_kernel=True, interpret=True)
    assert y.shape == (2, 5, 64)
    lr = svd_decompose(jax.random.normal(key, (96, 64)) * 0.1, 16, 8)
    y2 = ops.lrmm(x, lr, use_kernel=True, interpret=True)
    assert y2.shape == (2, 5, 64)


def test_vmem_budget_respected():
    """Auto-chosen blocks keep the working set under the VMEM budget."""
    for (m, k, n, r) in [(4096, 18432, 73728, 512), (256, 512, 512, 128),
                         (1, 8192, 1024, 64)]:
        bm, bk, bn = ops.choose_blocks(m, k, n, r)
        assert lr_vmem(bm, bk, bn, r) <= ops.VMEM_BUDGET
        bm2, bk2, bn2 = ops.choose_blocks(m, k, n)
        assert qm_vmem(bm2, bk2, bn2) <= ops.VMEM_BUDGET
        for b, d in ((bk, 128), (bn, 128)):
            assert b % d == 0


# ------------------------------------------------------- packed residency --
@pytest.mark.parametrize("m,k,n", [(48, 192, 320), (8, 128, 128),
                                   (130, 1024, 256)])
@pytest.mark.parametrize("wl", [4, 6, 8])
def test_qmm_packed_identical_to_carrier(m, k, n, wl):
    """pack_weights never changes a single output bit: W4 moves to the
    packed-nibble layout and unpacks in-kernel; W6/W8 are no-op carriers."""
    key = jax.random.PRNGKey(m + n + wl)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32) / np.sqrt(k)
    wq = quantize(w, wl, axis=0)
    wp = pack_weights(wq)
    # pack_weights only packs whole pack tiles; another N (e.g. 320,
    # 128) stays a carrier and wp is then wq itself — the identity below
    # still proves the no-op. The hand-built bad-axis case is covered by
    # test_qmm_forced_packed_bad_axis_demoted.
    from repro.core.quant import packed_pad_ok

    assert wp.packed == (wl == 4 and packed_pad_ok(n))
    if wp.packed:
        assert wp.values.shape == (k, n // 2)
    y_carrier = ops.qmm(x, wq, use_kernel=True, interpret=True)
    y_packed = ops.qmm(x, wp, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_packed),
                                  np.asarray(y_carrier))
    y_ref = ops.qmm(x, wp, use_kernel=False)
    np.testing.assert_allclose(np.asarray(y_packed), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_qmm_forced_packed_bad_axis_demoted():
    """A hand-built packed tensor on an axis that is not whole pack
    tiles (something compress_params never produces) still computes
    bit-identically: the dispatch demotes it to a carrier up front."""
    import dataclasses

    from repro.core.quant import pack_int4

    key = jax.random.PRNGKey(3)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (8, 128), jnp.float32)
    w = jax.random.normal(kw, (128, 128), jnp.float32) / np.sqrt(128)
    wq = quantize(w, 4, axis=0)
    forced = dataclasses.replace(wq, values=pack_int4(wq.values),
                                 packed=True)
    y_carrier = ops.qmm(x, wq, use_kernel=True, interpret=True)
    y_forced = ops.qmm(x, forced, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_forced),
                                  np.asarray(y_carrier))
    # and the byte model charges the demotion round-trip, so a forced
    # pack can never *report* fewer bytes than its own carrier saves
    assert ops.qmm_hbm_bytes(8, forced) > ops.qmm_hbm_bytes(8, wq)


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("wl", [4, 6, 8])
def test_lrmm_packed_identical_to_carrier(fused, wl):
    """Both cascade factors (W1 packed along R, W2 along N) stream packed
    and unpack in-kernel, bit-identical to the carrier path — in the fused
    cascade AND the two-launch single-engine schedule."""
    key = jax.random.PRNGKey(11 + wl)
    x = jax.random.normal(key, (48, 256), jnp.float32)
    # R=256 and N=512 are whole pack tiles, so a W4 decomposition packs
    # both factors (other widths stay carriers — see
    # test_lrmm_forced_packed_bad_axes_demoted)
    w = jax.random.normal(key, (256, 512), jnp.float32) * 0.05
    lr = svd_decompose(w, 256, wl)
    lrp = _pack_lr(lr)
    assert lrp.w1.packed == (wl == 4) and lrp.w2.packed == (wl == 4)
    assert lrp.rank == 256 and lrp.w2.shape == (256, 512)
    y_carrier = ops.lrmm(x, lr, use_kernel=True, interpret=True, fused=fused)
    y_packed = ops.lrmm(x, lrp, use_kernel=True, interpret=True, fused=fused)
    np.testing.assert_array_equal(np.asarray(y_packed),
                                  np.asarray(y_carrier))
    y_ref = ops.lrmm(x, lrp, use_kernel=False)
    np.testing.assert_allclose(np.asarray(y_packed), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("fused", [True, False])
def test_lrmm_forced_packed_bad_axes_demoted(fused):
    """Hand-built packed factors on axes that are not whole pack tiles
    (R=96, N=320) still compute bit-identically through both schedules —
    the dispatch demotes them to carriers up front."""
    import dataclasses

    from repro.core.quant import pack_int4

    key = jax.random.PRNGKey(29)
    x = jax.random.normal(key, (48, 192), jnp.float32)
    w = jax.random.normal(key, (192, 320), jnp.float32) * 0.05
    lr = svd_decompose(w, 96, 4)

    def force(q):
        return dataclasses.replace(q, values=pack_int4(q.values),
                                   packed=True)

    lrp = LowRankQ(force(lr.w1), force(lr.w2))
    assert lrp.w1.packed and lrp.w2.packed
    y_carrier = ops.lrmm(x, lr, use_kernel=True, interpret=True, fused=fused)
    y_forced = ops.lrmm(x, lrp, use_kernel=True, interpret=True, fused=fused)
    np.testing.assert_array_equal(np.asarray(y_forced),
                                  np.asarray(y_carrier))
    assert ops.lrmm_hbm_bytes(48, lrp) > ops.lrmm_hbm_bytes(48, lr)


def test_lrmm_mixed_packing():
    """Odd rank leaves W1 carrier while W2 still packs — the dispatch
    handles each factor's layout independently."""
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (16, 128), jnp.float32)
    w = jax.random.normal(key, (128, 256), jnp.float32) * 0.1
    lr = svd_decompose(w, 25, 4)           # odd rank: w1 (128, 25) unpackable
    lrp = _pack_lr(lr)
    assert not lrp.w1.packed and lrp.w2.packed
    y = ops.lrmm(x, lrp, use_kernel=True, interpret=True)
    y_ref = ops.lrmm(x, lr, use_kernel=False)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_hbm_bytes_moved_packed_halves_weight_term():
    """The bytes-moved model shows the W4 win: packed weight traffic is
    half the carrier's, and the total strictly shrinks."""
    wq8 = quantize(jnp.ones((4096, 4096)), 8, axis=0)
    wq4 = pack_weights(quantize(jnp.ones((4096, 4096)), 4, axis=0))
    b8 = ops.qmm_hbm_bytes(8, wq8)
    b4 = ops.qmm_hbm_bytes(8, wq4)
    assert b4 < b8
    # decode-like M=8: weight streaming dominates, so packed ~halves total
    assert b4 < 0.6 * b8
    lr8 = svd_decompose(jnp.ones((1024, 1024)), 512, 8)
    lr4 = _pack_lr(svd_decompose(jnp.ones((1024, 1024)), 512, 4))
    assert ops.lrmm_hbm_bytes(8, lr4) < ops.lrmm_hbm_bytes(8, lr8)


# ------------------------------------------------------------- act_wl -----
def test_act_wl_honored_at_runtime():
    """A4 and A8 plans produce different outputs (the clamp really is
    qmax(act_wl)), and the A4 kernel agrees with quant_linear_ref A4."""
    key = jax.random.PRNGKey(7)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (32, 192), jnp.float32)
    w = jax.random.normal(kw, (192, 256), jnp.float32) * 0.1
    wq = quantize(w, 8, axis=0)                       # act_wl=8 default
    wq_a4 = dataclasses.replace(wq, act_wl=4)
    y8 = ops.qmm(x, wq, use_kernel=True, interpret=True)
    y4 = ops.qmm(x, wq_a4, use_kernel=True, interpret=True)
    assert not np.allclose(np.asarray(y8), np.asarray(y4))
    # kernel == ref oracle == quant_linear_ref at A4
    y4_ref = ops.qmm(x, wq_a4, use_kernel=False)
    np.testing.assert_allclose(np.asarray(y4), np.asarray(y4_ref),
                               rtol=1e-5, atol=1e-5)
    y4_gold = quant_linear_ref(x, w, 8, 4)
    np.testing.assert_allclose(np.asarray(y4), np.asarray(y4_gold),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("fused", [True, False])
def test_act_wl_cascade_phase_boundary(fused):
    """The cascade's intermediate requant clamps to qmax(act_wl) too:
    A6 differs from A8 and matches the qm-threaded oracle."""
    key = jax.random.PRNGKey(13)
    x = jax.random.normal(key, (24, 256), jnp.float32)
    w = jax.random.normal(key, (256, 384), jnp.float32) * 0.05
    lr = itera_decompose(w, 64, 8)
    lr_a6 = LowRankQ(dataclasses.replace(lr.w1, act_wl=6),
                     dataclasses.replace(lr.w2, act_wl=6))
    y8 = ops.lrmm(x, lr, use_kernel=True, interpret=True, fused=fused)
    y6 = ops.lrmm(x, lr_a6, use_kernel=True, interpret=True, fused=fused)
    assert not np.allclose(np.asarray(y8), np.asarray(y6))
    y6_ref = ops.lrmm(x, lr_a6, use_kernel=False)
    np.testing.assert_allclose(np.asarray(y6), np.asarray(y6_ref),
                               rtol=1e-5, atol=1e-5)


def test_single_engine_phase1_uses_kernel(monkeypatch):
    """lrmm(fused=False, use_kernel=True) must not fall back to the jnp
    reference for phase 1 — the engine-comparison bench measures
    kernel-vs-kernel."""
    calls = []
    orig = ops._qm.quant_matmul

    def counting(*a, **k):
        calls.append(k.get("w_packed", False))
        return orig(*a, **k)

    monkeypatch.setattr(ops._qm, "quant_matmul", counting)
    monkeypatch.setattr(
        ops._ref, "quant_matmul_ref",
        lambda *a, **k: (_ for _ in ()).throw(
            AssertionError("phase 1 took the jnp reference path")))
    key = jax.random.PRNGKey(17)
    x = jax.random.normal(key, (9, 136), jnp.float32)   # odd shapes: fresh trace
    w = jax.random.normal(key, (136, 264), jnp.float32) * 0.1
    lr = svd_decompose(w, 40, 8)
    y = ops.lrmm(x, lr, use_kernel=True, interpret=True, fused=False)
    assert len(calls) == 2                 # phase 1 AND phase 2 launches
    y_ref = ops.lrmm(x, lr, use_kernel=False)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_requant_rows_matches_kernel_phase_boundary():
    t = jnp.array([[0.5, -3.0, 2.0], [0.0, 0.0, 0.0]])
    tq, st = ref.requant_rows(t)
    assert tq.dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(tq[1]), 0)
    np.testing.assert_allclose(np.asarray(tq.astype(np.float32) * st),
                               np.asarray(t), atol=3e-2)

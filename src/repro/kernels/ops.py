"""Public jit'd wrappers around the Pallas kernels.

Handles: activation quantization (per-row symmetric, clamp from the plan's
act_wl carried on the weight node — A8 by default), packed-W4 layout
dispatch (packed arrays DMA as-is; the kernels unpack in VMEM), padding to
block multiples (zero bytes unpack to zero codes, so padding happens
directly in the packed domain), automatic block-shape selection under a
VMEM budget (the DSE's per-layer choice — see hw/dse.py for the global
search), and backend dispatch.

`on_tpu()` is the one platform predicate of the package: it decides
whether a kernel runs compiled or in interpret mode here, whether
`models.apply_linear` ("auto") takes the kernels or the jnp reference,
and whether serving attention takes the paged-attention kernel
(`models.attention._paged_impl`). On the TPU everything compiles; on the
CPU the served path runs the jnp reference and the kernel tests run the
same kernels in interpret mode (bit-faithful emulation). Tests steer it
with monkeypatch, never through a program option.

  * use_kernel=False -> pure-jnp reference path (numerically identical
                        to the kernels — packed weights are unpacked up
                        front, which is exact)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.itera import LowRankQ
from repro.core.quant import (
    QuantizedTensor, packed_pad_ok, qmax, unpack_int4,
)
from repro.kernels import lowrank_gmm as _gmm
from repro.kernels import lowrank_qmm as _lr
from repro.kernels import quant_matmul as _qm
from repro.kernels import ref as _ref

# 16 MiB is the TPU compiler's default scoped-VMEM limit per kernel on
# v5e (not the chip's VMEM size); blocks are chosen under 12 MiB of it.
VMEM_BUDGET = 12 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def on_tpu() -> bool:
    """True where the Pallas kernels compile (see module docstring)."""
    return jax.default_backend() == "tpu"


def quantize_acts(x: jax.Array, qm: int = 127):
    """Per-row symmetric activation quantization into an int8 carrier,
    clamped to ±qm = ±qmax(act_wl); qm=127 is the A8 default."""
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    sx = jnp.where(absmax > 0, absmax / qm, 1.0).astype(jnp.float32)
    xq = jnp.clip(jnp.round(x / sx), -qm, qm).astype(jnp.int8)
    return xq, sx


# Pack axes that are not whole pack tiles (core.quant.packed_pad_ok
# false — e.g. the paper512 cascade's R=128) are refused at PACK time:
# compress_params stores them as int8 carriers, so the dispatchers below
# normally never see them. The demotion branches in qmm/lrmm are a
# fallback for hand-built packed tensors only — they unpack per call
# (exact, so still bit-identical), and the *_hbm_bytes models charge that
# unpack round-trip so the benchmark's packed<=carrier assert stays
# honest.


def choose_blocks(m: int, k: int, n: int, r: int | None = None,
                  budget: int = VMEM_BUDGET, *,
                  packed_n: bool = False, packed_r: bool = False):
    """Pick (bm, bk, bn) aligned to the MXU that fit the VMEM budget.

    Mirrors the paper's hardware-aware tile selection: prefer large bm/bn
    (amortize weight streaming), shrink until the working set fits.
    packed_n: the N-axis operand (W, or W2 in the cascade) is
    nibble-packed, so bn stays a multiple of 256 (whole pack tiles, each
    unpacking from a lane-aligned 128-byte half) and the working set
    counts the unpacked operand. packed_r:
    the cascade's W1 is packed along R (affects only the vmem model; R is
    never tiled). Callers must only set packed_* for axes where
    `packed_pad_ok` holds — qmm/lrmm demote the rest to carrier first —
    so the bn_floor=256 constraint never inflates a small-N/R launch.
    """
    bn_floor = 256 if packed_n else 128
    bm = min(_round_up(m, 8), 256)
    bk = min(_round_up(k, 128), 512)
    # packed N blocks must be multiples of 256 (half-block lane-aligned),
    # and halving from 512 keeps them so; carrier blocks align to 128
    bn = max(min(_round_up(n, bn_floor), 512), bn_floor)
    if r is not None:
        fits = lambda: _lr.vmem_bytes(bm, bk, bn, r, w1_packed=packed_r,
                                      w2_packed=packed_n)     # noqa: E731
    else:
        fits = lambda: _qm.vmem_bytes(bm, bk, bn,
                                      w_packed=packed_n)      # noqa: E731
    while fits() > budget and bm > 8:
        bm //= 2
    while fits() > budget and bn > bn_floor:
        bn //= 2
    while fits() > budget and bk > 128:
        bk //= 2
    return bm, bk, bn


def _pad2(x, m0, m1):
    p0, p1 = m0 - x.shape[0], m1 - x.shape[1]
    if p0 == 0 and p1 == 0:
        return x
    return jnp.pad(x, ((0, p0), (0, p1)))


@functools.partial(
    jax.jit,
    static_argnames=("use_kernel", "interpret", "blocks", "out_dtype"),
)
def qmm(
    x: jax.Array,
    w: QuantizedTensor,
    *,
    use_kernel: bool = True,
    interpret: bool | None = None,
    blocks: tuple | None = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """y = dequant(quant(x)) @ dequant(w) — WxAy dense linear.

    x: (..., K) float; w: QuantizedTensor (K, N) with per-column scales.
    The activation word length (Ay) and the packed/carrier layout ride on
    `w` as pytree aux data, so they are static here: the clamp range is
    qmax(w.act_wl), and a packed w streams its nibble bytes straight into
    the kernel.
    """
    if interpret is None:
        interpret = not on_tpu()
    lead = x.shape[:-1]
    k, n = w.shape                     # logical, even when packed
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    xq, sx = quantize_acts(x2, qmax(w.act_wl))
    sw = w.scale.reshape(1, n)

    if not use_kernel:
        wv = unpack_int4(w.values) if w.packed else w.values
        y = _ref.quant_matmul_ref(xq, sx, wv, sw)
        return y.astype(out_dtype).reshape(*lead, n)

    w_packed, wval = w.packed, w.values
    if w_packed and not packed_pad_ok(n):
        wval, w_packed = unpack_int4(wval), False  # exact; see packed_pad_ok
    bm, bk, bn = blocks or choose_blocks(m, k, n, packed_n=w_packed)
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    wv = _pad2(wval, kp, np_ // 2 if w_packed else np_)
    y = _qm.quant_matmul(
        _pad2(xq, mp, kp), _pad2(sx, mp, 1),
        wv, _pad2(sw, 1, np_),
        bm=bm, bk=bk, bn=bn, out_dtype=out_dtype, interpret=interpret,
        w_packed=w_packed,
    )[:m, :n]
    return y.reshape(*lead, n)


@functools.partial(
    jax.jit,
    static_argnames=("use_kernel", "interpret", "blocks", "out_dtype", "fused"),
)
def lrmm(
    x: jax.Array,
    lr: LowRankQ,
    *,
    use_kernel: bool = True,
    interpret: bool | None = None,
    blocks: tuple | None = None,
    out_dtype=jnp.float32,
    fused: bool = True,
) -> jax.Array:
    """y = ((quant(x) @ W1') @ W2') — the ITERA low-rank linear.

    fused=True  -> Cascade engine analog (single kernel, T pinned in VMEM)
    fused=False -> Single engine analog (two quant_matmul launches; T makes
                   an HBM round-trip — kept for the engine comparison bench)

    Activation word length (input quantization AND the phase-boundary
    requant clamp) comes from lr.act_wl; packed factors stream packed.
    """
    if interpret is None:
        interpret = not on_tpu()
    lead = x.shape[:-1]
    k, r = lr.w1.shape                 # logical
    _, n = lr.w2.shape
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    act_qm = qmax(lr.act_wl)
    xq, sx = quantize_acts(x2, act_qm)
    s1 = lr.w1.scale.reshape(1, r)
    s2 = lr.w2.scale.reshape(r, 1)

    if not use_kernel:
        w1v = unpack_int4(lr.w1.values) if lr.w1.packed else lr.w1.values
        w2v = unpack_int4(lr.w2.values) if lr.w2.packed else lr.w2.values
        y = _ref.lowrank_qmm_ref(xq, sx, w1v, s1, w2v, s2, act_qm)
        return y.astype(out_dtype).reshape(*lead, n)

    # demote packed factors whose axis would pad fatter than its carrier
    # (exact nibble unpack; see packed_pad_ok) — W1 packs along R, W2
    # along N
    w1_packed, w1v = lr.w1.packed, lr.w1.values
    if w1_packed and not packed_pad_ok(r):
        w1v, w1_packed = unpack_int4(w1v), False
    w2_packed, w2v = lr.w2.packed, lr.w2.values
    if w2_packed and not packed_pad_ok(n):
        w2v, w2_packed = unpack_int4(w2v), False

    if not fused:
        # Single-engine schedule: T leaves the chip between the two
        # matmuls — and both phases run the Pallas kernel, so the engine
        # comparison bench measures kernel-vs-kernel, not ref-vs-kernel.
        bm1, bk1, bn1 = choose_blocks(m, k, r, packed_n=w1_packed)
        mp, kp = _round_up(m, bm1), _round_up(k, bk1)
        rp1 = _round_up(r, bn1)
        t = _qm.quant_matmul(
            _pad2(xq, mp, kp), _pad2(sx, mp, 1),
            _pad2(w1v, kp, rp1 // 2 if w1_packed else rp1),
            _pad2(s1, 1, rp1),
            bm=bm1, bk=bk1, bn=bn1, interpret=interpret,
            w_packed=w1_packed,
        )[:m, :r]
        t = t * s2.reshape(1, -1)
        tq, st = quantize_acts(t, act_qm)
        bm, bk, bn = blocks or choose_blocks(m, r, n, packed_n=w2_packed)
        mp, rp, np_ = _round_up(m, bm), _round_up(r, bk), _round_up(n, bn)
        y = _qm.quant_matmul(
            _pad2(tq, mp, rp), _pad2(st, mp, 1),
            _pad2(w2v, rp, np_ // 2 if w2_packed else np_),
            jnp.ones((1, np_), jnp.float32),
            bm=bm, bk=bk, bn=bn, out_dtype=out_dtype, interpret=interpret,
            w_packed=w2_packed,
        )[:m, :n]
        return y.reshape(*lead, n)

    # R is held whole in VMEM; a packed W1 needs rp // 2 lane-aligned.
    rp = _round_up(r, 256 if w1_packed else 128)
    bm, bk, bn = blocks or choose_blocks(m, k, n, rp,
                                         packed_n=w2_packed,
                                         packed_r=w1_packed)
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    y = _lr.lowrank_qmm(
        _pad2(xq, mp, kp), _pad2(sx, mp, 1),
        _pad2(w1v, kp, rp // 2 if w1_packed else rp),
        _pad2(jnp.pad(s1, ((0, 0), (0, rp - r)), constant_values=1.0), 1, rp),
        _pad2(w2v, rp, np_ // 2 if w2_packed else np_),
        _pad2(jnp.pad(s2, ((0, rp - r), (0, 0)), constant_values=1.0), rp, 1),
        bm=bm, bk=bk, bn=bn, out_dtype=out_dtype, interpret=interpret,
        w1_packed=w1_packed, w2_packed=w2_packed, act_qmax=act_qm,
    )[:m, :n]
    return y.reshape(*lead, n)


GMM_TILE = 128      # rows per lowrank_gmm tile


@functools.partial(
    jax.jit, static_argnames=("use_kernel", "interpret", "out_dtype"))
def lrgmm(
    x: jax.Array,
    lr: LowRankQ,
    offsets: jax.Array,
    *,
    use_kernel: bool = True,
    interpret: bool | None = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Grouped ITERA linear: row i of x (M, K), sorted by expert, through
    expert e's cascade where offsets[e] <= i < offsets[e+1]; `lr` holds
    every expert's factors with a leading expert axis. Rows at or past
    offsets[E] come back unspecified (zeros on the reference path).

    use_kernel=False is the jnp oracle: every expert's `lowrank_qmm_ref`
    over all rows, each row kept from its own expert (E x the compute;
    the kernel's arithmetic bit for bit)."""
    if interpret is None:
        interpret = not on_tpu()
    e, k, r = lr.w1.shape
    n = lr.w2.shape[-1]
    m = x.shape[0]
    act_qm = qmax(lr.act_wl)
    xq, sx = quantize_acts(x, act_qm)
    s1 = lr.w1.scale.reshape(e, 1, r)
    s2 = lr.w2.scale.reshape(e, r, 1)
    rows = jnp.arange(m)[:, None]

    if not use_kernel:
        w1v = unpack_int4(lr.w1.values) if lr.w1.packed else lr.w1.values
        w2v = unpack_int4(lr.w2.values) if lr.w2.packed else lr.w2.values

        def one(y, ex):
            w1, a1, w2, a2, lo, hi = ex
            ye = _ref.lowrank_qmm_ref(xq, sx, w1, a1, w2, a2, act_qm)
            return jnp.where((rows >= lo) & (rows < hi), ye, y), None

        y, _ = jax.lax.scan(one, jnp.zeros((m, n), jnp.float32),
                            (w1v, s1, w2v, s2, offsets[:-1], offsets[1:]))
        return y.astype(out_dtype)

    w1_packed, w1v = lr.w1.packed, lr.w1.values
    if w1_packed and not packed_pad_ok(r):
        w1v, w1_packed = unpack_int4(w1v), False
    w2_packed, w2v = lr.w2.packed, lr.w2.values
    if w2_packed and not packed_pad_ok(n):
        w2v, w2_packed = unpack_int4(w2v), False
    rp = _round_up(r, 256 if w1_packed else 128)
    kp, np_ = _round_up(k, 128), _round_up(n, 256 if w2_packed else 128)
    mp = _round_up(m, GMM_TILE)

    def pad3(a, d1, d2, value=0):
        return jnp.pad(a, ((0, 0), (0, d1 - a.shape[1]),
                           (0, d2 - a.shape[2])), constant_values=value)

    y = _gmm.lowrank_gmm(
        _pad2(xq, mp, kp), _pad2(sx, mp, 1),
        pad3(w1v, kp, rp // 2 if w1_packed else rp),
        pad3(s1, 1, rp, 1.0),
        pad3(w2v, rp, np_ // 2 if w2_packed else np_),
        pad3(s2, rp, 1, 1.0),
        offsets, tm=GMM_TILE, out_dtype=out_dtype, interpret=interpret,
        w1_packed=w1_packed, w2_packed=w2_packed, act_qmax=act_qm,
    )
    return y[:m, :n]


def qgmm(x: jax.Array, w: QuantizedTensor, offsets: jax.Array, *,
         out_dtype=jnp.float32) -> jax.Array:
    """Grouped WxAy linear: row i of x (M, K), sorted by expert, through
    expert e's codes where offsets[e] <= i < offsets[e+1]; `w` holds
    every expert's codes (E, K, N) with per-column scales (E, 1, N).
    `qmm`'s reference arithmetic (per-row activation codes, an integer
    product, both scales) as one grouped product, so the work follows
    the rows and not E x M; rows at or past offsets[E] come back zero."""
    e = offsets.shape[0] - 1
    m, n = x.shape[0], w.shape[-1]
    xq, sx = quantize_acts(x, qmax(w.act_wl))
    wv = unpack_int4(w.values) if w.packed else w.values
    # integer codes are exact at the default precision, and the grouped
    # product's TPU kernel refuses "highest"
    acc = jax.lax.ragged_dot(xq, wv.astype(jnp.int8), jnp.diff(offsets),
                             precision=jax.lax.Precision.DEFAULT,
                             preferred_element_type=jnp.int32)
    grp = jnp.minimum(jnp.searchsorted(offsets[1:], jnp.arange(m),
                                       side="right"), e - 1)
    sw = w.scale.reshape(e, n)[grp]
    return (acc.astype(jnp.float32) * sx * sw).astype(out_dtype)


def qmm_hbm_bytes(m: int, w: QuantizedTensor,
                  blocks: tuple | None = None) -> int:
    """Modeled HBM bytes one qmm(x, w) launch moves for an (m, K) input —
    the bytes-moved column in BENCH_kernels.json. Uses the same block
    choice AND the same packed-axis demotion as the dispatch above, on
    the padded shapes."""
    k, n = w.shape
    packed = w.packed and packed_pad_ok(n)
    bm, bk, bn = blocks or choose_blocks(m, k, n, packed_n=packed)
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    total = _qm.hbm_bytes_moved(mp, kp, np_, bm, bn, w_packed=packed)
    if w.packed and not packed:
        total += k * n * 3 // 2     # fallback demotion: packed read + write
    return total


def lrmm_hbm_bytes(m: int, lr: LowRankQ,
                   blocks: tuple | None = None) -> int:
    """Modeled HBM bytes one fused lrmm(x, lr) launch moves (with the
    dispatch's packed-axis demotion applied, so the model prices what
    actually streams)."""
    k, r = lr.w1.shape
    _, n = lr.w2.shape
    w1p = lr.w1.packed and packed_pad_ok(r)
    w2p = lr.w2.packed and packed_pad_ok(n)
    rp = _round_up(r, 256 if w1p else 128)
    bm, bk, bn = blocks or choose_blocks(m, k, n, rp,
                                         packed_n=w2p, packed_r=w1p)
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    total = _lr.hbm_bytes_moved(mp, kp, np_, rp, bm,
                                w1_packed=w1p, w2_packed=w2p)
    if lr.w1.packed and not w1p:
        total += k * r * 3 // 2     # fallback demotion: packed read + write
    if lr.w2.packed and not w2p:
        total += r * n * 3 // 2
    return total

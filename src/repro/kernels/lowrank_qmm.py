"""Fused cascade low-rank quantized matmul — TPU analog of the paper's
*Cascade SVD MatMul Engine* (§V-B, Fig. 6 right).

Computes Y = ((Xq @ W1q) @ W2q) with the (bm x R) intermediate tile held in
VMEM for its whole lifetime — the paper's constraint that "the entire
M_t x R tile of intermediate results [is buffered] on-chip", which is the
source of the cascade engine's bandwidth advantage (no HBM round-trip for
X·W1).

Mechanically this is a two-phase sequential grid: for each M-row-block i the
inner grid axis s runs K/bk accumulation steps (phase 1: T += Xq_blk @ W1_blk)
followed by N/bn emission steps (phase 2: Y_blk = Tq @ W2_blk). The
intermediate is re-quantized to an int8 carrier once, at the phase boundary
— the paper's Ay intermediate quantization between the two engines, clamped
to qmax(act_wl) (`act_qmax`; 127 == the historical A8 behavior) — with the
per-R scales of W2 (s2) folded into T before requantization so phase 2 needs
only a per-row scale.

Sub-8-bit residency: both factors may arrive *packed* (two int4 nibbles per
byte along their last axis — W1 along R, W2 along N; core.quant.pack_int4
layout). The packed blocks are what DMA HBM→VMEM; the kernel sign-extends
on-chip right before each MXU dot. Unpacking is exact, so packed and
carrier runs are bit-identical.

dimension_semantics = ("parallel", "arbitrary"): M-blocks are independent;
the s axis is order-dependent (accumulate -> requant -> emit).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant_matmul import int8_dot, tile_bytes, unpack_int4_block


def _kernel(
    xq_ref, sx_ref, w1_ref, s1_ref, w2_ref, s2_ref,  # inputs
    o_ref,                                           # output
    tacc_ref, tq_ref, st_ref,                        # scratch
    *, k_blocks, n_blocks, w1_packed, w2_packed, act_qmax,
):
    s = pl.program_id(1)

    # ---- phase 1: accumulate T = Xq @ W1q over K blocks -------------------
    @pl.when(s == 0)
    def _init():
        tacc_ref[...] = jnp.zeros_like(tacc_ref)

    @pl.when(s < k_blocks)
    def _accum():
        w1 = unpack_int4_block(w1_ref[...]) if w1_packed else w1_ref[...]
        tacc_ref[...] += int8_dot(xq_ref[...], w1)

    # ---- phase boundary: dequant, fold s2, requantize per row to int8 -----
    @pl.when(s == k_blocks)
    def _requant():
        t = tacc_ref[...].astype(jnp.float32)
        t = t * sx_ref[...] * s1_ref[...] * s2_ref[...].reshape(1, -1)
        absmax = jnp.max(jnp.abs(t), axis=-1, keepdims=True)
        st = jnp.where(absmax > 0, absmax / act_qmax, 1.0)
        tq_ref[...] = jnp.clip(jnp.round(t / st),
                               -act_qmax, act_qmax).astype(jnp.int8)
        st_ref[...] = st.astype(jnp.float32)

    # ---- phase 2: emit Y n-block = Tq @ W2q ------------------------------
    @pl.when(s >= k_blocks)
    def _emit():
        w2 = unpack_int4_block(w2_ref[...]) if w2_packed else w2_ref[...]
        acc = int8_dot(tq_ref[...], w2)
        o_ref[...] = (acc.astype(jnp.float32) * st_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "bn", "interpret", "out_dtype",
                     "w1_packed", "w2_packed", "act_qmax"),
)
def lowrank_qmm(
    xq: jax.Array,
    sx: jax.Array,
    w1q: jax.Array,
    s1: jax.Array,
    w2q: jax.Array,
    s2: jax.Array,
    *,
    bm: int = 256,
    bk: int = 512,
    bn: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
    w1_packed: bool = False,
    w2_packed: bool = False,
    act_qmax: int = 127,
) -> jax.Array:
    """Y[M,N] = dequant-cascade((Xq @ W1q) @ W2q).

    xq: (M, K) int8, sx: (M, 1) f32      — quantized activations
    w1q: (K, R) int8, s1: (1, R) f32     — ITERA factor 1 (R kept whole in VMEM)
    w2q: (R, N) int8, s2: (R, 1) f32     — ITERA factor 2
    w1_packed / w2_packed: the factor array carries packed W4 nibbles along
    its last axis (R resp. N) — shapes become (K, R//2) / (R, N//2); scales
    stay unpacked. act_qmax: clamp of the phase-boundary requant,
    qmax(act_wl).
    Dims must divide by blocks; R is not tiled (ranks are ≤ ~1k by design —
    that is the whole point of the decomposition).
    """
    m, k = xq.shape
    k2, r1 = w1q.shape
    r = r1 * 2 if w1_packed else r1
    r2, nw = w2q.shape
    n = nw * 2 if w2_packed else nw
    assert k == k2 and r == r2, (xq.shape, w1q.shape, w2q.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (
        (m, k, n), (bm, bk, bn))
    # packed half-blocks must stay 128-lane aligned: W2's N half-block
    # (ops keeps bn >= 256) and W1's untiled R half-width (ops pads R to
    # a multiple of 256 when W1 is packed)
    assert not w2_packed or bn % 256 == 0, (
        f"packed W2 needs bn % 256 == 0, got bn={bn}")
    assert not w1_packed or r % 256 == 0, (
        f"packed W1 needs padded R % 256 == 0, got R={r}")
    bnw = bn // 2 if w2_packed else bn

    k_blocks, n_blocks = k // bk, n // bn
    grid = (m // bm, k_blocks + n_blocks)

    def nmap(i, s):
        # during phase 1 park on block 0; phase 2 walks the N blocks
        return jnp.maximum(s - k_blocks, 0)

    return pl.pallas_call(
        functools.partial(_kernel, k_blocks=k_blocks, n_blocks=n_blocks,
                          w1_packed=w1_packed, w2_packed=w2_packed,
                          act_qmax=act_qmax),
        grid=grid,
        in_specs=[
            # phase-1 operands: clamp to the last K block during phase 2
            pl.BlockSpec((bm, bk),
                         lambda i, s: (i, jnp.minimum(s, k_blocks - 1))),
            pl.BlockSpec((bm, 1), lambda i, s: (i, 0)),
            pl.BlockSpec((bk, r1),
                         lambda i, s: (jnp.minimum(s, k_blocks - 1), 0)),
            pl.BlockSpec((1, r), lambda i, s: (0, 0)),
            # phase-2 operands: park on block 0 during phase 1
            pl.BlockSpec((r, bnw), lambda i, s: (0, nmap(i, s))),
            pl.BlockSpec((r, 1), lambda i, s: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, s: (i, nmap(i, s))),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, r), jnp.int32),   # T accumulator
            pltpu.VMEM((bm, r), jnp.int8),    # requantized T
            pltpu.VMEM((bm, 1), jnp.float32), # per-row T scale
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="lowrank_qmm",
    )(xq, sx, w1q, s1, w2q, s2)


def vmem_bytes(bm: int, bk: int, bn: int, r: int, *,
               w1_packed: bool = False, w2_packed: bool = False) -> int:
    """Scoped VMEM one launch needs (constraint for the DSE), counted
    like `quant_matmul.vmem_bytes`: double-buffered tile-padded blocks,
    the T scratch, and one int8 MXU operand per factor (the unpacked
    copy when packed — packing buys HBM bandwidth, not VMEM)."""
    blocks = (tile_bytes(bm, bk, 1)                             # xq
              + tile_bytes(bm, 1, 4)                            # sx
              + tile_bytes(bk, r // 2 if w1_packed else r, 1)   # w1
              + tile_bytes(1, r, 4)                             # s1
              + tile_bytes(r, bn // 2 if w2_packed else bn, 1)  # w2
              + tile_bytes(r, 1, 4)                             # s2
              + tile_bytes(bm, bn, 4))                          # out f32
    scratch = (tile_bytes(bm, r, 4)          # T accumulator int32
               + tile_bytes(bm, r, 1)        # Tq int8
               + tile_bytes(bm, 1, 4))       # per-row T scale
    operands = tile_bytes(bk, r, 1) + tile_bytes(r, bn, 1)
    return 2 * blocks + scratch + operands


def hbm_bytes_moved(m: int, k: int, n: int, r: int, bm: int, *,
                    w1_packed: bool = False, w2_packed: bool = False) -> int:
    """Modeled HBM traffic of one fused cascade launch.

    Only the M row-blocking matters: X streams once (consecutive phase-2
    steps revisit the same X block, which stays resident); both factors
    are re-fetched per M row-block; the (bm x R) intermediate never
    leaves VMEM — the cascade's defining property; the f32 output is
    written once. bk/bn change nothing here, so they are not parameters.
    """
    m_rep = max(m // bm, 1)
    w1_bytes = (k * r // 2) if w1_packed else k * r
    w2_bytes = (r * n // 2) if w2_packed else r * n
    return (
        m * k                      # Xq int8, once
        + m * 4                    # sx
        + (w1_bytes + w2_bytes) * m_rep   # factors, once per M row
        + (r + r) * 4 * m_rep      # s1, s2
        + m * n * 4                # Y f32 out
    )

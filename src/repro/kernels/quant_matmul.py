"""Dense quantized matmul Pallas kernel — TPU analog of the paper's baseline
MatMul engine (§V-A).

The paper's engine tiles (M_t, N_t) spatially with K_f-parallel dot products;
on TPU the MXU is the inner 128x128 tile and the BlockSpec factors
(bm, bk, bn) play the role of (M_t, K_f, N_t). The grid accumulates over the
K dimension in an int32 VMEM scratch (output-stationary, exactly like the
paper's output-stationary PE array).

Inputs are pre-quantized int8 codes with per-row activation scales and
per-column weight scales (symmetric, matching core/quant.py). Sub-8-bit
weights arrive either as int8 carriers whose values are range-limited
(W6/W8) or — the paper's actual memory win — as *packed* W4 (two nibble
codes per byte along N, `w_packed=True`): the packed block is what DMAs
HBM→VMEM, and the kernel sign-extends the nibbles on-chip right before the
int8xint8->int32 MXU dot, so HBM moves wl/8 bytes per weight while the MXU
still sees int8. Unpacking is exact, so packed and carrier runs are
bit-identical.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import PACK_TILE

_HALF = PACK_TILE // 2


def unpack_int4_block(wp):
    """Sign-extend a packed-nibble int8 block (B, C) -> int8 codes (B, 2C).

    C is a multiple of 128: each 128-byte half-tile holds one
    core.quant.pack_int4 tile, column j in the low nibble and column
    j + 128 in the high nibble. Each half-tile unpacks with two int32
    shift pairs (Mosaic lowers sub-word shifts through 32-bit lanes
    anyway, and interpret mode matches exactly), and the two halves sit
    side by side on 128-lane boundaries, so no lane ever moves. Working
    half-tile by half-tile bounds the int32 temporaries to one
    (B, 128) tile at a time.
    """
    parts = []
    for t in range(0, wp.shape[-1], _HALF):
        w32 = wp[:, t:t + _HALF].astype(jnp.int32)
        parts += [((w32 << 28) >> 28).astype(jnp.int8),     # low nibble
                  ((w32 << 24) >> 28).astype(jnp.int8)]     # high nibble
    return jnp.concatenate(parts, axis=-1)


def int8_dot(a, b):
    """(M, K) @ (K, N) int8 codes -> int32 on the MXU. Integer products
    are exact at any precision, so the precision is pinned: a global
    `jax_default_matmul_precision` of "highest" would otherwise ask
    Mosaic for an f32 contraction, which it refuses for int8 operands."""
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.int32)


def _kernel(xq_ref, sx_ref, wq_ref, sw_ref, o_ref, acc_ref, *, k_blocks,
            w_packed):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    wq = unpack_int4_block(wq_ref[...]) if w_packed else wq_ref[...]
    acc_ref[...] += int8_dot(xq_ref[...], wq)

    @pl.when(k == k_blocks - 1)
    def _finish():
        o_ref[...] = (
            acc_ref[...].astype(jnp.float32) * sx_ref[...] * sw_ref[...]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "bn", "interpret", "out_dtype", "w_packed"),
)
def quant_matmul(
    xq: jax.Array,
    sx: jax.Array,
    wq: jax.Array,
    sw: jax.Array,
    *,
    bm: int = 256,
    bk: int = 512,
    bn: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
    w_packed: bool = False,
) -> jax.Array:
    """Y[M,N] = (Xq·sx) @ (Wq·sw) with int8 MXU arithmetic.

    w_packed=True: wq is (K, N//2) packed nibbles (core.quant.pack_int4
    layout along N); the kernel unpacks in VMEM. bn must then be even with
    bn//2 lane-aligned — `ops.choose_blocks` keeps bn >= 256 for packed
    weights. Shapes must be divisible by the block factors — `ops.py`
    handles padding (zero bytes unpack to zero codes, so padding in the
    packed domain is exact).
    """
    m, k = xq.shape
    k2, nw = wq.shape
    n = nw * 2 if w_packed else nw
    assert k == k2, (xq.shape, wq.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (
        (m, k, n), (bm, bk, bn))
    # packed half-blocks must stay 128-lane aligned (choose_blocks keeps
    # bn >= 256; caller-supplied blocks are checked here, not trusted)
    assert not w_packed or bn % 256 == 0, (
        f"packed weights need bn % 256 == 0, got bn={bn}")
    bnw = bn // 2 if w_packed else bn

    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_kernel, k_blocks=k // bk, w_packed=w_packed),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((bk, bnw), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="quant_matmul",
    )(xq, sx, wq, sw)


def tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) buffer in the TPU's native tiling:
    rows pad to the dtype's sublane tile (8 for 4-byte, 32 for 1-byte
    elements) and columns to 128 lanes."""
    sub = 8 * (4 // itemsize)
    return (-(-rows // sub) * sub) * (-(-cols // 128) * 128) * itemsize


def vmem_bytes(bm: int, bk: int, bn: int, *, w_packed: bool = False) -> int:
    """Scoped VMEM one launch needs (the BRAM analog, DESIGN.md §2).

    The Pallas pipeline double-buffers every input and output block, at
    tile-padded size; the int32 accumulator is single scratch. One int8
    (bk, bn) weight block on top: the unpacked copy the MXU reads when
    the weight is packed, and the operand copy the compiler makes for
    wide carrier blocks. Checked as an upper bound against the TPU
    compiler (tests/test_tpu_compile.py compiles with exactly this
    limit): packing halves the streamed block, not the MXU operand.
    """
    blocks = (tile_bytes(bm, bk, 1)                          # xq
              + tile_bytes(bm, 1, 4)                         # sx
              + tile_bytes(bk, bn // 2 if w_packed else bn, 1)  # wq
              + tile_bytes(1, bn, 4)                         # sw
              + tile_bytes(bm, bn, 4))                       # out f32
    return 2 * blocks + tile_bytes(bm, bn, 4) + tile_bytes(bk, bn, 1)


def hbm_bytes_moved(m: int, k: int, n: int, bm: int, bn: int,
                    *, w_packed: bool = False) -> int:
    """Modeled HBM traffic of one quant_matmul launch.

    Per the grid order (i, j, kk): each X block is re-fetched for every
    N block column, each W block for every M block row; scales ride along
    with the same reuse; the f32 output is written once. Only bm/bn set
    the reuse counts — bk is not a parameter because it changes nothing
    here. This is the number the bytes-moved benchmark column reports —
    the W term is what packing halves.
    """
    n_rep = max(n // bn, 1)
    m_rep = max(m // bm, 1)
    w_bytes = (k * n // 2) if w_packed else k * n
    return (
        m * k * n_rep              # Xq int8, once per N column
        + m * 4 * n_rep            # sx
        + w_bytes * m_rep          # W (packed or carrier), once per M row
        + n * 4 * m_rep            # sw
        + m * n * 4                # Y f32 out
    )

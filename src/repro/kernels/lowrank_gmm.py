"""Grouped ITERA expert kernel: `lowrank_qmm`'s cascade over the rows of
many experts at once, each row against its own expert's factors.

The routed (row, expert) pairs of a MoE layer arrive sorted by expert,
so expert e owns the contiguous rows [offsets[e], offsets[e+1]). The
grid walks tiles: one tile is (expert g, row block m) for every row
block that expert's rows touch, so a row block that straddles a group
boundary is visited once per group. Per tile, with the expert's whole
factors in VMEM (ranks are small by design, as in `lowrank_qmm`):

  phase 1: T = Xq_blk @ W1q[g]                (int32, exact, all of K)
  requant: T~ = T * sx * s1[g] * s2[g]^T, rowquant to +-act_qmax
  phase 2: Y_blk = (Tq @ W2q[g]) * sT

— the same two chained W4-code products with the per-row requantization
between them, in the same order of operations, so a row's result is bit
for bit `lowrank_qmm`'s on its expert. Rows of the block outside group g
keep what the tile that owns them wrote (the output block stays in VMEM
across consecutive tiles of one row block).

Group offsets and the tile table arrive by scalar prefetch. An expert
with no rows has no tile, so its factors are never fetched; consecutive
tiles of one expert keep its factors resident (an unchanged block index
issues no copy), so each expert with rows moves its weights once. Tiles
past the last real one repeat its indices and compute nothing. The
compute is proportional to the routed pairs (whole row tiles per group),
not to experts x rows. Factors may arrive packed (W1 along R, W2 along
N) and unpack in VMEM, as in `lowrank_qmm`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant_matmul import int8_dot, unpack_int4_block


def tile_table(offsets, m: int, tm: int):
    """(tile_group, tile_row, num_tiles) of the grid: for each expert in
    order, the row blocks [first, last] its rows [offsets[e],
    offsets[e+1]) touch; entries past num_tiles repeat the last real
    tile. Static length m // tm + E - 1 (every group boundary can split
    one more block)."""
    e = offsets.shape[0] - 1
    starts, ends = offsets[:-1], offsets[1:]
    first = starts // tm
    count = jnp.where(ends > starts, (ends - 1) // tm - first + 1, 0)
    cum = jnp.cumsum(count)
    num = cum[-1]
    n_max = m // tm + e - 1
    t = jnp.minimum(jnp.arange(n_max), jnp.maximum(num - 1, 0))
    g = jnp.minimum(jnp.searchsorted(cum, t, side="right"), e - 1)
    row = jnp.clip(first[g] + t - (cum[g] - count[g]), 0, m // tm - 1)
    return g.astype(jnp.int32), row.astype(jnp.int32), num.reshape(1)


def _kernel(go_ref, tg_ref, tr_ref, nt_ref,          # scalar prefetch
            xq_ref, sx_ref, w1_ref, s1_ref, w2_ref, s2_ref, o_ref,
            *, tm, w1_packed, w2_packed, act_qmax):
    t = pl.program_id(0)

    @pl.when(t < nt_ref[0])
    def _tile():
        g = tg_ref[t]
        rows = tr_ref[t] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (rows >= go_ref[g]) & (rows < go_ref[g + 1])
        w1 = unpack_int4_block(w1_ref[...]) if w1_packed else w1_ref[...]
        tacc = int8_dot(xq_ref[...], w1)
        tf = tacc.astype(jnp.float32)
        tf = tf * sx_ref[...] * s1_ref[...] * s2_ref[...].reshape(1, -1)
        absmax = jnp.max(jnp.abs(tf), axis=-1, keepdims=True)
        st = jnp.where(absmax > 0, absmax / act_qmax, 1.0)
        tq = jnp.clip(jnp.round(tf / st), -act_qmax,
                      act_qmax).astype(jnp.int8)
        w2 = unpack_int4_block(w2_ref[...]) if w2_packed else w2_ref[...]
        y = (int8_dot(tq, w2).astype(jnp.float32)
             * st.astype(jnp.float32)).astype(o_ref.dtype)
        o_ref[...] = jnp.where(mine, y, o_ref[...])


@functools.partial(
    jax.jit, static_argnames=("tm", "out_dtype", "interpret", "w1_packed",
                              "w2_packed", "act_qmax"))
def lowrank_gmm(xq, sx, w1q, s1, w2q, s2, offsets, *, tm: int = 128,
                out_dtype=jnp.float32, interpret: bool = False,
                w1_packed: bool = False, w2_packed: bool = False,
                act_qmax: int = 127):
    """Y[M, N] for rows sorted by expert, row i on expert e where
    offsets[e] <= i < offsets[e+1].

    xq (M, K) int8, sx (M, 1) f32; w1q (E, K, R[/2]) int8, s1 (E, 1, R);
    w2q (E, R, N[/2]) int8, s2 (E, R, 1); offsets (E+1,) int32. M is a
    multiple of tm; rows at or past offsets[E] come back unspecified.
    """
    m, k = xq.shape
    e, _, r1 = w1q.shape
    r = s1.shape[-1]
    nw = w2q.shape[-1]
    n = nw * 2 if w2_packed else nw
    assert m % tm == 0, (m, tm)
    # packed factors unpack in whole 256-column tiles (128-lane halves):
    # W1 along its untiled R, W2 along its untiled N
    assert not w1_packed or r % 256 == 0, (
        f"packed W1 needs R % 256 == 0, got R={r}")
    assert not w2_packed or n % 256 == 0, (
        f"packed W2 needs N % 256 == 0, got N={n}")
    tg, tr, nt = tile_table(offsets.astype(jnp.int32), m, tm)
    sq = pl.Squeezed()

    def rows(t, go, tg, tr, nt):
        return (tr[t], 0)

    def expert(t, go, tg, tr, nt):
        return (tg[t], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,   # offsets, tile group, tile row, tile count
        grid=(tg.shape[0],),
        in_specs=[
            pl.BlockSpec((tm, k), rows),
            pl.BlockSpec((tm, 1), rows),
            pl.BlockSpec((sq, k, r1), expert),
            pl.BlockSpec((sq, 1, r), expert),
            pl.BlockSpec((sq, r, nw), expert),
            pl.BlockSpec((sq, r, 1), expert),
        ],
        out_specs=pl.BlockSpec((tm, n), rows),
    )
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, w1_packed=w1_packed,
                          w2_packed=w2_packed, act_qmax=act_qmax),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="lowrank_gmm",
    )(offsets.astype(jnp.int32), tg, tr, nt, xq, sx, w1q, s1, w2q, s2)

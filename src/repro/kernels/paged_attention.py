"""FlashAttention-style Pallas paged-attention kernel for the unified
serving step — the KV-cache counterpart of the packed-W4 discipline in
`quant_matmul.py`: stream only the bytes that hold real data, and do any
sub-8-bit decoding on-chip, right before the MXU dot.

The jnp serving path (`models.attention.span_attention_paged`, kept as the
selectable oracle) gathers the ENTIRE logical pool view
`pool["k"][block_table] -> (B, MB*bs, Hk, Dh)` every step, every layer:
O(max-context) HBM traffic and a full dense materialization regardless of
how much context each row actually holds — and with int8 KV it dequantizes
that whole window in jnp before the dot. This kernel instead:

  * runs a `(B, Hk, MB)` grid — one program per (row, kv-head, table slot)
    — with the block table, `ctx_lens`, `q_lens`, and the per-row
    valid-block counts (`runtime.kvblocks.valid_block_counts`) scalar-
    prefetched into SMEM, so the BlockSpec index maps can chase the table;
  * walks the block table and fetches ONLY blocks that hold valid context:
    grid step j DMAs physical block `block_table[r, min(j, nb[r]-1)]`, so
    every step past a row's valid count re-addresses the block already
    resident in VMEM — the Pallas pipeline skips the re-fetch. Trash-
    block-0 padding entries past a row's valid count are never addressed
    (pads sit at `j >= nb`); idle rows (`q_lens == 0`, `nb == 0`) clamp
    onto `block_table[r, 0]` — the trash block — so they fetch that one
    block and compute nothing (`stream_hbm_bytes` charges exactly that);
  * computes online softmax over (W-span queries x block keys) with the
    in-span causal mask `slot <= ctx_lens[r] + i` fused into the score
    tile (key position `j*bs + col` vs query position `ctx + row // G`),
    in f32 running (m, l, acc) VMEM scratch;
  * dequantizes int8 K/V tiles in VMEM right before the dot — the scale
    planes DMA alongside the codes, and the `code.astype(q.dtype) *
    scale.astype(q.dtype)` order mirrors the jnp oracle exactly — so int8
    KV streams 1 byte/element + a thin scale plane instead of a dense
    dequantized bf16 window;
  * accumulates the output per (row, head) without ever materializing the
    `(B, MB*bs, Hk, Dh)` gather.

GQA runs grouped: the G query heads of one kv head are flattened into the
query-row axis `(W*G, Dh)`, so K/V tiles are fetched once per kv head, not
per query head.

Like the matmul kernels, this runs compiled on TPU and bit-faithfully
under `interpret=True` on CPU (how the identity tests drive it).

Tensor parallelism: the kernel needs no TP awareness. Under the
shard_map serving step (api.engine with a "model"-axis mesh) it is
invoked per shard with the PER-SHARD config — `Hk` here is
num_kv_heads / tp and the pool ref is that shard's head-slice
(runtime.kvblocks.pool_pspecs), so the grid is (B, Hk/tp, MB) and each
chip streams only its own heads' KV blocks. Attention is head-local,
so no collective touches the kernel; the single psum per attention
boundary happens outside, after the wo projection
(models.transformer.unified_step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -2.3819763e38  # large negative for masking in f32 (models.attention)


def _softcap(s, cap: float):
    return (cap * jnp.tanh(s / cap)) if cap > 0 else s


def _kernel(nb_ref, bt_ref, ctx_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, bs, g, scale, cap,
            ks_ref=None, vs_ref=None):
    r, j = pl.program_id(0), pl.program_id(2)
    nb = nb_ref[r]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < nb)
    def _block():
        q = q_ref[0, 0]                                   # (WG, Dh)
        k = k_ref[0, 0]                                   # (bs, Dh)
        v = v_ref[0, 0]
        if ks_ref is not None:
            # in-VMEM dequant right before the dot, mirroring the oracle's
            # `codes.astype(q.dtype) * scales.astype(q.dtype)` order
            k = k.astype(q.dtype) * ks_ref[0, 0].astype(q.dtype)
            v = v.astype(q.dtype) * vs_ref[0, 0].astype(q.dtype)
        s = jax.lax.dot_general(                          # (WG, bs) f32
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = _softcap(s, cap)
        wg = s.shape[0]
        # fused in-span causal mask: key slot j*bs+col visible to query row
        # `row` (kv-head-grouped, q position row // G) iff slot <= ctx + pos
        qpos = ctx_ref[r] + jax.lax.broadcasted_iota(jnp.int32, (wg, bs), 0) // g
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (wg, bs), 1)
        s = jnp.where(kpos <= qpos, s, NEG)
        # online softmax update in f32
        m_new = jnp.maximum(m_ref[...], jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_ref[...] - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(j == jnp.maximum(nb - 1, 0))
    def _finish():
        # idle rows (nb == 0) never accumulated: l == 0 -> emit zeros, the
        # caller discards them (same contract as the oracle's garbage rows)
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("logit_softcap", "interpret"))
def paged_attention(q, pool, block_table, ctx_lens, q_lens, *,
                    logit_softcap: float = 0.0,
                    interpret: bool = False):
    """Span queries against a blocked KV pool, streaming only valid blocks.

    q: (B, W, H, Dh) post-RoPE queries (row r valid in [:q_lens[r]]);
    pool: ONE layer's blocks {"k","v"[,"ks","vs"]} with leaves
    (NB, Hk, bs, *) — already holding this step's scattered span K/V;
    block_table: (B, MB) int32; ctx_lens / q_lens: (B,) int32.

    Returns (B, W, H, Dh) in q.dtype: attention output at every span
    position, numerically matching the jnp gather oracle
    (`span_attention_paged(..., impl="ref")`) on the valid region
    [:q_lens[r]] of every active row. Rows with q_lens == 0 return zeros.
    """
    b, w, h, dh = q.shape
    _, hk, bs, _ = pool["k"].shape
    mb = block_table.shape[1]
    g = h // hk
    wg = w * g
    quant = "ks" in pool

    from repro.runtime.kvblocks import valid_block_counts

    nb = valid_block_counts(ctx_lens, q_lens, bs, mb)
    # group queries by kv head: (B, Hk, W*G, Dh) — W major, G minor, so
    # flattened row i sits at query position i // G
    qh = (q.reshape(b, w, hk, g, dh).transpose(0, 2, 1, 3, 4)
          .reshape(b, hk, wg, dh))
    bt = block_table.astype(jnp.int32)

    def q_map(r, h_, j, nb_, bt_, ctx_):
        return (r, h_, 0, 0)

    def kv_map(r, h_, j, nb_, bt_, ctx_):
        # clamp past-the-end steps onto the last valid block: the index
        # map returns the same physical block as the previous step, so the
        # pipeline skips the DMA — only valid context ever streams
        jj = jnp.maximum(jnp.minimum(j, nb_[r] - 1), 0)
        return (bt_[r, jj], h_, 0, 0)

    # one (bs, Dh) tile of one head per step: the block's last two dims
    # are the pool's own, which the TPU's tiling accepts at any bs
    kv_specs = [
        pl.BlockSpec((1, 1, bs, dh), kv_map),
        pl.BlockSpec((1, 1, bs, dh), kv_map),
    ]
    operands = [qh, pool["k"], pool["v"]]
    if quant:
        kv_specs += [pl.BlockSpec((1, 1, bs, 1), kv_map),
                     pl.BlockSpec((1, 1, bs, 1), kv_map)]
        operands += [pool["ks"], pool["vs"]]

    def kernel(*refs):
        if quant:
            nb_r, bt_r, ctx_r, q_r, k_r, v_r, ks_r, vs_r, o_r, m_r, l_r, a_r = refs
            _kernel(nb_r, bt_r, ctx_r, q_r, k_r, v_r, o_r, m_r, l_r, a_r,
                    bs=bs, g=g, scale=dh ** -0.5, cap=logit_softcap,
                    ks_ref=ks_r, vs_ref=vs_r)
        else:
            nb_r, bt_r, ctx_r, q_r, k_r, v_r, o_r, m_r, l_r, a_r = refs
            _kernel(nb_r, bt_r, ctx_r, q_r, k_r, v_r, o_r, m_r, l_r, a_r,
                    bs=bs, g=g, scale=dh ** -0.5, cap=logit_softcap)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # nb, block_table, ctx_lens
        grid=(b, hk, mb),
        in_specs=[pl.BlockSpec((1, 1, wg, dh), q_map)] + kv_specs,
        out_specs=pl.BlockSpec((1, 1, wg, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((wg, 1), jnp.float32),    # running max m
            pltpu.VMEM((wg, 1), jnp.float32),    # running denom l
            pltpu.VMEM((wg, dh), jnp.float32),   # running numerator acc
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, wg, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_attention",
    )(nb, bt, ctx_lens.astype(jnp.int32), *operands)
    return (out.reshape(b, hk, w, g, dh).transpose(0, 2, 1, 3, 4)
            .reshape(b, w, h, dh))


# ------------------------------------------------------------- byte model --
def kv_bytes_per_token(hk: int, dh: int, kv_bits: int) -> float:
    """HBM bytes one cached token position occupies across K and V: int8
    codes + per-(token, head) f32 scale planes at kv_bits == 8, else the
    model dtype (bf16/f32 treated as 2 B — the bandwidth-relevant case)."""
    if kv_bits == 8:
        return 2 * (hk * dh + hk * 4)
    return 2 * hk * dh * 2


def stream_hbm_bytes(ctx_lens, q_lens, block_size: int, hk: int, dh: int,
                     *, kv_bits: int = 16, n_q_heads: int | None = None
                     ) -> int:
    """Modeled HBM traffic of one paged_attention launch: each row streams
    ceil((ctx+q)/bs) KV blocks ONCE (idle q_lens == 0 rows stream just
    the single trash block their clamped index map lands on), plus the q
    tile in and the output tile back. This is the O(ctx) term the kernel
    converts serving attention to — compare `gather_hbm_bytes` for what
    the jnp path moves."""
    h = n_q_heads or hk
    per_tok = kv_bytes_per_token(hk, dh, kv_bits)
    total = 0
    for ctx, ql in zip(ctx_lens, q_lens):
        nb = 1 if ql <= 0 else -(-(int(ctx) + int(ql)) // block_size)
        total += nb * block_size * per_tok
    w = max((int(x) for x in q_lens), default=0)
    io = 2 * len(list(ctx_lens)) * w * h * dh * 2     # q in + o out (bf16)
    return int(total + io)


def gather_hbm_bytes(batch: int, max_blocks: int, block_size: int, hk: int,
                     dh: int, *, kv_bits: int = 16, w: int = 1,
                     n_q_heads: int | None = None) -> int:
    """Modeled HBM traffic of the jnp gather oracle: every row reads its
    FULL (MB*bs) logical pool view — valid or not — and the int8 case
    additionally writes + re-reads the dense dequantized view at compute
    dtype. Independent of ctx_lens: the term the kernel deletes."""
    h = n_q_heads or hk
    slots = batch * max_blocks * block_size
    total = slots * kv_bytes_per_token(hk, dh, kv_bits)
    if kv_bits == 8:
        # materialized dequantized (B, MB*bs, Hk, Dh) K and V views at
        # compute dtype: written once, read once by the einsum
        total += 2 * slots * hk * dh * 2 * 2
    io = 2 * batch * w * h * dh * 2
    return int(total + io)

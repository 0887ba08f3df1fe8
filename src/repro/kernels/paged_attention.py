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

  * runs a `(B, Hk // ht)` grid — one program per (row, group of `ht` KV
    heads) — with the block table, `ctx_lens` and the per-row valid-block
    counts `nb` (`runtime.kvblocks.valid_block_counts`) scalar-prefetched
    into SMEM. The number of programs no longer grows with the table
    width, only the work inside a program grows with the row's context;
  * walks the row's block table in chunks of `P` entries inside the
    program (`lax.fori_loop` over ceil(nb / P) chunks): the pool leaves
    stay in HBM (`memory_space=pl.ANY`) and the program DMAs
    `pool[block_table[r, j], h0:h0+ht]` itself, one copy per valid entry
    `j < nb[r]`, into a two-slot VMEM buffer — chunk c+1's copies start
    before chunk c is computed. Entries at `j >= nb[r]` (the trash-block-0
    padding) are never copied; idle rows (`q_lens == 0`, `nb == 0`) copy
    nothing, run no chunk, and write zeros;
  * computes online softmax over (W-span queries x P*bs chunk keys), one
    dot batched over the program's heads per chunk, with the in-span
    causal mask `slot <= ctx_lens[r] + i` fused into the score tile (key
    position `c*P*bs + col` vs query position `ctx + row // G`); the
    unfilled slots of a partial last chunk hold stale VMEM, so their
    scores are masked and their V rows zeroed. The running (m, l, acc)
    live in f32 VMEM scratch;
  * dequantizes int8 K/V tiles in VMEM right before the dot — the scale
    planes DMA alongside the codes, and the `code.astype(q.dtype) *
    scale.astype(q.dtype)` order mirrors the jnp oracle exactly — so int8
    KV streams 1 byte/element + a thin scale plane instead of a dense
    dequantized bf16 window;
  * accumulates the output per (row, head) without ever materializing the
    `(B, MB*bs, Hk, Dh)` gather.

Tile sizes follow the shapes (`tile_plan`): a chunk is `P = 128 // bs`
table entries (128 key positions, one lane-dense score tile; fewer where
the table is narrower), and a program takes the most KV heads `ht`
(a divisor of Hk) whose working set — q and output blocks, the f32
(m, l, acc) scratch, the score tiles and the two KV slots — fits the
VMEM budget (`vmem_bytes`). Decode and short spans take every head;
a wide GQA prefill span (W*G = 1024 at Phi-3-medium width) takes fewer.

GQA runs grouped: the G query heads of one kv head are flattened into the
query-row axis `(W*G, Dh)`, so K/V tiles are fetched once per kv head, not
per query head.

Like the matmul kernels, this runs compiled on TPU and bit-faithfully
under `interpret=True` on CPU (how the identity tests drive it).

Tensor parallelism: the kernel needs no TP awareness. Under the
shard_map serving step (api.engine with a "model"-axis mesh) it is
invoked per shard with the PER-SHARD config — `Hk` here is
num_kv_heads / tp and the pool ref is that shard's head-slice
(runtime.kvblocks.pool_pspecs), so the grid is (B, Hk/tp/ht) and each
chip streams only its own heads' KV blocks. Attention is head-local,
so no collective touches the kernel; the single psum per attention
boundary happens outside, after the wo projection
(models.transformer.unified_step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import VMEM_BUDGET
from repro.kernels.quant_matmul import tile_bytes

NEG = -2.3819763e38  # large negative for masking in f32 (models.attention)
CHUNK_KEYS = 128     # key positions per chunk: one lane-dense score tile


def _softcap(s, cap: float):
    return (cap * jnp.tanh(s / cap)) if cap > 0 else s


def chunk_blocks(block_size: int, table_width: int) -> int:
    """Table entries one chunk of the kernel's walk covers (P)."""
    return max(1, min(CHUNK_KEYS // block_size, table_width))


def vmem_bytes(wg: int, dh: int, ht: int, p: int, bs: int, *,
               q_itemsize: int, kv_itemsize: int, quant: bool) -> int:
    """Scoped VMEM one program needs, at tile-padded sizes: the pipeline
    double-buffers the q and output blocks; (m, l, acc) are f32 scratch;
    K and V (and the int8 scale planes) hold two chunk slots; every
    head's f32 scores, probabilities and dequantized K/V tiles are live
    at once."""
    io = 2 * 2 * tile_bytes(wg, dh, q_itemsize)
    state = tile_bytes(wg, dh, 4) + 2 * tile_bytes(wg, 1, 4)
    slots = 2 * 2 * p * tile_bytes(bs, dh, kv_itemsize)
    if quant:
        slots += 2 * 2 * p * tile_bytes(bs, 1, 4)
    temps = 3 * tile_bytes(wg, p * bs, 4) + 2 * tile_bytes(p * bs, dh, 4)
    return ht * (io + state + slots + temps)


def tile_plan(wg: int, dh: int, hk: int, bs: int, table_width: int, *,
              q_itemsize: int, kv_itemsize: int,
              quant: bool) -> tuple[int, int]:
    """(P, ht): table entries per chunk, and the most KV heads per program
    (a divisor of hk) whose working set fits the VMEM budget; one head if
    none does."""
    p = chunk_blocks(bs, table_width)
    for ht in range(hk, 0, -1):
        if hk % ht == 0 and vmem_bytes(
                wg, dh, ht, p, bs, q_itemsize=q_itemsize,
                kv_itemsize=kv_itemsize, quant=quant) <= VMEM_BUDGET:
            return p, ht
    return p, 1


def grid_blocks(nb, chunk: int) -> int:
    """Block slots the kernel's chunks cover per layer and KV head,
    Σ_r ceil(nb_r / P) * P, whether or not each slot holds valid context
    (host side: the serving loop's `kv_grid_blocks` counter)."""
    nb = np.asarray(nb, np.int64)
    return int(((nb + chunk - 1) // chunk * chunk).sum())


def _kernel(nb_ref, bt_ref, ctx_ref, q_ref, *refs, bs, g, p, ht, scale,
            cap, quant):
    n_kv = 4 if quant else 2
    hbm, o_ref = refs[:n_kv], refs[n_kv]
    bufs = refs[n_kv + 1:2 * n_kv + 1]
    sem, m_ref, l_ref, acc_ref = refs[2 * n_kv + 1:]
    r, h0 = pl.program_id(0), pl.program_id(1) * ht
    nb = nb_ref[r]
    n_chunks = (nb + p - 1) // p

    def copies(c, slot, i):
        # one block's K, V (and scale planes) for this program's heads
        blk = bt_ref[r, c * p + i]
        return [pltpu.make_async_copy(
                    src.at[blk, pl.ds(h0, ht), :, pl.ds(0, dst.shape[-1])],
                    dst.at[slot, :, i], sem.at[slot])
                for src, dst in zip(hbm, bufs)]

    def load(buf, slot, n):
        # (ht, P, bs, n): the slot's blocks, less the lane padding
        x = buf[slot]
        return x if x.shape[-1] == n else x[..., :n]

    def each_block(c, slot, op):
        # the chunk's valid entries only: min(P, nb - c*P) of them
        def body(i, carry):
            for cp in copies(c, slot, i):
                op(cp)
            return carry

        jax.lax.fori_loop(0, jnp.minimum(p, nb - c * p), body, 0)

    def start(c, slot):
        each_block(c, slot, lambda cp: cp.start())

    def wait(c, slot):
        each_block(c, slot, lambda cp: cp.wait())

    m_ref[...] = jnp.full_like(m_ref, NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(nb > 0)
    def _first():
        start(0, 0)

    wg = q_ref.shape[2]
    pb = p * bs

    def chunk(c, carry):
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _next():
            start(c + 1, 1 - slot)

        wait(c, slot)
        # fused in-span causal mask: key slot c*P*bs+col visible to query
        # row `row` (kv-head-grouped, q position row // G) iff
        # slot <= ctx + pos; slots past the row's valid blocks were not
        # copied this chunk, so their scores are masked and V rows zeroed
        kpos = c * pb + jax.lax.broadcasted_iota(jnp.int32, (wg, pb), 1)
        qpos = (ctx_ref[r]
                + jax.lax.broadcasted_iota(jnp.int32, (wg, pb), 0) // g)
        live = (kpos <= qpos) & (kpos < nb * bs)
        filled = (c * pb + jax.lax.broadcasted_iota(jnp.int32, (pb, 1), 0)
                  < nb * bs)
        q = q_ref[0]                                      # (ht, WG, Dh)
        dh = q.shape[-1]
        k = load(bufs[0], slot, dh)                       # (ht, P, bs, Dh)
        v = load(bufs[1], slot, dh)
        if quant:
            # in-VMEM dequant right before the dot, mirroring the oracle's
            # `codes.astype(q.dtype) * scales.astype(q.dtype)`
            k = k.astype(q.dtype) * load(bufs[2], slot, 1).astype(q.dtype)
            v = v.astype(q.dtype) * load(bufs[3], slot, 1).astype(q.dtype)
        k = k.reshape(ht, pb, dh)
        v = jnp.where(filled, v.reshape(ht, pb, dh), 0).astype(v.dtype)
        s = jax.lax.dot_general(                          # (ht, WG, P*bs)
            q, k, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale
        s = jnp.where(live, _softcap(s, cap), NEG)
        # online softmax update in f32
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pr = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pr, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pr.astype(v.dtype), v,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk, 0)
    # idle rows (nb == 0) never accumulated: l == 0 -> emit zeros, the
    # caller discards them (same contract as the oracle's garbage rows)
    l = l_ref[...]
    o_ref[0] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


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
    p, ht = tile_plan(wg, dh, hk, bs, mb, q_itemsize=q.dtype.itemsize,
                      kv_itemsize=pool["k"].dtype.itemsize, quant=quant)

    from repro.runtime.kvblocks import valid_block_counts

    nb = valid_block_counts(ctx_lens, q_lens, bs, mb)
    # group queries by kv head: (B, Hk, W*G, Dh) — W major, G minor, so
    # flattened row i sits at query position i // G
    qh = (q.reshape(b, w, hk, g, dh).transpose(0, 2, 1, 3, 4)
          .reshape(b, hk, wg, dh))
    keys = ("k", "v", "ks", "vs") if quant else ("k", "v")
    leaves = [pool[key] for key in keys]

    def lanes(n):
        # the TPU lays a leaf's last axis out in whole 128-lane tiles, and
        # the compiler slices HBM refs in whole tiles only, so a compiled
        # copy moves the padded tile and the kernel drops the pad lanes
        return n if interpret else -(-n // 128) * 128

    def q_map(r, hg, *_):
        return (r, hg, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # nb, block_table, ctx_lens
        grid=(b, hk // ht),
        in_specs=[pl.BlockSpec((1, ht, wg, dh), q_map)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(leaves),
        out_specs=pl.BlockSpec((1, ht, wg, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, ht, p, bs, lanes(leaf.shape[-1])), leaf.dtype)
            for leaf in leaves
        ] + [
            pltpu.SemaphoreType.DMA((2,)),       # one per chunk slot
            pltpu.VMEM((ht, wg, 1), jnp.float32),    # running max m
            pltpu.VMEM((ht, wg, 1), jnp.float32),    # running denom l
            pltpu.VMEM((ht, wg, dh), jnp.float32),   # running numerator acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, g=g, p=p, ht=ht,
                          scale=dh ** -0.5, cap=logit_softcap, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, wg, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="paged_attention",
    )(nb, block_table.astype(jnp.int32), ctx_lens.astype(jnp.int32), qh,
      *leaves)
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
    ceil((ctx+q)/bs) KV blocks ONCE (idle q_lens == 0 rows stream
    nothing), plus the q tile in and the output tile back. This is the
    O(ctx) term the kernel converts serving attention to — compare
    `gather_hbm_bytes` for what the jnp path moves."""
    h = n_q_heads or hk
    per_tok = kv_bytes_per_token(hk, dh, kv_bits)
    total = 0
    for ctx, ql in zip(ctx_lens, q_lens):
        nb = 0 if ql <= 0 else -(-(int(ctx) + int(ql)) // block_size)
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

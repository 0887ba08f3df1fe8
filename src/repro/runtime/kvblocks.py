"""Blocked (paged) KV-cache pool for continuous-batching decode.

The monolithic decode cache sizes every row at `max_len`, so a batch pays
for its longest request and a finished row's memory is stranded until the
whole batch retires. This module replaces it, for the shared serving
batch, with the paged layout production servers use (vLLM /
TensorRT-LLM style):

  * a physical pool of fixed-size blocks per layer —
    `(L, num_blocks, Hk, block_size, Dh)` for K and V, plus per-(token,
    head) scale planes when `cfg.kv_cache_bits == 8`. One (block, head)
    tile is `(block_size, Dh)`, the last two dims, so the paged-attention
    kernel DMAs whole tiles the TPU accepts;
  * a host-side `BlockPool` free-list allocator. Block 0 is reserved as
    the *trash block*: inactive batch rows write there and nothing ever
    reads it back, so the jitted step needs no control flow;
  * per-sequence block tables mapping logical position `p` to physical
    slot `(table[p // block_size], p % block_size)`. Tables are dense,
    append-only, and padded with the trash block;
  * refcounted prefix sharing: a fully-written block can be *registered*
    under a chained content digest (`prefix_digests`), after which later
    sequences with the same token prefix `share` it by reference instead
    of recomputing it. Freeing decrements the refcount; a registered
    block whose refcount reaches zero is parked in an LRU side pool (it
    still counts as `available`) and is evicted — digest dropped, block
    reused — only when the free list runs dry. `copy_block` is the
    copy-on-write primitive: the scheduler materializes a private copy
    before the first divergent write into a shared block.

Tokens enter the pool a *span* at a time: `span_slots` maps a batch of
per-row token spans (a chunk of prompt during chunked prefill, or a
single decode token) to physical (block, offset) scatter targets;
`models.attention.span_attention_paged` does the span write + gather.
Admission/eviction policy lives in `runtime.scheduler`; this module is
pure layout + accounting.

Supported: dense / MoE layouts with global causal attention. Sliding
windows, local/global alternation, and SSM state are not paged yet (their
decode state is O(window) / O(1) per row, so paging buys much less).
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict

import numpy as np

# jax.numpy is imported lazily inside the device-facing functions
# (init_paged_cache, span_slots): the allocator / digest half of this
# module is on the scheduler's host path, and
# `from repro.runtime.kvblocks import BlockPool` must not initialize a
# device runtime. Function-local imports are trace-safe — they run at
# trace time, not per step.


def check_paged_support(cfg) -> None:
    """Raise when `cfg` cannot decode through the blocked KV pool."""
    if cfg.layout not in ("dense", "moe"):
        raise NotImplementedError(
            f"paged KV decode supports dense/moe layouts, not {cfg.layout!r}"
            " (SSM/hybrid decode state is O(1) per row and is not paged)")
    if cfg.local_global_period or cfg.attn_window:
        raise NotImplementedError(
            "paged KV decode does not support windowed or local/global "
            "attention yet — their rolling caches are already O(window)")


def blocks_needed(prompt_len: int, max_tokens: int, block_size: int) -> int:
    """Blocks a request occupies at peak. Chunked prefill writes every
    prompt position into the pool, and decode caches every generated
    token except the last (which is returned, never attended), so the
    footprint is prompt_len + max_tokens - 1 positions."""
    return -(-(prompt_len + max(max_tokens, 1) - 1) // block_size)


def blocks_for_positions(n_positions: int, block_size: int) -> int:
    """Block-table entries covering the first `n_positions` pool slots —
    the committed-context footprint the speculative rollback rewinds to
    (scheduler.Scheduler.commit_speculation)."""
    return -(-max(n_positions, 0) // block_size)


class BlockPool:
    """Host-side refcounting allocator over `num_blocks` KV blocks.

    Block 0 is reserved (the trash block for inactive rows) and is never
    handed out, so `capacity == num_blocks - 1`. Freeing a block nobody
    holds is a hard error — the scheduler tests lean on this to prove
    admit/evict sequences never leak.

    Prefix caching layers three states on top of the plain free list:

      free      — on `_free`, content unknown, refcount 0;
      live      — refcount >= 1 holder (one owner, or owner + sharers);
      idle      — refcount 0 but *registered* under a content digest.
                  Idle blocks sit in an LRU (`_idle`), still answer
                  `lookup`/`share`, still count as `available`, and are
                  evicted oldest-first only when `alloc` drains the free
                  list.

    With no `register` calls the pool degenerates to the PR-2 free-list
    allocator: every alloc returns refcount-1 blocks and every free
    returns them straight to the free list.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError(f"need >= 2 blocks (one is reserved), got "
                             f"{num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._free = list(range(num_blocks - 1, 0, -1))
        self._ref: dict[int, int] = {}          # block -> refcount >= 1
        self._index: dict[bytes, int] = {}      # digest -> block
        self._digest: dict[int, bytes] = {}     # block -> digest
        self._idle: OrderedDict[int, None] = OrderedDict()  # LRU, old first
        self.evictions = 0

    @property
    def capacity(self) -> int:
        return self.num_blocks - 1

    @property
    def available(self) -> int:
        """Blocks an alloc can claim right now: free + evictable idle."""
        return len(self._free) + len(self._idle)

    @property
    def cached_blocks(self) -> int:
        """Blocks currently indexed by digest (live sharers + idle)."""
        return len(self._index)

    @property
    def idle_cached_blocks(self) -> int:
        return len(self._idle)

    def can_alloc(self, n: int) -> bool:
        return n <= self.available

    def alloc(self, n: int) -> list[int]:
        if not self.can_alloc(n):
            raise RuntimeError(
                f"block pool exhausted: want {n}, have {self.available} "
                f"(callers must check can_alloc and queue instead)")
        ids = []
        for _ in range(n):
            if self._free:
                b = self._free.pop()
            else:  # evict the least-recently-idle cached block
                b, _ = self._idle.popitem(last=False)
                del self._index[self._digest.pop(b)]
                self.evictions += 1
            self._ref[b] = 1
            ids.append(b)
        return ids

    def free(self, ids) -> None:
        """Drop one reference per listed block. The last holder's free
        parks registered blocks in the idle LRU (newest end) and returns
        unregistered ones to the free list."""
        for b in ids:
            if self._ref.get(b, 0) < 1:
                raise RuntimeError(f"double free / foreign block {b}")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                if b in self._digest:
                    self._idle[b] = None
                else:
                    self._free.append(b)

    def refcount(self, block_id: int) -> int:
        return self._ref.get(block_id, 0)

    def register(self, block_id: int, digest: bytes) -> bool:
        """Index a fully-written, currently-held block under its content
        digest. First writer wins: if the digest is already indexed (or
        the block already registered) this is a no-op returning False —
        the duplicate block simply stays private. Trash block 0 can never
        get here because it is never handed out by `alloc`."""
        if self._ref.get(block_id, 0) < 1:
            raise RuntimeError(
                f"register of unheld block {block_id} (only live blocks "
                f"can be indexed)")
        if digest in self._index or block_id in self._digest:
            return False
        self._index[digest] = block_id
        self._digest[block_id] = digest
        return True

    def lookup(self, digest: bytes):
        """Block currently indexed under `digest`, or None. Does not take
        a reference — pair with `share` before relying on the block."""
        return self._index.get(digest)

    def share(self, digest: bytes):
        """Take one reference on the block cached under `digest`,
        reviving it from the idle LRU if nobody holds it. None on miss."""
        b = self._index.get(digest)
        if b is None:
            return None
        if b in self._idle:
            del self._idle[b]
        self._ref[b] = self._ref.get(b, 0) + 1
        return b


def prefix_digests(tokens, block_size: int, fingerprint: bytes = b"") \
        -> list[bytes]:
    """Chained content digests for every FULL block of a token prefix.

    digest[i] commits to (fingerprint, block_size, tokens[0 : (i+1)*bs]):
    the chain folds each block's token ids into the previous digest, so
    equal digests mean equal position-aligned prefixes under the same
    model/plan fingerprint. Partial tail blocks get no digest — they are
    never shared. Host-side only (SHA-256 over int64 token bytes)."""
    toks = np.asarray(tokens, dtype=np.int64)
    if toks.ndim != 1:
        raise ValueError(f"tokens must be 1-D, got shape {toks.shape}")
    prev = hashlib.sha256(
        b"kvprefix:%d:" % block_size + fingerprint).digest()
    out = []
    for i in range(toks.size // block_size):
        blk = toks[i * block_size:(i + 1) * block_size]
        prev = hashlib.sha256(prev + blk.astype("<i8").tobytes()).digest()
        out.append(prev)
    return out


def copy_block(pool, src, dst):
    """Copy-on-write primitive: duplicate physical block `src` into `dst`
    across every pool leaf (codes and int8 scale planes alike). jit-safe
    with traced src/dst, and TP-safe — the copy moves along the block
    axis 1 while `pool_pspecs` shards the KV-head axis 2, so each shard
    copies exactly its own head slice."""
    return {key: leaf.at[:, dst].set(leaf[:, src])
            for key, leaf in pool.items()}


def init_paged_cache(cfg, num_blocks: int, block_size: int, dtype=None):
    """Physical pool arrays for every layer: {"k","v"} of shape
    (L, num_blocks, Hk, block_size, Dh), plus {"ks","vs"} f32 scale planes
    (L, num_blocks, Hk, block_size, 1) when cfg.kv_cache_bits == 8 (same
    int8 code + scale convention as attention.init_kv_cache).

    The paged-attention kernel reads one (block_size, Dh) tile per
    (block, head). Those are the leaves' own last two dims, which the
    TPU's tiling rules accept whole, so any block size compiles for every
    KV dtype (tests/test_tpu_compile.py checks the int8 pool at the
    serving block sizes)."""
    import jax.numpy as jnp

    check_paged_support(cfg)
    dtype = dtype or jnp.dtype(cfg.dtype)
    L, hk, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    shape = (L, num_blocks, hk, block_size, hd)
    if getattr(cfg, "kv_cache_bits", 16) == 8:
        sshape = (L, num_blocks, hk, block_size, 1)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "ks": jnp.ones(sshape, jnp.float32),
                "vs": jnp.ones(sshape, jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def pool_pspecs(cfg):
    """shard_map PartitionSpecs for the paged pool under tensor-parallel
    serving: every pool leaf — (L, NB, Hk, bs, Dh) codes and the int8
    (L, NB, Hk, bs, 1) scale planes — is sliced on the KV-head axis 2
    over the mesh "model" axis, so each shard owns the KV blocks for
    exactly the heads it computes. Block tables, step buffers, and all
    scheduler state stay host-side/replicated (P()); only the pool
    shards. int8 KV quantization is per-(token, head), so head slicing
    is bit-exact — shard r's codes and scales equal rows
    [r*Hk/tp, (r+1)*Hk/tp) of the single-device pool."""
    from jax.sharding import PartitionSpec as P
    spec = P(None, None, "model", None, None)
    keys = ("k", "v", "ks", "vs") if getattr(cfg, "kv_cache_bits", 16) == 8 \
        else ("k", "v")
    return {k: spec for k in keys}


def shard_pool(pool, tp: int, shard: int):
    """The head-slice of `pool` that TP shard `shard` of `tp` owns —
    the reference the property tests compare shard_map's placement
    against. Pure slicing, no device semantics."""
    if not 0 <= shard < tp:
        raise ValueError(f"shard {shard} out of range for tp={tp}")
    out = {}
    for key, leaf in pool.items():
        hk = leaf.shape[2]
        if hk % tp:
            raise ValueError(
                f"pool leaf {key!r} has {hk} KV heads, not divisible by "
                f"tp={tp}")
        n = hk // tp
        out[key] = leaf[:, :, shard * n:(shard + 1) * n]
    return out


def valid_block_counts(ctx_lens, q_lens, block_size, max_blocks):
    """Per-row count of block-table entries holding valid context THIS
    step — the metadata the Pallas paged-attention kernel walks.

    Row r's span writes its K/V first, so after the scatter the pool holds
    `ctx_lens[r] + q_lens[r]` valid positions = the first
    ceil((ctx + q) / block_size) table entries; everything past that is
    trash-block-0 padding the kernel must never fetch. Idle rows
    (q_lens == 0) count zero — the kernel skips them entirely. Pure index
    math on numpy or jax arrays alike: the serving step traces it for the
    kernel's walk, and the serve loop's step counters run it on the host
    copy of the same columns. Clamped to the table width for
    caller-supplied out-of-range metadata."""
    total = ctx_lens + q_lens
    nb = (total + block_size - 1) // block_size * (q_lens > 0)
    return nb.clip(0, max_blocks).astype(np.int32)


def span_slots(block_table, ctx_lens, q_lens, width, block_size):
    """Physical scatter targets for a batch of per-row token spans.

    Row r's span this step covers logical positions
    `ctx_lens[r] .. ctx_lens[r] + q_lens[r] - 1` (a prefill chunk, or a
    single decode token at q_lens == 1). Returns (blk, off), each
    (B, width) int32: span slot (r, i) writes physical block
    `blk[r, i]` at in-block offset `off[r, i]`. Slots past a row's
    `q_lens` — and whole rows with q_lens == 0 — are routed to the
    reserved trash block 0, so the caller can scatter the full (B, width)
    rectangle with no control flow. jit-safe (pure index math, static
    shapes).
    """
    import jax.numpy as jnp

    pos = ctx_lens[:, None] + jnp.arange(width)[None, :]        # (B, W)
    valid = jnp.arange(width)[None, :] < q_lens[:, None]        # (B, W)
    mb = block_table.shape[1]
    bidx = jnp.minimum(pos // block_size, mb - 1)               # clamp pads
    blk = jnp.where(valid,
                    jnp.take_along_axis(block_table, bidx, axis=1), 0)
    off = jnp.where(valid, pos % block_size, 0)
    return blk.astype(jnp.int32), off.astype(jnp.int32)

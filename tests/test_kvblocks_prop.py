"""Property-based `BlockPool` invariants (hypothesis).

Under ANY sequence of alloc/free operations the free-list allocator must
uphold:
  * the reserved trash block 0 is never handed out;
  * no block is ever held twice (no double-alloc), and freeing a block
    not currently held is a hard error (no double-free);
  * `available` always equals capacity minus blocks held — the free list
    never drifts from the allocation set.

The speculative draft path adds provisional allocation on top
(`Scheduler.reserve_speculation` / `commit_speculation`): under ANY
sequence of reserve→accept→rollback rounds, rejected drafts must return
every provisional block, the trash block must never be captured, and a
row's holdings must stay consistent with its committed context.

Prefix caching layers refcounts and a content index on top: under ANY
interleaving of admissions (with shared/duplicated prompts), chunked
prefill, decode, speculation rounds, preemptions, and finishes, the
refcounts must exactly mirror the live holders (no leak, never
negative), the trash block is never held or cached, a block held by
more than one sequence is never a prefill scatter target (shared
payload never mutated in place), and draining every sequence returns
the pool to full availability.

Tensor-parallel serving head-shards the physical pool but keeps the
allocator and block tables host-side REPLICATED — every shard indexes
its head-slice with the same block ids. The TP invariants here pin
that contract: the same op script driven against one allocator per
shard never lets the shards drift (identical free lists, identical
draft grants, trash block captured on no shard), and `shard_pool` is
an exact head-partition of the single-device pool.
"""
import collections
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.runtime import elastic
from repro.runtime.kvblocks import (BlockPool, blocks_for_positions,
                                    blocks_needed, init_paged_cache,
                                    pool_pspecs, shard_pool, span_slots,
                                    valid_block_counts)
from repro.runtime.scheduler import Request, Scheduler, Sequence

settings.register_profile("ci", max_examples=40, deadline=None)
settings.load_profile("ci")


@st.composite
def pool_and_ops(draw):
    """A pool geometry plus a random alloc/free script. Ops are encoded
    so they stay meaningful whatever the interleaving: ('alloc', k) asks
    for k blocks (possibly more than available — callers must see a
    clean refusal), ('free', i) releases the i-th live group (mod the
    number of groups alive at that point)."""
    num_blocks = draw(st.integers(2, 24))
    block_size = draw(st.integers(1, 8))
    ops = []
    for _ in range(draw(st.integers(1, 60))):
        k = draw(st.integers(-8, 8))
        ops.append(("free", -k - 1) if k < 0 else ("alloc", k + 1))
    return num_blocks, block_size, ops


@given(pool_and_ops())
def test_block_pool_invariants_random_ops(case):
    num_blocks, block_size, ops = case
    pool = BlockPool(num_blocks, block_size)
    live: list[list[int]] = []
    for op, arg in ops:
        if op == "alloc":
            if pool.can_alloc(arg):
                ids = pool.alloc(arg)
                assert len(ids) == arg
                assert 0 not in ids, "reserved trash block handed out"
                live.append(ids)
            else:
                with pytest.raises(RuntimeError, match="exhausted"):
                    pool.alloc(arg)
        elif live:
            pool.free(live.pop(arg % len(live)))
        held = [b for ids in live for b in ids]
        assert len(held) == len(set(held)), "block held twice"
        assert all(0 < b < num_blocks for b in held)
        assert pool.available == pool.capacity - len(held), \
            "free list inconsistent with allocations"
        assert pool.can_alloc(pool.available)
        assert not pool.can_alloc(pool.available + 1)
    for ids in live:
        pool.free(ids)
    assert pool.available == pool.capacity
    # every block freed exactly once: a second free must be rejected
    if pool.capacity >= 1:
        ids = pool.alloc(1)
        pool.free(ids)
        with pytest.raises(RuntimeError, match="double free"):
            pool.free(ids)


@given(st.integers(1, 8), st.integers(0, 20), st.integers(0, 12))
def test_span_slots_route_every_valid_token_once(bsz, ctx, qlen):
    """span_slots maps each valid span token to the unique physical slot
    its logical position owns; pad slots all land in trash block 0."""
    width = max(qlen, 1)
    mb = (ctx + width + bsz - 1) // bsz + 1
    table = np.arange(1, mb + 1, dtype=np.int32)[None, :]   # blocks 1..mb
    blk, off = span_slots(table, np.asarray([ctx], np.int32),
                          np.asarray([qlen], np.int32), width, bsz)
    blk, off = np.asarray(blk)[0], np.asarray(off)[0]
    for i in range(width):
        pos = ctx + i
        if i < qlen:
            assert blk[i] == table[0, pos // bsz]
            assert off[i] == pos % bsz
        else:
            assert blk[i] == 0 and off[i] == 0
    # valid slots are distinct (no token overwrites another)
    valid = [(int(blk[i]), int(off[i])) for i in range(qlen)]
    assert len(valid) == len(set(valid))


@st.composite
def spec_rounds(draw):
    """A pool geometry, one under-provisioned decoding row, and a script
    of speculative rounds: each round offers k draft tokens and then
    accepts a (possibly empty) prefix of whatever was granted. The row
    starts holding only its committed-context blocks — NOT the admission
    worst case — so reserve_speculation genuinely has to allocate."""
    block_size = draw(st.integers(1, 4))
    prompt_len = draw(st.integers(1, 10))
    max_tokens = draw(st.integers(2, 20))
    # sometimes too small to back every draft: the shrink path must
    # engage, never crash
    num_blocks = draw(st.integers(2, 30))
    rounds = [(draw(st.integers(1, 6)),    # k offered
               draw(st.integers(0, 6)))    # acceptance draw
              for _ in range(draw(st.integers(1, 12)))]
    return block_size, prompt_len, max_tokens, num_blocks, rounds


@given(spec_rounds())
def test_speculative_rollback_never_leaks(case):
    block_size, prompt_len, max_tokens, num_blocks, rounds = case
    pool = BlockPool(num_blocks, block_size)
    committed = prompt_len            # prompt cached, first token pending
    base_need = blocks_for_positions(committed, block_size)
    if base_need > pool.capacity:
        return                        # config can't even hold the prompt
    sched = Scheduler(pool, 1)
    req = Request(tokens=np.ones(prompt_len, np.int32),
                  max_tokens=max_tokens, rid=0)
    seq = Sequence(req=req, row=0, block_ids=pool.alloc(base_need),
                   prefilled=prompt_len, n_emitted=1)
    for k_offer, acc_draw in rounds:
        if seq.done:
            break
        avail_before = pool.available
        held_before = len(seq.block_ids)
        k = sched.reserve_speculation(seq, k_offer)
        # grant is clamped inside the request and the pool
        assert 0 <= k <= min(k_offer, seq.max_tokens - seq.n_emitted - 1)
        assert 0 not in seq.draft_blocks, "trash block 0 captured"
        assert len(set(seq.block_ids)) == len(seq.block_ids)
        if k == 0:
            # no grant -> no draft round; a plain decode step would lean
            # on the admission-time worst-case reservation, which this
            # deliberately under-provisioned row does not carry
            assert seq.draft_blocks == []
            assert pool.available == avail_before
            continue
        # the grant covers through the verify span's last written
        # position (index end -> end + 1 slots)
        end = seq.prompt_len + seq.n_emitted - 1 + k
        assert len(seq.block_ids) >= \
            blocks_for_positions(end + 1, block_size)
        # kernel-walk safety: the paged-attention metadata for this
        # row's verify span never exceeds the blocks actually held
        ctx0 = seq.prompt_len + seq.n_emitted - 1
        vb = int(valid_block_counts(np.asarray([ctx0], np.int32),
                                    np.asarray([1 + k], np.int32),
                                    block_size, 1 << 30)[0])
        assert vb <= len(seq.block_ids)
        # accept a prefix: 0..k drafts survive, plus the full model's own
        # token (every verify emits at least one)
        seq.n_emitted += min(acc_draw, k) + 1
        released = sched.commit_speculation(seq)
        assert seq.draft_blocks == []
        assert 0 not in released
        # reject-then-free leaks nothing: blocks either stayed with the
        # row or went back to the pool, and the free list agrees
        assert pool.available == \
            pool.capacity - len(seq.block_ids), \
            "pool accounting drifted across a speculative round"
        # holdings rewound to the committed context (never below the
        # pre-draft holdings, never past what the round allocated) —
        # i.e. valid_block_counts for every future span over the cached
        # context stays within the rewound table
        ctx = seq.prompt_len + seq.n_emitted - 1
        assert len(seq.block_ids) >= blocks_for_positions(ctx, block_size)
        assert int(valid_block_counts(
            np.asarray([max(ctx - 1, 0)], np.int32),
            np.asarray([1], np.int32), block_size,
            len(seq.block_ids))[0]) <= len(seq.block_ids)
        assert held_before <= len(seq.block_ids) + len(released)
        assert pool.available <= avail_before
    sched.finish(seq)
    assert pool.available == pool.capacity, "blocks leaked after finish"


# ------------------------------------------------- head-sharded pool (TP) --

@given(pool_and_ops(), st.sampled_from([2, 4]))
def test_block_pool_replicated_across_shards(case, tp):
    """Under TP the allocator is replicated host-side: one logical
    BlockPool per shard fed the SAME op script. Whatever the script, the
    per-shard free lists must stay identical step by step (a drifted
    shard would scatter KV into different physical blocks than its
    peers' block tables name) and the trash block is handed out on no
    shard."""
    num_blocks, block_size, ops = case
    pools = [BlockPool(num_blocks, block_size) for _ in range(tp)]
    live: list[list[list[int]]] = [[] for _ in range(tp)]
    for op, arg in ops:
        for s, pool in enumerate(pools):
            if op == "alloc":
                if pool.can_alloc(arg):
                    ids = pool.alloc(arg)
                    assert 0 not in ids, f"shard {s}: trash block captured"
                    live[s].append(ids)
                else:
                    with pytest.raises(RuntimeError, match="exhausted"):
                        pool.alloc(arg)
            elif live[s]:
                pool.free(live[s].pop(arg % len(live[s])))
        # shards agree exactly: same groups, same free count
        assert all(live[s] == live[0] for s in range(tp)), \
            "per-shard allocations drifted"
        assert all(p.available == pools[0].available for p in pools), \
            "per-shard free lists drifted"
    for s, pool in enumerate(pools):
        for ids in live[s]:
            pool.free(ids)
        assert pool.available == pool.capacity


@given(spec_rounds(), st.sampled_from([2, 4]))
def test_speculative_rounds_replicated_across_shards(case, tp):
    """reserve→accept→rollback rounds replayed on one Scheduler per
    shard: every shard grants the same k, rewinds to the same holdings,
    and valid_block_counts over the rewound table agree across shards
    (the kernel walks the same number of blocks on every chip)."""
    block_size, prompt_len, max_tokens, num_blocks, rounds = case
    base_need = blocks_for_positions(prompt_len, block_size)
    if base_need > num_blocks - 1:
        return
    pools = [BlockPool(num_blocks, block_size) for _ in range(tp)]
    scheds = [Scheduler(p, 1) for p in pools]
    reqs = [Request(tokens=np.ones(prompt_len, np.int32),
                    max_tokens=max_tokens, rid=0) for _ in range(tp)]
    seqs = [Sequence(req=reqs[s], row=0, block_ids=pools[s].alloc(base_need),
                     prefilled=prompt_len, n_emitted=1) for s in range(tp)]
    for k_offer, acc_draw in rounds:
        if seqs[0].done:
            break
        grants = [scheds[s].reserve_speculation(seqs[s], k_offer)
                  for s in range(tp)]
        assert len(set(grants)) == 1, "draft grant differs across shards"
        for s in range(tp):
            assert 0 not in seqs[s].draft_blocks
            assert seqs[s].block_ids == seqs[0].block_ids
        k = grants[0]
        adv = min(acc_draw, k) + 1 if k else 0
        for s in range(tp):
            seqs[s].n_emitted += adv
            if k:
                scheds[s].commit_speculation(seqs[s])
        assert all(seqs[s].block_ids == seqs[0].block_ids
                   for s in range(tp)), "rollback diverged across shards"
        assert all(pools[s].available == pools[0].available
                   for s in range(tp))
        # per-shard kernel metadata agrees: same valid block walk
        ctx = seqs[0].prompt_len + seqs[0].n_emitted - 1
        counts = {int(valid_block_counts(
            np.asarray([max(ctx - 1, 0)], np.int32),
            np.asarray([1], np.int32), block_size,
            len(seqs[s].block_ids))[0]) for s in range(tp)}
        assert len(counts) == 1, "valid_block_counts differ across shards"
    for s in range(tp):
        scheds[s].finish(seqs[s])
        assert pools[s].available == pools[s].capacity


def test_shard_pool_partitions_heads_exactly():
    """shard_pool is an exact partition: the per-shard head-slices
    concatenate back to the single-device pool for every leaf (16-bit
    and int8-with-scales layouts), per-shard shapes carry Hk/tp heads,
    and non-dividing geometry / bad shard indices are hard errors."""
    from repro.configs import get_config

    cfg = get_config("opus-mt", smoke=True)
    for kv_bits in (16, 8):
        c = dataclasses.replace(cfg, kv_cache_bits=kv_bits)
        pool = init_paged_cache(c, num_blocks=5, block_size=4)
        hk = c.num_kv_heads
        for tp in (1, 2, 4):
            shards = [shard_pool(pool, tp, s) for s in range(tp)]
            for key, leaf in pool.items():
                for s in range(tp):
                    assert shards[s][key].shape[2] == hk // tp
                glued = np.concatenate(
                    [np.asarray(s[key]) for s in shards], axis=2)
                assert np.array_equal(glued, np.asarray(leaf)), key
        with pytest.raises(ValueError, match="shard"):
            shard_pool(pool, 2, 2)
        with pytest.raises(ValueError, match="not divisible"):
            shard_pool(pool, 3, 0)


# ---------------------------------------------- prefix-cache refcounts --

@st.composite
def cache_scripts(draw):
    """A pool geometry plus a random script over the prefix-caching
    scheduler: submissions drawn from two shared prompt prefixes (tail
    length 0 makes a fully-cached, copy-on-write candidate), interleaved
    with admission, prefill chunks, decode/speculation rounds, pool-
    pressure preemptions, and finishes."""
    num_blocks = draw(st.integers(6, 24))
    block_size = draw(st.integers(1, 4))
    max_batch = draw(st.integers(1, 3))
    ops = []
    for _ in range(draw(st.integers(5, 45))):
        kind = draw(st.sampled_from(
            ["submit", "admit", "chunk", "decode", "spec", "finish",
             "preempt"]))
        ops.append((kind, draw(st.integers(0, 7))))
    return num_blocks, block_size, max_batch, ops


@given(cache_scripts())
def test_prefix_cache_refcounts_mirror_holders_exactly(case):
    num_blocks, bs, max_batch, ops = case
    pool = BlockPool(num_blocks, bs)
    sched = Scheduler(pool, max_batch, prefix_cache=True, fingerprint=b"prop")
    rid = 0

    def live():
        return [s for s in sched.rows if s is not None]

    for kind, arg in ops:
        if kind == "submit":
            p = arg % 2                             # two shared prefixes
            plen = (1 + p) * bs                     # 1 or 2 full blocks
            tail = (arg >> 1) % (bs + 2)            # 0 -> COW candidate
            toks = np.concatenate([
                np.full(plen, 17 + p, np.int32),
                np.arange(1000 + 10 * rid, 1000 + 10 * rid + tail,
                          dtype=np.int32)])
            req = Request(tokens=toks, max_tokens=1 + arg % 3, rid=rid)
            rid += 1
            if blocks_needed(toks.size, req.max_tokens, bs) <= pool.capacity:
                sched.submit(req)
        elif kind == "admit":
            s = sched.try_admit()
            if s is not None and s.cow_dst is not None:
                # engine contract: dispatch the device copy, then drop
                # the source pin
                assert s.cow_src is not None and s.cow_src != s.cow_dst
                sched.release_cow(s)
        elif kind == "chunk":
            cands = [s for s in live() if not s.prefill_done]
            if cands:
                s = cands[arg % len(cands)]
                width = min(1 + arg, s.prompt_len - s.prefilled)
                span = range(s.prefilled, s.prefilled + width)
                assert all(p // bs >= s.n_shared for p in span), \
                    "prefill chunk aimed inside the shared prefix"
                for b in {s.block_ids[p // bs] for p in span}:
                    assert pool.refcount(b) == 1, \
                        "prefill chunk would write a shared block"
                shared_before = s.block_ids[:s.n_shared]
                sched.advance_prefill(s, width)
                assert s.block_ids[:s.n_shared] == shared_before
        elif kind == "decode":
            cands = [s for s in live() if s.prefill_done and not s.done]
            if cands:
                cands[arg % len(cands)].n_emitted += 1
        elif kind == "spec":
            cands = [s for s in live()
                     if s.prefill_done and not s.done and s.n_emitted]
            if cands:
                s = cands[arg % len(cands)]
                shared_before = s.block_ids[:s.n_shared]
                k = sched.reserve_speculation(s, 1 + arg % 3)
                if k:
                    s.n_emitted += min(arg % (k + 1), k) + 1
                    sched.commit_speculation(s)
                assert s.block_ids[:s.n_shared] == shared_before, \
                    "speculative rollback rewound into shared blocks"
        elif kind == "finish":
            if live():
                sched.finish(live()[arg % len(live())])
        elif kind == "preempt":
            victims = elastic.preemption_victims(sched.rows)
            if victims:
                sched.preempt(victims[0])
        # ------ global invariants after EVERY op ------
        expect = collections.Counter()
        for s in live():
            assert 0 not in s.block_ids, "trash block held by a sequence"
            assert s.cow_src != 0 and s.cow_dst != 0
            assert len(set(s.block_ids)) == len(s.block_ids)
            assert s.prefilled >= s.n_shared * bs, \
                "write watermark fell inside the shared prefix"
            expect.update(s.block_ids)
            if s.cow_src is not None:
                expect[s.cow_src] += 1
        for b, c in expect.items():
            assert pool.refcount(b) == c, f"refcount drift on block {b}"
        assert pool.refcount(0) == 0
        assert pool.available == pool.capacity - len(expect), \
            "pool accounting drifted (leak or double count)"
        priv = collections.Counter()
        for s in live():
            priv.update(s.block_ids[s.n_shared:])
        assert all(c == 1 for c in priv.values()), \
            "privately-held block appears in two sequences"
    for s in list(sched.rows):
        if s is not None:
            sched.finish(s)
    assert pool.available == pool.capacity, "blocks leaked after drain"
    # refcounts can never go negative: the first over-free is an error
    ids = pool.alloc(1)
    pool.free(ids)
    with pytest.raises(RuntimeError, match="double free"):
        pool.free(ids)


def test_pool_pspecs_shard_heads_only():
    """pool_pspecs slices exactly the KV-head axis (2) over "model" for
    every pool leaf, and names the int8 scale planes iff the config
    carries int8 KV."""
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config

    cfg = get_config("opus-mt", smoke=True)
    specs = pool_pspecs(cfg)
    assert set(specs) == {"k", "v"}
    specs8 = pool_pspecs(dataclasses.replace(cfg, kv_cache_bits=8))
    assert set(specs8) == {"k", "v", "ks", "vs"}
    for spec in list(specs.values()) + list(specs8.values()):
        assert spec == P(None, None, "model", None, None)

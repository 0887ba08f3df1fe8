"""Pallas paged-attention kernel vs the jnp gather oracle.

The kernel (`kernels/paged_attention.py`) streams only the block-table
entries that hold valid context and dequantizes int8 K/V in VMEM; the
oracle (`span_attention_paged(..., impl="ref")`) gathers the full logical
pool view. These tests pin the contract between them:

  * numerically matching outputs on every valid span position, across
    mixed prefill-chunk + decode + idle spans, GQA, logit soft-capping,
    and bf16/f32/int8 KV pools;
  * trash-block padding and blocks past the valid count are NEVER read by
    the kernel (poisoned-pool proof);
  * token-identical greedy generation through `engine.serve` for both
    KV formats — the acceptance bar of the kernel PR;
  * the bytes-moved model scales with ctx_lens (stream) vs pool capacity
    (gather), strictly favoring the kernel whenever ctx < capacity.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import InferenceEngine, SamplingParams
from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.kernels import paged_attention as pa
from repro.models import attention as attn
from repro.runtime import kvblocks


def _mk_cfg(**kw):
    base = dict(name="pa-test", layout="dense", num_layers=1, d_model=32,
                num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                dtype="float32", remat=False)
    base.update(kw)
    return ModelConfig(**base)


def _mk_state(cfg, key, *, B=3, W=4, MB=4, bs=4,
              ctx=(5, 0, 9), ql=(3, 0, 1), poison=None):
    """Params, a pre-populated single-layer pool, block tables, and a span
    batch: row 0 = mid-prompt chunk, row 1 = idle, row 2 = decode.

    Table entries past a row's valid blocks point at the reserved trash
    block 0 or at the pool's last block, which no row owns; `poison`
    fills both."""
    ks = jax.random.split(key, 6)
    params = attn.attn_init(ks[0], cfg, jnp.dtype(cfg.dtype))
    nb_pool = 2 + sum(-(-(c + q) // bs) for c, q in zip(ctx, ql))
    pool = {k: v[0] for k, v in kvblocks.init_paged_cache(
        dataclasses.replace(cfg, num_layers=1), nb_pool, bs).items()}
    if "ks" in pool:
        shp = pool["k"].shape
        pool["k"] = jax.random.randint(ks[1], shp, -127, 128).astype(jnp.int8)
        pool["v"] = jax.random.randint(ks[2], shp, -127, 128).astype(jnp.int8)
        pool["ks"] = jax.random.uniform(ks[3], pool["ks"].shape,
                                        jnp.float32, 0.01, 0.1)
        pool["vs"] = jax.random.uniform(ks[4], pool["vs"].shape,
                                        jnp.float32, 0.01, 0.1)
    else:
        dt = pool["k"].dtype
        pool["k"] = jax.random.normal(ks[1], pool["k"].shape, dt)
        pool["v"] = jax.random.normal(ks[2], pool["v"].shape, dt)
    if poison is not None:
        # blocks the kernel must never read: the reserved trash block and
        # the unowned block that padding entries inside a chunk point at
        pool["k"] = pool["k"].at[0].set(poison).at[-1].set(poison)
        pool["v"] = pool["v"].at[0].set(poison).at[-1].set(poison)
    bt = np.zeros((len(ctx), MB), np.int32)
    nxt = 1
    for r, (c, q) in enumerate(zip(ctx, ql)):
        need = -(-(c + q) // bs)
        bt[r, :need] = np.arange(nxt, nxt + need)
        bt[r, need:need + 1] = nb_pool - 1
        nxt += need
    x = jax.random.normal(ks[5], (B, W, cfg.d_model), jnp.dtype(cfg.dtype))
    return (params, pool, jnp.asarray(bt), jnp.asarray(ctx, jnp.int32),
            jnp.asarray(ql, jnp.int32), x)


def _both(cfg, state):
    params, pool, bt, ctx, ql, x = state
    yr, pr = attn.span_attention_paged(params, x, pool, bt, ctx, ql, cfg,
                                       impl="ref")
    yk, pk = attn.span_attention_paged(params, x, pool, bt, ctx, ql, cfg,
                                       impl="kernel")
    return (yr, pr), (yk, pk)


def _assert_span_close(yr, yk, ql, *, rtol, atol):
    for r in range(yr.shape[0]):
        n = int(ql[r])
        if n:
            np.testing.assert_allclose(
                np.asarray(yr[r, :n], np.float32),
                np.asarray(yk[r, :n], np.float32), rtol=rtol, atol=atol)


# ------------------------------------------------------- kernel vs oracle --
def test_kernel_matches_oracle_f32_mixed_spans():
    """Mixed chunk + idle + decode spans, GQA (Hk < H), fp32: the kernel
    reproduces the gather oracle to fp32 round-off, and both paths write
    the identical scattered pool."""
    cfg = _mk_cfg()
    state = _mk_state(cfg, jax.random.PRNGKey(0))
    (yr, pr), (yk, pk) = _both(cfg, state)
    for k in pr:
        np.testing.assert_array_equal(np.asarray(pr[k]), np.asarray(pk[k]))
    _assert_span_close(yr, yk, state[4], rtol=2e-5, atol=2e-5)


def test_kernel_matches_oracle_int8_kv_and_softcap():
    """int8 KV pool (in-kernel dequant) + Gemma-style logit soft-capping,
    bf16 activations: matches the oracle to bf16 round-off."""
    cfg = _mk_cfg(dtype="bfloat16", kv_cache_bits=8, logit_softcap=30.0)
    state = _mk_state(cfg, jax.random.PRNGKey(1))
    (yr, _), (yk, _) = _both(cfg, state)
    # outputs are O(10) bf16 values (codes up to 127 x scales up to 0.1):
    # atol of one bf16 ulp at that magnitude, since online softmax and the
    # one-shot softmax legitimately round the last bit differently
    _assert_span_close(yr, yk, state[4], rtol=2e-2, atol=1e-1)


def test_kernel_never_reads_trash_or_invalid_blocks():
    """Poison the reserved trash block, and the block that table entries
    just past each row's valid count point at (inside the chunk the
    kernel fetches), with huge values: the kernel's output must equal the
    clean-pool output bit for bit — proof the DMA walk never touches
    table padding (the oracle relies on masking instead; both must agree
    on the valid region either way)."""
    cfg = _mk_cfg()
    clean = _mk_state(cfg, jax.random.PRNGKey(2))
    poisoned = _mk_state(cfg, jax.random.PRNGKey(2), poison=1e30)
    params, pool, bt, ctx, ql, x = poisoned
    _, (yk_clean, _) = _both(cfg, clean)
    yk_poison, _ = attn.span_attention_paged(params, x, pool, bt, ctx, ql,
                                             cfg, impl="kernel")
    _assert_span_close(yk_clean, yk_poison, ql, rtol=0, atol=0)


def _chunked_state(kv, g, w, key):
    """Rows at nb = 0, 1, P-1, P, P+1 and 3P valid blocks in a table of
    MB = 3P entries (bs 16, P = 8 table entries per chunk): idle, a lone
    block, partial and full single chunks, a partial last chunk, and a
    full table. Physical blocks are shuffled; the pool's last block is
    unowned. Each span is as wide as the row allows, up to w."""
    bs = 16
    p = pa.chunk_blocks(bs, 10 ** 6)
    mb = 3 * p
    nbs = (0, 1, p - 1, p, p + 1, 3 * p)
    ql = [min(w, n * bs) if n else 0 for n in nbs]
    ctx = [n * bs - q if n else 0 for n, q in zip(nbs, ql)]
    dtype = {"f32": "float32"}.get(kv, "bfloat16")
    cfg = _mk_cfg(dtype=dtype, num_heads=2 * g, num_kv_heads=2,
                  head_dim=16, kv_cache_bits=8 if kv == "int8" else 16,
                  logit_softcap=30.0 if kv == "int8" else 0.0)
    n_blocks = 2 + sum(nbs)
    ks = jax.random.split(key, 5)
    shp = (n_blocks, 2, bs, 16)
    if kv == "int8":
        pool = {"k": jax.random.randint(ks[0], shp, -127, 128, jnp.int8),
                "v": jax.random.randint(ks[1], shp, -127, 128, jnp.int8),
                "ks": jax.random.uniform(ks[2], shp[:3] + (1,),
                                         jnp.float32, 0.01, 0.1),
                "vs": jax.random.uniform(ks[3], shp[:3] + (1,),
                                         jnp.float32, 0.01, 0.1)}
    else:
        pool = {"k": jax.random.normal(ks[0], shp, jnp.dtype(dtype)),
                "v": jax.random.normal(ks[1], shp, jnp.dtype(dtype))}
    ids = np.random.default_rng(0).permutation(np.arange(1, n_blocks - 1))
    bt = np.zeros((len(nbs), mb), np.int32)
    at = 0
    for r, n in enumerate(nbs):
        bt[r, :n] = ids[at:at + n]
        at += n
    q = jax.random.normal(ks[4], (len(nbs), w, 2 * g, 16), jnp.dtype(dtype))
    return (cfg, q, pool, jnp.asarray(bt), jnp.asarray(ctx, jnp.int32),
            jnp.asarray(ql, jnp.int32), nbs)


@pytest.mark.parametrize("w", [1, 8])
@pytest.mark.parametrize("g", [1, 4])
@pytest.mark.parametrize("kv", ["f32", "bf16", "int8"])
def test_kernel_matches_oracle_across_chunks(kv, g, w):
    """Tables three chunks wide, rows ending inside, at and just past a
    chunk boundary: the chunked walk (double-buffered copies, a partial
    last chunk masked in the scores) matches the gather oracle on every
    valid position, for f32, bf16 and int8 KV (with soft-cap), plain and
    grouped heads, decode and span widths; idle rows stay zero."""
    cfg, q, pool, bt, ctx, ql, nbs = _chunked_state(
        kv, g, w, jax.random.PRNGKey(7))
    np.testing.assert_array_equal(
        np.asarray(kvblocks.valid_block_counts(ctx, ql, 16, bt.shape[1])),
        nbs)
    pos = ctx[:, None] + jnp.arange(w)[None, :]
    yr = attn._span_attend_gather(q, pool, bt, pos, cfg)
    yk = pa.paged_attention(q, pool, bt, ctx, ql,
                            logit_softcap=cfg.logit_softcap, interpret=True)
    tol = {"f32": (2e-5, 2e-5), "bf16": (2e-2, 2e-2),
           "int8": (2e-2, 1e-1)}[kv]
    _assert_span_close(yr, yk, ql, rtol=tol[0], atol=tol[1])
    np.testing.assert_array_equal(np.asarray(yk[0], np.float32), 0.0)


def test_idle_rows_emit_zeros_and_skip_work():
    """q_lens == 0 rows return exactly zero from the kernel (the oracle
    computes garbage there; both are discarded by the caller — zeros just
    prove the kernel skipped the row entirely)."""
    cfg = _mk_cfg()
    params, pool, bt, ctx, ql, x = _mk_state(cfg, jax.random.PRNGKey(3))
    q = jax.random.normal(jax.random.PRNGKey(9), (3, 4, 4, 8), jnp.float32)
    o = pa.paged_attention(q, pool, bt, ctx, ql, interpret=True)
    assert int(ql[1]) == 0
    np.testing.assert_array_equal(np.asarray(o[1]), np.zeros_like(o[1]))


def test_valid_block_counts():
    ctx = jnp.asarray([0, 5, 16, 9, 100], jnp.int32)
    ql = jnp.asarray([4, 3, 1, 0, 1], jnp.int32)
    nb = kvblocks.valid_block_counts(ctx, ql, 4, 8)
    # idle rows count zero; others ceil((ctx+q)/bs), clamped to the table
    np.testing.assert_array_equal(np.asarray(nb), [1, 2, 5, 0, 8])


# --------------------------------------------------------- through serve --
@pytest.mark.parametrize("kv_bits", [16, 8])
def test_serve_token_identical_kernel_vs_oracle(kv_bits):
    """The acceptance bar: greedy engine.serve emits identical tokens
    whether serving attention runs the Pallas kernel or the jnp gather
    oracle — mixed ragged prompts (chunked prefill + decode + idle rows),
    GQA, fp32 model, both KV formats."""
    cfg = dataclasses.replace(get_config("opus-mt", smoke=True),
                              num_kv_heads=2, kv_cache_bits=kv_bits)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=s).astype(np.int32)
               for s in (5, 11, 8)]
    sp = SamplingParams(max_tokens=6)
    outs = {}
    for impl in ("ref", "kernel"):
        eng = InferenceEngine.build(cfg, None, paged_attn=impl)
        res = eng.serve(prompts, sp, max_batch=4, block_size=4)
        outs[impl] = np.stack(res.outputs)
    np.testing.assert_array_equal(outs["ref"], outs["kernel"])


@pytest.mark.parametrize("kv_bits", [16, 8])
def test_serve_token_identical_bf16(kv_bits):
    """Same bar on a bfloat16 GQA model: the kernel's online softmax must
    not flip greedy tokens even at bf16 logits."""
    cfg = _mk_cfg(name="pa-bf16", num_layers=2, d_model=64, d_ff=128,
                  vocab_size=256, dtype="bfloat16", kv_cache_bits=kv_bits)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab_size, size=s).astype(np.int32)
               for s in (7, 3, 12)]
    sp = SamplingParams(max_tokens=8)
    outs = {}
    for impl in ("ref", "kernel"):
        eng = InferenceEngine.build(cfg, None, paged_attn=impl)
        outs[impl] = np.stack(
            eng.serve(prompts, sp, max_batch=4, block_size=4).outputs)
    np.testing.assert_array_equal(outs["ref"], outs["kernel"])


def test_paged_attn_impl_validation_and_auto():
    cfg = _mk_cfg(paged_attn_impl="bogus")
    with pytest.raises(ValueError, match="paged_attn_impl"):
        attn._paged_impl(cfg)
    auto = attn._paged_impl(_mk_cfg())
    assert auto == ("kernel" if jax.default_backend() == "tpu" else "ref")


# ------------------------------------------------------------ byte model --
def test_stream_bytes_scale_with_ctx_not_pool():
    """The bytes-moved claim of the PR: the kernel's modeled traffic
    grows with ctx_lens and stays strictly below the gather path whenever
    ctx < pool capacity; the gather path is flat in ctx."""
    bs, hk, dh, mb, b = 16, 4, 64, 32, 4
    short = pa.stream_hbm_bytes([16, 8, 0, 24], [8, 1, 0, 8], bs, hk, dh)
    long_ = pa.stream_hbm_bytes([400, 290, 0, 500], [8, 1, 0, 8], bs, hk, dh)
    gather = pa.gather_hbm_bytes(b, mb, bs, hk, dh, w=8)
    # gather_hbm_bytes takes no ctx argument at all — flat in context by
    # construction — so the property under test is the stream ordering:
    assert short < long_ < gather
    # int8 KV: gather additionally round-trips the dense dequantized
    # view, so the stream/gather gap widens
    s8 = pa.stream_hbm_bytes([400, 290, 0, 500], [8, 1, 0, 8], bs, hk, dh,
                             kv_bits=8)
    g8 = pa.gather_hbm_bytes(b, mb, bs, hk, dh, kv_bits=8, w=8)
    assert s8 / g8 < long_ / gather
    # idle rows stream nothing, never their stale ctx
    assert pa.stream_hbm_bytes([100], [0], bs, hk, dh) == 0


def test_tpu_model_prices_paged_attention():
    from repro.hw import tpu_model as tm

    ctx, ql = [400, 290, 0, 500], [8, 1, 0, 8]
    sp = tm.paged_attention_point(ctx, ql, num_kv_heads=4, head_dim=64,
                                  num_heads=8, block_size=16, max_blocks=32)
    gp = tm.paged_attention_point(ctx, ql, num_kv_heads=4, head_dim=64,
                                  num_heads=8, block_size=16, max_blocks=32,
                                  streamed=False)
    assert sp.kind == "pattn_stream" and gp.kind == "pattn_gather"
    assert sp.hbm_bytes < gp.hbm_bytes
    assert sp.latency_s < gp.latency_s          # decode attn is bw-bound
    assert sp.memory_s >= sp.compute_s

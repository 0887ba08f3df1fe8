"""Serving throughput: in-flight batching (chunked prefill, unified
token-budget step) vs static rectangular batching.

Not a paper figure — ITERA-LLM stops at the compressed linear layer; this
benchmark extends the reproduction to the serving regime the ROADMAP
targets (cf. TensorRT-LLM inflight batching and the batching survey in
arXiv:2408.03130). Both modes run the SAME mixed-length synthetic
workload on the SAME compiled engine:

  * static     — requests grouped FCFS into rectangular batches; prompts
    right-padded to the group max, every row decodes until the group's
    longest request finishes (the pre-scheduler `generate` path);
  * continuous — `InferenceEngine.serve`: ONE jitted token-budget step
    per iteration that mixes prefill chunks of newly admitted prompts
    with in-flight decode rows over the blocked KV pool — admissions
    never stall decode (the old loop prefilled each admitted prompt
    alone while the whole decode batch waited; `mixed_steps` counts the
    steps where chunks and decode now overlap, the decode-stall
    elimination this benchmark exists to measure, and the TTFT/TPOT
    percentiles show where that time goes).

Throughput counts only *useful* tokens (each request's own max_tokens),
so static batching pays for its padding and tail steps. Emits
BENCH_serving.json; the acceptance bar is continuous >= static tok/s.

  PYTHONPATH=src:benchmarks python benchmarks/fig13_serving.py \
      --out BENCH_serving.json

  # CI smoke: tiny workload, seconds on CPU, asserts both modes agree
  PYTHONPATH=src:benchmarks python benchmarks/fig13_serving.py --smoke
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.api import DraftSpec, InferenceEngine, Request, SamplingParams
from repro.configs import get_config
from repro.core.compress import CompressionConfig, shape_spectra
from repro.models import transformer as tfm

# length buckets keep the number of distinct jit shapes small; the mix of
# short/long generations is what continuous batching exploits.
PROMPT_LENS = (8, 16, 24, 32)
GEN_LENS = (2, 4, 8, 24)

# the speculation section runs decode-heavy (short prompts, long
# generations): that is the regime where a round's k cheap draft passes
# amortize — prefill-heavy mixes leave no per-step budget for drafting.
SPEC_PROMPT_LENS = (8, 12, 16)
SPEC_GEN_LENS = (32, 48, 64)
SPEC_MAX_BATCH = 2
SPEC_CHUNK_TOKENS = 16
SPEC_ALPHA = 3.0          # power-law spectrum exponent for the proxy
SPEC_N = 8                # short admission queue: decode rounds, not
                          # prefill churn, must dominate the section


def make_workload(n, vocab, seed=0, prompt_lens=PROMPT_LENS,
                  gen_lens=GEN_LENS):
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n):
        plen = int(rng.choice(prompt_lens))
        gen = int(rng.choice(gen_lens))
        reqs.append(Request(tokens=rng.integers(0, vocab, size=plen),
                            max_tokens=gen))
    return reqs


def run_static(engine, reqs, max_batch):
    """FCFS rectangular groups: pad prompts to the group max (repeating
    each row's last token), decode to the group's longest request."""
    seconds = 0.0
    steps = 0
    for i in range(0, len(reqs), max_batch):
        group = reqs[i:i + max_batch]
        s = max(r.tokens.size for r in group)
        gen = max(r.max_tokens for r in group)
        batch = np.stack([np.pad(r.tokens, (0, s - r.tokens.size),
                                 mode="edge") for r in group])
        res = engine.generate(batch, SamplingParams(max_tokens=gen))
        seconds += res.seconds
        steps += gen
    useful = sum(r.max_tokens for r in reqs)
    return {"seconds": seconds, "decode_steps": steps,
            "useful_tokens": useful,
            "tokens_per_second": useful / max(seconds, 1e-9)}


def run_continuous(engine, reqs, max_batch, block_size, chunk_tokens):
    res = engine.serve(reqs, max_batch=max_batch, block_size=block_size,
                       chunk_tokens=chunk_tokens)
    return {"seconds": res.seconds, "steps": res.steps,
            "prefill_chunks": res.prefill_chunks,
            "prefill_tokens": res.prefill_tokens,
            "mixed_steps": res.mixed_steps,
            "chunk_tokens": res.chunk_tokens,
            "max_queue_depth": res.max_queue_depth,
            "ttft_p50_s": res.ttft_p50, "ttft_p95_s": res.ttft_p95,
            "tpot_p50_s": res.tpot_p50, "tpot_p95_s": res.tpot_p95,
            "useful_tokens": res.total_tokens,
            "tokens_per_second": res.tokens_per_second}, res.outputs


def run_speculation(args):
    """Self-speculative decoding section: the SAME low-rank engine served
    with its truncated-cascade draft model on vs off, decode-heavy
    workload. The reported speedup is real tokens per second, so
    rejected drafts are paid for honestly, and token identity vs the
    plain path is hard-asserted request by request on every run.

    This section pins its own regime instead of inheriting the timed
    comparison's, because speculation only ever pays where a decode step
    does NOT cost proportionally to the tokens it carries. On the TPU
    target that is ordinary decode (weight-streaming-bound: a width-k+1
    verify moves the same bytes as a width-1 step — the premise the
    paper's sub-8-bit residency work is built on). The CPU proxy at full
    size is the opposite — compute-bound, cost ∝ tokens, so every
    drafted-then-verified token is paid twice and NO draft can win; its
    dispatch-bound regime (smoke geometry, small batch) is the regime
    where step cost is ~flat, so that is what this section serves.

    The proxy's weights are spectrum-shaped before compression
    (`shape_spectra`): random-init matrices have near-flat singular
    spectra, which makes ANY rank truncation argmax-flipping — an
    artifact of the proxy, not a property of the trained weights the
    paper targets, whose decaying spectra are the reason low-rank
    compression works at all. Shaping restores that regime so the
    draft's acceptance rate measures the design, not init noise."""
    plan = CompressionConfig(method="svd", weight_wl=8, rank_fraction=0.75)
    spec = DraftSpec(k=args.speculate,
                     rank_fraction=args.draft_rank_fraction)
    cfg = get_config("opus-mt", smoke=True)
    params = shape_spectra(tfm.init_params(jax.random.PRNGKey(args.seed),
                                           cfg), alpha=SPEC_ALPHA)
    engine = InferenceEngine.build(cfg, plan, params=params,
                                   max_batch=SPEC_MAX_BATCH,
                                   block_size=args.block_size,
                                   chunk_tokens=SPEC_CHUNK_TOKENS,
                                   speculate=spec)
    n = min(args.n, SPEC_N)
    reqs = make_workload(n, engine.cfg.vocab_size, seed=args.seed,
                         prompt_lens=SPEC_PROMPT_LENS,
                         gen_lens=SPEC_GEN_LENS)
    engine.serve(reqs, speculate=False)                # warmup both modes
    engine.serve(reqs, speculate=True)
    base = on = None
    ratios = []
    # the section is seconds long, so extra paired repeats are cheap and
    # the median ratio needs them (smoke-scale walltime is noisy)
    for _ in range(max(args.repeat, 5)):
        r0 = engine.serve(reqs, speculate=False)
        r1 = engine.serve(reqs, speculate=True)
        mism = [i for i in range(len(reqs))
                if not np.array_equal(r0.outputs[i], r1.outputs[i])]
        assert not mism, (
            f"request {mism[0]}: speculative {r1.outputs[mism[0]]} "
            f"!= plain {r0.outputs[mism[0]]}")
        if base is None or r0.seconds < base.seconds:
            base = r0
        if on is None or r1.seconds < on.seconds:
            on = r1
        ratios.append(r1.tokens_per_second / r0.tokens_per_second)
    print(f"speculation: k={on.spec_k} accept {on.accept_rate:.2f} "
          f"({on.accepted}/{on.drafted} over {on.spec_rounds} rounds), "
          f"{on.tokens_per_second:.1f} tok/s vs "
          f"{base.tokens_per_second:.1f} plain "
          f"({float(np.median(ratios)):.2f}x); "
          f"{len(reqs)}/{len(reqs)} requests token-identical")
    return {
        "k": on.spec_k,
        "rank_fraction": args.draft_rank_fraction,
        "plan": "svd_W8_r0.75",
        "regime": {"model": cfg.name, "max_batch": SPEC_MAX_BATCH,
                   "chunk_tokens": SPEC_CHUNK_TOKENS,
                   "spectrum_alpha": SPEC_ALPHA},
        "workload": {"n": n, "prompt_lens": list(SPEC_PROMPT_LENS),
                     "gen_lens": list(SPEC_GEN_LENS), "seed": args.seed},
        "accept_rate": on.accept_rate,
        "drafted": on.drafted, "accepted": on.accepted,
        "spec_rounds": on.spec_rounds, "steps": on.steps,
        "baseline_steps": base.steps,
        "mismatched_requests": 0,
        "tokens_per_second": on.tokens_per_second,
        "baseline_tokens_per_second": base.tokens_per_second,
        "speedup_vs_plain": float(np.median(ratios)),
    }


# the prefix-cache section models production traffic: most requests open
# with the same long system prompt, so their full KV blocks are shared by
# reference and prefill restarts at the first uncached position. A small
# max_batch keeps first-step co-admissions (which cannot share: their
# blocks are unwritten) from diluting the hit rate the workload offers.
PC_PREFIX_LEN = 512
PC_PREFIX_LEN_SMOKE = 48
PC_SHARED_FRACTION = 0.9
PC_TAIL_LENS = (4, 8, 12, 16)
PC_GEN_LENS = (4, 8)
PC_N = 32
PC_MAX_BATCH = 3
PC_REPEAT = 2             # cache-off prefills ~n*prefix tokens per run


def make_shared_prefix_workload(n, vocab, prefix_len, seed=0,
                                shared_fraction=PC_SHARED_FRACTION,
                                tail_lens=PC_TAIL_LENS,
                                gen_lens=PC_GEN_LENS):
    """`shared_fraction` of n requests = the same `prefix_len`-token
    system prompt + a private tail; the rest are short unrelated prompts
    (arrival order shuffled, so sharers and strangers interleave)."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, size=prefix_len)
    shared = np.zeros(n, bool)
    shared[:round(n * shared_fraction)] = True
    rng.shuffle(shared)
    reqs = []
    for s in shared:
        tail = int(rng.choice(tail_lens))
        toks = (np.concatenate([prefix, rng.integers(0, vocab, size=tail)])
                if s else rng.integers(0, vocab, size=2 * tail))
        reqs.append(Request(tokens=toks,
                            max_tokens=int(rng.choice(gen_lens))))
    return reqs


def run_prefix_cache(engine, args):
    """Prefix-caching section: the SAME engine serves the shared-prefix
    workload with the cache on vs off. Identity is hard-asserted request
    by request on every repeat (the cache must be a pure perf feature);
    the full-size run also hard-asserts the acceptance bar — hit rate
    > 0.8, better TTFT p50, higher tok/s — while --smoke only checks
    identity and a nonzero hit rate (its workload is too small for the
    0.8 bar). The pool gets headroom beyond the worst-case rows so the
    shared prefix blocks survive request rotation instead of being
    LRU-evicted the moment their holders finish."""
    from repro.hw.tpu_model import prefix_cache_point

    prefix_len = PC_PREFIX_LEN_SMOKE if args.smoke else PC_PREFIX_LEN
    n = min(args.n, 8) if args.smoke else PC_N
    cap = min(args.max_batch, PC_MAX_BATCH)
    bs = args.block_size
    cfg = engine.cfg
    reqs = make_shared_prefix_workload(n, cfg.vocab_size, prefix_len,
                                       seed=args.seed)
    from repro.runtime.kvblocks import blocks_needed

    mb = max(blocks_needed(r.tokens.size, r.max_tokens, bs) for r in reqs)
    kw = dict(max_batch=cap, block_size=bs, chunk_tokens=args.chunk_tokens,
              num_blocks=cap * mb + prefix_len // bs + 1)
    engine.serve(reqs, prefix_cache=False, **kw)       # warmup both modes
    engine.serve(reqs, prefix_cache=True, **kw)
    base = on = None
    ratios = []
    for _ in range(max(min(args.repeat, PC_REPEAT), 1)):
        r0 = engine.serve(reqs, prefix_cache=False, **kw)
        r1 = engine.serve(reqs, prefix_cache=True, **kw)
        mism = [i for i in range(len(reqs))
                if not np.array_equal(r0.outputs[i], r1.outputs[i])]
        assert not mism, (
            f"request {mism[0]}: cache-on {r1.outputs[mism[0]]} "
            f"!= cache-off {r0.outputs[mism[0]]}")
        if base is None or r0.seconds < base.seconds:
            base = r0
        if on is None or r1.seconds < on.seconds:
            on = r1
        ratios.append(r1.tokens_per_second / r0.tokens_per_second)
    hit_rate = on.cache_hit_token_rate
    if args.smoke:
        assert hit_rate > 0.0, "smoke shared-prefix workload never hit"
    else:
        assert hit_rate > 0.8, f"hit rate {hit_rate:.3f} <= 0.8"
        assert on.ttft_p50 < base.ttft_p50, (
            f"TTFT p50 did not improve: {on.ttft_p50:.3f}s cached vs "
            f"{base.ttft_p50:.3f}s")
        assert on.tokens_per_second > base.tokens_per_second, (
            f"tok/s did not improve: {on.tokens_per_second:.1f} cached "
            f"vs {base.tokens_per_second:.1f}")
    point = prefix_cache_point(
        prefix_len, hit_rate, num_layers=cfg.num_layers,
        d_model=cfg.d_model, d_ff=cfg.d_ff, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        block_size=bs, kv_bits=getattr(cfg, "kv_cache_bits", 16))
    print(f"prefix cache: hit rate {hit_rate:.2f} "
          f"({on.cache_hit_blocks}/{on.cache_lookup_blocks} blocks, "
          f"{on.cache_hit_tokens} prompt tokens skipped, "
          f"{on.cache_blocks_saved} blocks saved, {on.cache_cow_blocks} "
          f"COW), {on.tokens_per_second:.1f} tok/s vs "
          f"{base.tokens_per_second:.1f} off "
          f"({float(np.median(ratios)):.2f}x), TTFT p50 "
          f"{on.ttft_p50 * 1e3:.0f}ms vs {base.ttft_p50 * 1e3:.0f}ms; "
          f"{len(reqs)}/{len(reqs)} requests token-identical")
    return {
        "workload": {"n": n, "prefix_len": prefix_len,
                     "shared_fraction": PC_SHARED_FRACTION,
                     "tail_lens": list(PC_TAIL_LENS),
                     "gen_lens": list(PC_GEN_LENS), "seed": args.seed,
                     "max_batch": cap, "block_size": bs,
                     "num_blocks": kw["num_blocks"]},
        "hit_rate": hit_rate,
        "hit_rate_blocks": on.cache_hit_rate,
        "cache_hit_tokens": on.cache_hit_tokens,
        "cache_hit_blocks": on.cache_hit_blocks,
        "cache_lookup_blocks": on.cache_lookup_blocks,
        "blocks_saved": on.cache_blocks_saved,
        "cow_blocks": on.cache_cow_blocks,
        "evictions": on.cache_evictions,
        "preemptions": on.preemptions,
        "mismatched_requests": 0,
        "ttft_p50_s_on": on.ttft_p50, "ttft_p95_s_on": on.ttft_p95,
        "ttft_p50_s_off": base.ttft_p50, "ttft_p95_s_off": base.ttft_p95,
        "tokens_per_second_on": on.tokens_per_second,
        "tokens_per_second_off": base.tokens_per_second,
        "speedup_vs_off": float(np.median(ratios)),
        "modeled": {"prefill_s": point.prefill_s,
                    "prefill_s_nocache": point.prefill_s_nocache,
                    "ttft_speedup": point.ttft_speedup,
                    "macs_saved": point.macs_saved,
                    "kv_bytes_saved": point.kv_bytes_saved},
    }


SAMPLE_TEMP = 0.8
SAMPLE_TOP_K = 40
SAMPLE_TOP_P = 0.95


def run_sampling(engine, reqs, args):
    """Sampled-serving section: the SAME engine serves the SAME workload
    greedy vs sampled (per-row temperature/top_k/top_p riding the one
    packed dispatch buffer — the fused step's only extra work is the
    static top-k candidate window). Three properties are hard-asserted
    on every run:

      * seeded reproducibility — two sampled serves under one seed are
        token-identical request by request;
      * temperature-0 identity — greedy rows inside a mixed sampled
        batch emit bit-identical tokens to the all-greedy serve (the
        fused sample branch reduces exactly to argmax for them);
      * at full size, sampled throughput >= 0.95x greedy tok/s (the
        acceptance bar: sampling must not fall off the greedy path).
        The ratio compares each mode's FASTEST run over the paired
        repeats (timeit-style min): serve-to-serve walltime on a
        shared box swings ~10%, which additive load noise explains and
        a per-pair median at small N cannot reject, while the ~2%
        true sampler cost is exactly what best-vs-best resolves.

    --smoke checks the identities only; smoke-scale walltime is
    dispatch noise, not a throughput claim. `hw.tpu_model.sampling_point`
    prices the fused selection against the host round-trip alternative
    (full logits over PCIe + a second dispatch per step) at this
    geometry — the comparison the fused design wins by construction."""
    from repro.hw.tpu_model import sampling_point

    cfg = engine.cfg
    sampled_sp = SamplingParams(
        max_tokens=max(r.max_tokens for r in reqs),
        temperature=SAMPLE_TEMP, top_k=SAMPLE_TOP_K, top_p=SAMPLE_TOP_P,
        seed=args.seed)

    def with_sampling(temp_every_other=False):
        return [Request(tokens=r.tokens, max_tokens=r.max_tokens,
                        temperature=0.0 if temp_every_other and i % 2
                        else SAMPLE_TEMP, top_k=SAMPLE_TOP_K,
                        top_p=SAMPLE_TOP_P, seed=args.seed)
                for i, r in enumerate(reqs)]

    engine.serve(reqs)                                 # warmup both modes
    engine.serve(with_sampling(), sampled_sp)
    greedy = samp = None
    ratios = []
    for _ in range(max(args.repeat, 1) if args.smoke
                   else max(args.repeat, 5)):
        r0 = engine.serve(reqs)
        r1 = engine.serve(with_sampling(), sampled_sp)
        r2 = engine.serve(with_sampling(), sampled_sp)
        mism = [i for i in range(len(reqs))
                if not np.array_equal(r1.outputs[i], r2.outputs[i])]
        assert not mism, (
            f"request {mism[0]}: seeded sampled serve not reproducible: "
            f"{r2.outputs[mism[0]]} != {r1.outputs[mism[0]]}")
        if greedy is None or r0.seconds < greedy.seconds:
            greedy = r0
        if samp is None or r1.seconds < samp.seconds:
            samp = r1
        ratios.append(r1.tokens_per_second / r0.tokens_per_second)
    # greedy rows in a mixed batch == the all-greedy serve, bit for bit
    mixed = engine.serve(with_sampling(temp_every_other=True), sampled_sp)
    for i in range(1, len(reqs), 2):
        assert np.array_equal(mixed.outputs[i], greedy.outputs[i]), (
            f"request {i}: temperature-0 row diverged from greedy serve: "
            f"{mixed.outputs[i]} != {greedy.outputs[i]}")
    ratio = samp.tokens_per_second / greedy.tokens_per_second
    if not args.smoke:
        assert ratio >= 0.95, (
            f"sampled serve {ratio:.3f}x greedy tok/s < 0.95x bar "
            f"({samp.tokens_per_second:.1f} vs "
            f"{greedy.tokens_per_second:.1f})")
    point = sampling_point(batch=args.max_batch, vocab=cfg.vocab_size)
    print(f"sampled:    {samp.tokens_per_second:8.1f} tok/s vs "
          f"{greedy.tokens_per_second:.1f} greedy ({ratio:.2f}x), "
          f"queue p50 {samp.queue_p50 * 1e3:.0f}ms, seeded runs + "
          f"temperature-0 rows token-identical; fused selection "
          f"{point.speedup_vs_host:.0f}x over host round-trip (modeled)")
    return {
        "temperature": SAMPLE_TEMP, "top_k": SAMPLE_TOP_K,
        "top_p": SAMPLE_TOP_P, "seed": args.seed,
        "reproducible_requests": len(reqs),
        "mismatched_requests": 0,
        "steps": samp.steps,
        "tokens_per_second": samp.tokens_per_second,
        "greedy_tokens_per_second": greedy.tokens_per_second,
        "throughput_vs_greedy": ratio,
        "paired_ratio_median": float(np.median(ratios)),
        "queue_p50_s": samp.queue_p50, "queue_p95_s": samp.queue_p95,
        "goodput_tok_per_s_at_2x_median": samp.goodput(
            2 * float(np.median(samp.finish_times))),
        "modeled": {"fused_s": point.fused_s, "host_s": point.host_s,
                    "speedup_vs_host": point.speedup_vs_host,
                    "overhead_vs_greedy": point.overhead_vs_greedy},
    }


TP_N = 8                  # requests in the TP section: identity + bytes
TP_REPEAT = 2             # accounting, not a perf claim (see run_tp)


def run_tp(args):
    """Tensor-parallel serving section: the SAME weights served through
    the shard_map engine on a (1, --mesh) device mesh vs the
    single-device engine, greedy outputs hard-asserted token-identical
    request by request on every run.

    On CPU the "devices" are forced host-platform devices sharing one
    processor (XLA_FLAGS=--xla_force_host_platform_device_count), so the
    tok/s column is a bookkeeping canary, NOT a scaling claim — the row
    that matters for the DSE is the communication side:
    `hw.tpu_model.tp_point` prices the step's 2L boundary all-reduces
    (ring wire bytes per chip, ICI seconds) at this geometry, which is
    what a real multi-chip deployment pays."""
    from repro.hw import tpu_model
    from repro.launch.mesh import make_serving_mesh

    if len(jax.devices()) < args.mesh:
        raise SystemExit(
            f"--mesh {args.mesh} needs {args.mesh} devices, have "
            f"{len(jax.devices())}: run under XLA_FLAGS=--xla_force_"
            f"host_platform_device_count={args.mesh}")
    cfg = get_config("opus-mt", smoke=args.smoke)
    params = tfm.init_params(jax.random.PRNGKey(args.seed), cfg)
    n = min(args.n, TP_N)
    reqs = make_workload(n, cfg.vocab_size, seed=args.seed)
    kw = dict(params=params, max_batch=args.max_batch,
              block_size=args.block_size, chunk_tokens=args.chunk_tokens)
    solo = InferenceEngine.build(cfg, None, **kw)
    tp = InferenceEngine.build(cfg, None, mesh=make_serving_mesh(args.mesh),
                               **kw)
    solo.serve(reqs)                                   # warmup both engines
    tp.serve(reqs)
    base = on = None
    for _ in range(max(min(args.repeat, TP_REPEAT), 1)):
        r0 = solo.serve(reqs)
        r1 = tp.serve(reqs)
        mism = [i for i in range(len(reqs))
                if not np.array_equal(r0.outputs[i], r1.outputs[i])]
        assert not mism, (
            f"request {mism[0]}: tp={args.mesh} {r1.outputs[mism[0]]} "
            f"!= single-device {r0.outputs[mism[0]]}")
        if base is None or r0.seconds < base.seconds:
            base = r0
        if on is None or r1.seconds < on.seconds:
            on = r1
    import jax.numpy as jnp

    point = tpu_model.tp_point(
        batch=args.max_batch, span_w=1, d_model=cfg.d_model,
        num_layers=cfg.num_layers, tp=args.mesh,
        dtype_bytes=jnp.dtype(cfg.dtype).itemsize)
    print(f"tp: mesh {args.mesh}, {on.tokens_per_second:.1f} tok/s vs "
          f"{base.tokens_per_second:.1f} single-device, "
          f"{point.allreduce_bytes / 1024:.1f} KiB all-reduce wire/chip/"
          f"step ({point.boundaries} boundaries, "
          f"{point.allreduce_s * 1e6:.1f}us ICI); "
          f"{len(reqs)}/{len(reqs)} requests token-identical")
    return {
        "mesh": args.mesh,
        "model": cfg.name,
        "workload": {"n": n, "prompt_lens": list(PROMPT_LENS),
                     "gen_lens": list(GEN_LENS), "seed": args.seed,
                     "max_batch": args.max_batch,
                     "block_size": args.block_size,
                     "chunk_tokens": args.chunk_tokens},
        "identical_requests": n,
        "mismatched_requests": 0,
        "steps": on.steps,
        "tokens_per_second": on.tokens_per_second,
        "baseline_tokens_per_second": base.tokens_per_second,
        "allreduce_boundaries_per_step": point.boundaries,
        "allreduce_payload_bytes": point.payload_bytes,
        "allreduce_bytes_per_step": point.allreduce_bytes,
        "allreduce_s_per_step": point.allreduce_s,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=24, help="number of requests")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--chunk-tokens", type=int, default=256,
                    help="unified-step token budget")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeat", type=int, default=3,
                    help="timed repetitions per mode; the fastest run "
                         "is reported (wall-clock noise rejection)")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI workload (seconds on CPU): fewer "
                         "requests, one warmup, and a hard assert that "
                         "greedy outputs match between the two modes")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="also benchmark self-speculative decoding at "
                         "draft depth K on a low-rank engine "
                         "(dedicated dispatch-bound decode-heavy "
                         "regime, spec on vs off; outputs are asserted "
                         "token-identical)")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="also benchmark tensor-parallel serving on a "
                         "(1, N) device mesh: greedy outputs are hard-"
                         "asserted token-identical to the single-device "
                         "engine and the step's all-reduce traffic is "
                         "priced by hw.tpu_model.tp_point (needs N "
                         "devices; on CPU force them with XLA_FLAGS=--"
                         "xla_force_host_platform_device_count=N)")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="also benchmark prefix caching on a workload "
                         "where 90%% of requests share a long system "
                         "prompt: cache on vs off on the same engine, "
                         "outputs hard-asserted token-identical, hit "
                         "rate / blocks saved / TTFT / tok/s recorded")
    ap.add_argument("--sample", action="store_true",
                    help="also benchmark sampled serving (per-row "
                         "temperature/top_k/top_p fused into the one "
                         "jitted step): seeded reproducibility and "
                         "temperature-0 bit-identity are hard-asserted "
                         "on every run, and at full size sampled tok/s "
                         "must stay >= 0.95x greedy")
    ap.add_argument("--draft-rank-fraction", type=float, default=0.17,
                    help="rank fraction the speculation draft keeps "
                         "(0.17 of the r0.75 plan's rank 48 = rank 8 at "
                         "the section's geometry: the draft streams ~1/6 "
                         "of the cascade bytes, and the shaped spectrum "
                         "keeps its argmax agreeing with the full rank)")
    args = ap.parse_args(argv)

    if args.smoke:
        args.n = min(args.n, 8)
        args.max_batch = min(args.max_batch, 2)
        args.chunk_tokens = min(args.chunk_tokens, 16)
        args.repeat = 1

    # the timed comparison runs the FULL-SIZE proxy (d=512, 12 layers,
    # 32k vocab): at smoke-model scale every step is host-overhead-bound
    # and the tok/s ratio measures dispatch noise, not serving design.
    # --smoke keeps the tiny config for the CI smoke job (seconds, CPU).
    engine = InferenceEngine.build("opus-mt", None, smoke=args.smoke,
                                   max_batch=args.max_batch,
                                   block_size=args.block_size,
                                   chunk_tokens=args.chunk_tokens)
    reqs = make_workload(args.n, engine.cfg.vocab_size, seed=args.seed)

    # warmup pass compiles every (shape-bucketed) prefill/step variant so
    # the timed passes measure steady-state serving, not XLA compilation
    run_static(engine, reqs, args.max_batch)
    run_continuous(engine, reqs, args.max_batch, args.block_size,
                   args.chunk_tokens)

    # repeats run the two modes back to back, so each pair sees the same
    # background load; the reported speedup is the median of the paired
    # ratios (robust to load drift), absolute numbers are each mode's
    # fastest run.
    static = ct_out = cont = None
    ratios = []
    for _ in range(max(args.repeat, 1)):
        st = run_static(engine, reqs, args.max_batch)
        if static is None or st["seconds"] < static["seconds"]:
            static = st
        ct, out = run_continuous(engine, reqs, args.max_batch,
                                 args.block_size, args.chunk_tokens)
        if cont is None or ct["seconds"] < cont["seconds"]:
            cont, ct_out = ct, out
        ratios.append(ct["tokens_per_second"] / st["tokens_per_second"])
    speedup = float(np.median(ratios))

    if args.smoke:
        # greedy serve outputs must match per-prompt solo runs — the
        # serve loop can't silently rot behind a green tok/s number.
        # (Static-mode outputs are not the oracle: its edge-padding
        # extends short prompts, legitimately changing their tokens.)
        for i, r in enumerate(reqs):
            solo = engine.generate(
                np.asarray(r.tokens)[None],
                SamplingParams(max_tokens=r.max_tokens)).tokens[0]
            assert np.array_equal(np.asarray(ct_out[i]), solo), (
                f"request {i}: continuous {np.asarray(ct_out[i])} "
                f"!= solo {solo}")
        print(f"smoke: continuous outputs == solo generate for "
              f"{len(reqs)} requests")

    report = {
        "workload": {"n": args.n, "prompt_lens": list(PROMPT_LENS),
                     "gen_lens": list(GEN_LENS), "seed": args.seed,
                     "max_batch": args.max_batch,
                     "block_size": args.block_size,
                     "chunk_tokens": args.chunk_tokens},
        "static": static,
        "continuous": cont,
        "speedup": speedup,
    }
    if args.sample:
        report["sampled"] = run_sampling(engine, reqs, args)
    if args.shared_prefix:
        report["prefix_cache"] = run_prefix_cache(engine, args)
    if args.speculate > 0:
        report["speculation"] = run_speculation(args)
    if args.mesh > 0:
        report["tp"] = run_tp(args)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"static:     {static['tokens_per_second']:8.1f} tok/s "
          f"({static['decode_steps']} decode steps)")
    print(f"continuous: {cont['tokens_per_second']:8.1f} tok/s "
          f"({cont['steps']} unified steps, {cont['mixed_steps']} mixed, "
          f"{cont['prefill_chunks']} prefill chunks)")
    print(f"latency:    TTFT p50 {cont['ttft_p50_s'] * 1e3:.0f}ms / "
          f"p95 {cont['ttft_p95_s'] * 1e3:.0f}ms, "
          f"TPOT p50 {cont['tpot_p50_s'] * 1e3:.1f}ms")
    print(f"speedup:    {speedup:.2f}x  -> {args.out}")
    return report


if __name__ == "__main__":
    from repro.launch.compile_cache import use_checkout_cache

    use_checkout_cache()
    main()

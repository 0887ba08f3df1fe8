"""Serving CLI — a thin front-end over `repro.api.InferenceEngine`.

The inference face of ITERA-LLM: weights are compressed post-training per
a `CompressionPlan` (a DSE artifact, or a uniform plan built from the
legacy flags), then batched requests are prefilled and decoded by the
compiled engine.

  # deploy a DSE result (per-layer method x wl x rank):
  python -m repro.launch.serve --arch opus-mt --smoke --plan plan.json

  # or a uniform plan from flags (legacy CompressionConfig semantics):
  python -m repro.launch.serve --arch opus-mt --smoke --compression itera \
      --rank-fraction 0.4 --wl 4 --prompt-len 64 --gen 32 --batch 4

  # mixed-length prompts through the continuous-batching scheduler
  # (blocked KV cache; see docs/serving.md):
  python -m repro.launch.serve --arch opus-mt --smoke --ragged \
      --batch 8 --max-batch 4 --block-size 16

On TPU the same entry point dispatches the compiled Pallas kernels
(matmul cascades and paged attention); on CPU it runs the pure-jnp
reference math (kernels.ops.on_tpu decides both).
"""
from __future__ import annotations

import argparse

import jax.numpy as jnp
import numpy as np

from repro.api import (CompressionPlan, InferenceEngine, SamplingParams,
                       TokenEvent)
from repro.configs import get_config
from repro.core.compress import CompressionConfig
from repro.data import pipeline


async def serve_stream(engine, requests, sampling=None, **serve_kwargs):
    """Async streaming front door over `engine.serve`: yields each
    `TokenEvent` the moment the pipelined readback confirms it, then the
    final `ServeResult` as the last item.

    The serve loop runs unchanged on a worker thread (its 2-deep
    dispatch pipeline never blocks on the consumer); the engine's
    `on_token` callback bridges events onto the caller's running event
    loop with `call_soon_threadsafe`, so ordering is preserved and the
    consumer sees tokens at true completion time — not at drain. A
    serve-side exception is re-raised here after the events that
    preceded it.

        async for ev in serve_stream(engine, prompts, sampling):
            if isinstance(ev, TokenEvent):
                ...                     # stream ev.rid / ev.token out
            else:
                result = ev             # the closing ServeResult
    """
    import asyncio
    import threading

    loop = asyncio.get_running_loop()
    q: asyncio.Queue = asyncio.Queue()

    def on_token(ev: TokenEvent) -> None:
        loop.call_soon_threadsafe(q.put_nowait, ev)

    def run() -> None:
        try:
            res = engine.serve(requests, sampling, on_token=on_token,
                               **serve_kwargs)
        except BaseException as e:     # surface serve errors to the consumer
            loop.call_soon_threadsafe(q.put_nowait, e)
        else:
            loop.call_soon_threadsafe(q.put_nowait, res)

    threading.Thread(target=run, daemon=True).start()
    while True:
        item = await q.get()
        if isinstance(item, BaseException):
            raise item
        yield item
        if not isinstance(item, TokenEvent):   # the ServeResult closes it
            return


def generate(params, cfg, prompts, gen_len: int, *, greedy=True, seed=0):
    """Back-compat helper: decode `prompts` with already-built params.

    New code should hold an `InferenceEngine` and call `.generate` — this
    wrapper rebuilds the jitted callables on every call.
    """
    eng = InferenceEngine(cfg, params)
    res = eng.generate(prompts, SamplingParams(
        max_tokens=gen_len, temperature=0.0 if greedy else 1.0, seed=seed))
    return jnp.asarray(res.tokens)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="opus-mt")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--plan", default=None,
                    help="CompressionPlan JSON (e.g. a serialized DSE "
                         "design point); overrides --compression/--wl/"
                         "--rank-fraction")
    ap.add_argument("--compression", default="none",
                    choices=["none", "quant", "svd", "itera"])
    ap.add_argument("--wl", type=int, default=8)
    ap.add_argument("--rank-fraction", type=float, default=0.5)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=8,
                    help="continuous batching: batch-row capacity")
    ap.add_argument("--block-size", type=int, default=16,
                    help="continuous batching: KV-cache block size (tokens)")
    ap.add_argument("--chunk-tokens", type=int, default=256,
                    help="continuous batching: per-step token budget split "
                         "between prefill chunks and decode tokens")
    ap.add_argument("--paged-attn", default="auto",
                    choices=["auto", "kernel", "ref"],
                    help="serving attention over the blocked KV pool: "
                         "Pallas paged-attention kernel vs jnp gather "
                         "oracle (auto = kernel on TPU, oracle on CPU)")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="self-speculative decoding: draft K tokens per "
                         "decode row with the truncated low-rank cascade, "
                         "verify with the full model in the same dispatch "
                         "(greedy outputs are unchanged; needs --ragged "
                         "and a low-rank plan to actually save work)")
    ap.add_argument("--draft-rank-fraction", type=float, default=0.5,
                    help="fraction of each cascade's rank the draft model "
                         "keeps (see runtime.speculation.DraftSpec)")
    ap.add_argument("--draft-act-wl", type=int, default=None,
                    help="optional activation word length override for "
                         "the draft pass (default: inherit the plan's)")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="tensor-parallel serving: shard the engine over "
                         "a (1, N) device mesh — attention/KV heads and "
                         "MLP hidden dims split N ways, one all-reduce "
                         "per layer boundary (greedy outputs unchanged; "
                         "needs N devices — on CPU force them with "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N)")
    ap.add_argument("--ragged", action="store_true",
                    help="mixed-length demo: vary prompt lengths and serve "
                         "through the continuous-batching scheduler")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=True,
                    help="share KV blocks between requests with equal "
                         "full-block prompt prefixes (on by default; "
                         "greedy outputs are unchanged)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="<= 0 -> greedy decode (sampling is fused "
                         "in-device; seeded runs replay token-for-token)")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling threshold in (0, 1]; 1.0 "
                         "keeps the whole distribution")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop a request after it emits this token id "
                         "(evaluated on device, inclusive)")
    ap.add_argument("--stop", action="append", default=[], metavar="IDS",
                    help="stop token sequence as comma-separated ids "
                         "(repeatable; matched inclusively on device)")
    ap.add_argument("--stream", action="store_true",
                    help="with --ragged: consume the serve through the "
                         "async streaming front door (serve_stream) and "
                         "print tokens as they complete")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.plan is not None:
        plan = CompressionPlan.load(args.plan)
        print(f"[serve] {plan.summary()}")
    elif args.compression != "none":
        plan = CompressionConfig(method=args.compression, weight_wl=args.wl,
                                 rank_fraction=args.rank_fraction)
    else:
        plan = None

    speculate = None
    if args.speculate > 0:
        from repro.api import DraftSpec

        speculate = DraftSpec(k=args.speculate,
                              rank_fraction=args.draft_rank_fraction,
                              act_wl=args.draft_act_wl)
    mesh = None
    if args.mesh > 0:
        from repro.launch.mesh import make_serving_mesh

        mesh = make_serving_mesh(args.mesh)
        print(f"[serve] tensor-parallel over mesh (data=1, model="
              f"{args.mesh})")
    engine = InferenceEngine.build(cfg, plan, seed=args.seed, verbose=True,
                                   mesh=mesh,
                                   max_batch=args.max_batch,
                                   block_size=args.block_size,
                                   chunk_tokens=args.chunk_tokens,
                                   paged_attn=args.paged_attn,
                                   speculate=speculate,
                                   prefix_cache=args.prefix_cache)

    task = pipeline.MarkovTask(cfg.vocab_size, seed=args.seed)
    prompts = task.batch(0, args.batch, args.prompt_len)["tokens"]
    stop = tuple(tuple(int(t) for t in s.split(",")) for s in args.stop)
    sampling = SamplingParams(max_tokens=args.gen,
                              temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              seed=args.seed, eos_id=args.eos_id,
                              stop=stop)

    if args.ragged:
        # mixed-length workload: truncate each row to a different length
        base = np.asarray(prompts)
        lens = [max(4, args.prompt_len - 4 * (i % 4))
                for i in range(args.batch)]
        ragged = [base[i, :lens[i]] for i in range(args.batch)]
        if args.stream:
            import asyncio

            async def drive():
                shown = 0
                async for ev in serve_stream(engine, ragged, sampling):
                    if isinstance(ev, TokenEvent):
                        if shown < 8 or ev.final:
                            tag = " (final)" if ev.final else ""
                            print(f"[stream] rid={ev.rid} "
                                  f"#{ev.index}: {ev.token}{tag}")
                        shown += 1
                    else:
                        return ev

            res = asyncio.run(drive())
        else:
            res = engine.serve(ragged, sampling)
        print(f"[serve] in-flight batching: {len(ragged)} requests "
              f"(prompt lens {lens}) in {res.seconds:.1f}s — "
              f"{res.steps} unified steps ({res.mixed_steps} mixed), "
              f"{res.prefill_chunks} prefill chunks "
              f"({res.prefill_tokens} tokens, budget "
              f"{res.chunk_tokens}/step), peak queue "
              f"{res.max_queue_depth}, {res.tokens_per_second:.1f} tok/s")
        print(f"[serve] latency: TTFT p50 {res.ttft_p50 * 1e3:.0f}ms / "
              f"p95 {res.ttft_p95 * 1e3:.0f}ms, per-output-token p50 "
              f"{res.tpot_p50 * 1e3:.1f}ms / p95 {res.tpot_p95 * 1e3:.1f}ms")
        # goodput under a deadline of 2x the median finish time: requests
        # the queue starved past that contribute nothing
        deadline = 2 * float(np.median(res.finish_times))
        print(f"[serve] SLO: queue p50 {res.queue_p50 * 1e3:.0f}ms / "
              f"p95 {res.queue_p95 * 1e3:.0f}ms, goodput@{deadline:.1f}s "
              f"{res.goodput(deadline):.1f} tok/s, "
              f"{res.stopped_early} stopped early")
        if res.spec_k:
            print(f"[serve] speculation: k={res.spec_k}, accept rate "
                  f"{res.accept_rate:.2f} ({res.accepted}/{res.drafted} "
                  f"draft tokens over {res.spec_rounds} rounds)")
        if res.prefix_cache:
            print(f"[serve] prefix cache: hit rate "
                  f"{res.cache_hit_rate:.2f} "
                  f"({res.cache_hit_blocks}/{res.cache_lookup_blocks} "
                  f"blocks, {res.cache_hit_tokens} prompt tokens "
                  f"skipped), {res.cache_blocks_saved} blocks saved, "
                  f"{res.cache_cow_blocks} COW, "
                  f"{res.cache_evictions} evictions, "
                  f"{res.preemptions} preemptions")
        print("[serve] sample:", res.outputs[0][:16].tolist())
        out = np.zeros((len(res.outputs), args.gen), np.int32)
        for i, o in enumerate(res.outputs):   # stop-shortened rows: 0-pad
            out[i, :o.size] = o
        return out

    res = engine.generate(prompts, sampling)
    print(f"[serve] generated {res.tokens.shape} in {res.seconds:.1f}s "
          f"({res.tokens_per_second:.1f} tok/s)")
    print("[serve] sample:", np.asarray(res.tokens[0][:16]).tolist())
    return res.tokens


if __name__ == "__main__":
    from repro.launch.compile_cache import use_checkout_cache

    use_checkout_cache()
    main()

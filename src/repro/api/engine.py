"""`InferenceEngine` — the online half of the plan→engine seam.

Build compiles a serving engine from (architecture, CompressionPlan):
compress the weights per the plan, optionally place them on a device mesh,
and jit the prefill / step callables once. Generation then runs any
number of batched requests against the same compiled engine:

    plan = CompressionPlan.load("plan.json")          # e.g. a DSE winner
    eng = InferenceEngine.build("opus-mt", plan, smoke=True)
    out = eng.generate(prompts, SamplingParams(max_tokens=32, top_k=40))

Two serving paths share the compiled model:

  * `generate` on a rectangular (B, S) batch — prefill once (prompts are
    right-padded to power-of-two length buckets, so N distinct lengths
    cost O(log N) compilations), decode in lockstep; the static-batching
    baseline.
  * `serve` (which `generate` uses for ragged prompt lists) — in-flight
    batching with chunked prefill: every forward pass is ONE jitted
    token-budget step (`models.transformer.unified_step`) that mixes
    prefill chunks of newly admitted prompts with in-flight decode rows
    over a `runtime.kvblocks` blocked KV pool, scheduled by
    `runtime.scheduler.Scheduler`. There is no solo-prefill path: a
    prompt enters the pool chunk by chunk while older rows keep
    decoding, and rows leave the moment they finish, returning their
    blocks to the pool.

`launch.serve` is a thin CLI over this class; every future serving feature
(KV paging variants, multi-host decode) lands behind this facade rather
than in loose scripts.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.plan import CompressionPlan
from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core.compress import CompressionConfig, compress_params
from repro.kernels import paged_attention as pa
from repro.models import transformer as tfm
from repro.runtime import kvblocks, tracing
from repro.runtime.scheduler import Request, Scheduler
from repro.runtime.speculation import DraftSpec, SpeculationController


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-call sampling / stop controls (a `runtime.scheduler.Request`
    can override any of them per request). temperature <= 0 means
    greedy; top_k == 0 and top_p == 1.0 apply no truncation tighter
    than the sampler's static top-`sampling.TOPK_CAP` candidate window. `stop` is a tuple of token-id sequences matched inclusively
    — generation stops after emitting the token that completes a match,
    and the matched tokens stay in the output (see runtime/sampling.py);
    eos_id is a single-token stop. Seeded sampled runs are reproducible
    token-for-token across repeats, prefix-cache on/off, TP mesh sizes,
    and the generate()/serve() split (per-row counter-based PRNG keys)."""

    max_tokens: int = 32
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    eos_id: int | None = None
    stop: tuple = ()

    def __post_init__(self):
        if self.max_tokens < 1:
            raise ValueError(f"max_tokens must be >= 1, got {self.max_tokens}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.eos_id is not None and self.eos_id < 0:
            raise ValueError(f"eos_id must be >= 0, got {self.eos_id}")
        object.__setattr__(self, "stop", tuple(
            tuple(int(t) for t in s) for s in self.stop))
        if any(len(s) == 0 for s in self.stop):
            raise ValueError("empty stop sequence")

    def to_dict(self) -> dict:
        d = {"max_tokens": self.max_tokens, "temperature": self.temperature,
             "top_k": self.top_k, "top_p": self.top_p, "seed": self.seed}
        if self.eos_id is not None:
            d["eos_id"] = int(self.eos_id)
        if self.stop:
            d["stop"] = [list(s) for s in self.stop]
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "SamplingParams":
        return cls(max_tokens=int(d.get("max_tokens", 32)),
                   temperature=float(d.get("temperature", 0.0)),
                   top_k=int(d.get("top_k", 0)),
                   top_p=float(d.get("top_p", 1.0)),
                   seed=int(d.get("seed", 0)),
                   eos_id=(None if d.get("eos_id") is None
                           else int(d["eos_id"])),
                   stop=tuple(tuple(s) for s in d.get("stop", ())))


@dataclasses.dataclass(frozen=True)
class TokenEvent:
    """One streamed token, delivered by serve(on_token=...) the moment
    the pipelined readback confirms it (true completion time, the same
    timestamp TTFT/TPOT use). `index` is the token's position in the
    request's output; `final` marks the request's last token (its stop
    criterion fired or max_tokens was reached)."""

    rid: int
    token: int
    index: int
    time: float
    final: bool


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray          # (B, max_tokens) int32
    prompt_len: int             # ragged batches: the longest prompt
    seconds: float
    prompt_lens: list[int] | None = None   # set for ragged batches

    @property
    def tokens_per_second(self) -> float:
        b, g = self.tokens.shape
        return b * g / max(self.seconds, 1e-9)


def _percentile(xs, q) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else 0.0


@dataclasses.dataclass
class ServeResult:
    """In-flight batching outcome: per-request continuations in
    submission order, plus step/chunk/latency accounting."""

    outputs: list[np.ndarray]   # outputs[i]: (requests[i].max_tokens,) int32
    prompt_lens: list[int]
    seconds: float
    steps: int                  # unified token-budget steps executed
    prefill_chunks: int         # prompt chunks processed across all steps
    prefill_tokens: int         # prompt tokens entered via those chunks
    mixed_steps: int            # steps running prefill AND decode together
    chunk_tokens: int           # the per-step token budget
    max_queue_depth: int        # peak waiting-queue length (overflow proof)
    max_batch: int
    block_size: int
    num_blocks: int
    ttft: list[float] = dataclasses.field(default_factory=list)
    tpot: list[float] = dataclasses.field(default_factory=list)
    # self-speculative decoding accounting (0 when speculation is off):
    # over the whole serve, `drafted` draft tokens were proposed and
    # `accepted` of them survived full-model verification across
    # `spec_rounds` drafting rounds of width spec_k.
    spec_k: int = 0
    drafted: int = 0
    accepted: int = 0
    spec_rounds: int = 0
    # prefix-cache accounting (all zero when prefix_cache is False):
    # admission looked up `cache_lookup_blocks` full prompt blocks in the
    # pool's content index, mapped `cache_hit_blocks` of them by
    # reference (skipping `cache_hit_tokens` prompt tokens of prefill),
    # copy-on-wrote `cache_cow_blocks` final blocks of fully-cached
    # prompts, and the pool evicted `cache_evictions` idle cached blocks
    # under pressure. `preemptions` counts pool-pressure victim requeues.
    prefix_cache: bool = False
    cache_lookup_blocks: int = 0
    cache_hit_blocks: int = 0
    cache_hit_tokens: int = 0
    cache_cow_blocks: int = 0
    cache_evictions: int = 0
    preemptions: int = 0
    # SLO accounting: queue_times[i] is request i's admission wait
    # (serve() start -> scheduler admission; re-admission after a
    # preemption overwrites it), finish_times[i] its completion time
    # relative to serve() start. `stopped_early` counts requests a
    # device stop criterion (eos / stop sequence) finished before
    # max_tokens.
    queue_times: list[float] = dataclasses.field(default_factory=list)
    finish_times: list[float] = dataclasses.field(default_factory=list)
    stopped_early: int = 0

    @property
    def accept_rate(self) -> float:
        """Fraction of proposed draft tokens the full model kept."""
        return self.accepted / self.drafted if self.drafted else 0.0

    @property
    def cache_hit_rate(self) -> float:
        """Fraction of looked-up full prompt blocks served by reference."""
        return (self.cache_hit_blocks / self.cache_lookup_blocks
                if self.cache_lookup_blocks else 0.0)

    @property
    def cache_hit_token_rate(self) -> float:
        """Fraction of all prompt tokens whose prefill was skipped."""
        total = sum(self.prompt_lens)
        return self.cache_hit_tokens / total if total else 0.0

    @property
    def cache_blocks_saved(self) -> int:
        """Physical blocks admission did not allocate thanks to sharing
        (hit blocks mapped by reference; COW sources still cost a private
        copy, so they don't count)."""
        return self.cache_hit_blocks - self.cache_cow_blocks

    @property
    def total_tokens(self) -> int:
        return int(sum(o.size for o in self.outputs))

    @property
    def tokens_per_second(self) -> float:
        return self.total_tokens / max(self.seconds, 1e-9)

    # per-request latency aggregates (seconds). ttft[i] is measured from
    # serve() start to request i's first sampled token; tpot[i] is the
    # mean inter-token time over its remaining outputs (0.0 for
    # single-token requests).
    @property
    def ttft_p50(self) -> float:
        return _percentile(self.ttft, 50)

    @property
    def ttft_p95(self) -> float:
        return _percentile(self.ttft, 95)

    @property
    def tpot_p50(self) -> float:
        return _percentile([t for t in self.tpot if t > 0], 50)

    @property
    def tpot_p95(self) -> float:
        return _percentile([t for t in self.tpot if t > 0], 95)

    @property
    def queue_p50(self) -> float:
        return _percentile(self.queue_times, 50)

    @property
    def queue_p95(self) -> float:
        return _percentile(self.queue_times, 95)

    def goodput(self, deadline_s: float) -> float:
        """Tokens per second counting ONLY requests that finished within
        `deadline_s` of serve() start — the SLO-aware throughput number
        (a request that blows its deadline contributes nothing, however
        many tokens it produced)."""
        good = sum(self.outputs[i].size for i, f in enumerate(
            self.finish_times) if f <= deadline_s)
        return good / max(self.seconds, 1e-9)

    def slo_attainment(self, ttft_s: float, tpot_s: float) -> float:
        """Fraction of requests meeting BOTH a TTFT and a per-output-
        token latency target."""
        n = len(self.outputs)
        if not n:
            return 0.0
        ok = sum(1 for i in range(n)
                 if self.ttft[i] <= ttft_s and self.tpot[i] <= tpot_s)
        return ok / n


def _as_token_batch(requests):
    """Normalize requests: a (B, S) int32 array when rectangular, else a
    list of 1-D int32 prompts (the caller routes those through the
    continuous-batching scheduler)."""
    if isinstance(requests, (list, tuple)):
        if not requests:
            raise ValueError("empty request batch")
        rows = [np.asarray(r, np.int32) for r in requests]
        if any(r.ndim != 1 for r in rows):
            raise ValueError(
                f"each request must be a 1-D token sequence, got shapes "
                f"{[r.shape for r in rows]}")
        if any(r.size == 0 for r in rows):
            raise ValueError("empty prompt in request batch")
        if len({r.size for r in rows}) != 1:
            return rows
        requests = np.stack(rows)
    toks = jnp.asarray(requests, jnp.int32)
    if toks.ndim != 2:
        raise ValueError(f"requests must be (batch, seq), got {toks.shape}")
    return toks


def _pow2_bucket(n: int) -> int:
    """Smallest power of two >= n."""
    return 1 << max(n - 1, 0).bit_length()


def _step_counters(buf, ctx_col: int, block_size: int,
                   table_width: int) -> dict:
    """The `repro.serve.step` span's counters, from the step's packed
    buffer (ctx_lens at column `ctx_col`, q_lens after it): positions
    the step computes, KV blocks the attention kernel streams, and the
    block slots its chunks cover, the last two per layer and KV head
    (under speculation, those of the verifying pass)."""
    ctx, q = buf[:, ctx_col], buf[:, ctx_col + 1]
    nb = kvblocks.valid_block_counts(ctx, q, block_size, table_width)
    return {"valid_tokens": int(q.sum()),
            "kv_blocks_valid": int(nb.sum()),
            "kv_grid_blocks": pa.grid_blocks(
                nb, pa.chunk_blocks(block_size, table_width))}


def _tree_nbytes(tree) -> int:
    """Bytes a pytree's arrays actually occupy (packed nibble arrays
    report their true halved size) — the single definition of measured
    weight residency."""
    return sum(int(getattr(l, "nbytes", 0))
               for l in jax.tree_util.tree_leaves(tree))


def _generate_pick(logits, temperature, top_k, top_p, seed, counter):
    """Sampled next tokens for the rectangular generate() path: (B, 1)
    int32 from (B, ..., V) last-position logits. Scalar sampling
    controls are broadcast per row; keys are counter-based —
    fold_in(fold_in(PRNGKey(seed), row), counter) with row == batch
    index == the rid serve() would assign the same prompts — so the
    rectangular and continuous-batching paths sample identical tokens
    under a shared seed (counter is a traced scalar: one trace serves
    every step)."""
    from repro.runtime import sampling as smp

    last = logits[:, -1]
    b = last.shape[0]
    bcast = lambda x, dt: jnp.full((b,), x, dt)    # noqa: E731
    keys = smp.row_keys(bcast(seed, jnp.int32),
                        jnp.arange(b, dtype=jnp.int32),
                        bcast(counter, jnp.int32))
    return smp.sample_tokens(last, bcast(temperature, jnp.float32),
                             bcast(top_k, jnp.int32),
                             bcast(top_p, jnp.float32), keys)[:, None]


class InferenceEngine:
    """Compiled compress→shard→serve pipeline for one model + plan."""

    def __init__(self, cfg: ModelConfig, params, *, plan=None, report=None,
                 mesh=None, max_batch: int = 8, block_size: int = 16,
                 chunk_tokens: int = 256, bucket_prompts: bool = True,
                 speculate: DraftSpec | None = None,
                 prefix_cache: bool = True):
        self.cfg = cfg
        self.params = params
        self.plan = plan
        self.report = report
        self.mesh = mesh
        # prefix caching (serve): share full KV blocks between requests
        # with equal position-aligned prompt prefixes. The content-hash
        # chain is seeded with a model+plan fingerprint so blocks can
        # never be shared across engines whose K/V for the same tokens
        # would differ (different weights, dtype, or KV residency).
        self.prefix_cache = prefix_cache
        try:
            plan_id = plan.dumps() if plan is not None else "dense"
        except TypeError:           # unserializable plan metadata
            plan_id = repr(plan)
        self._cache_fingerprint = hashlib.sha256(
            (f"{getattr(cfg, 'name', 'model')}:{cfg.dtype}:"
             f"{getattr(cfg, 'kv_cache_bits', 16)}:{plan_id}")
            .encode()).digest()
        # tensor-parallel serving: a mesh with a "model" axis shard-maps
        # the unified step — params column/row-sliced, the KV pool
        # head-sliced, one psum per attention/MLP boundary. The mesh
        # model-axis size IS the TP degree (1 runs the same path).
        self._tp = (int(mesh.shape["model"])
                    if mesh is not None and "model" in mesh.axis_names
                    else 0)
        # self-speculative decoding: derive the truncated-cascade draft
        # tree once at engine construction (it shares every dense array
        # with `params` by reference — no second checkpoint in HBM)
        self.speculation = (SpeculationController(speculate, cfg, params,
                                                  mesh=mesh)
                            if speculate is not None else None)
        self.max_batch = max_batch      # serve(): batch-row capacity
        self.block_size = block_size    # serve(): KV block size (tokens)
        self.chunk_tokens = chunk_tokens  # serve(): per-step token budget
        # generate(): right-pad prompts to power-of-two length buckets so
        # N distinct lengths cost O(log N) prefill compilations. Only
        # sound where right-padding is inert: dense global causal
        # attention (padding K/V slots are overwritten before any decode
        # query can see them). Rolling/windowed caches and SSM state
        # fold padding into what decode reads, and MoE expert routing is
        # capacity-bounded per batch — pad tokens compete for expert
        # slots and can displace real tokens — so those archs prefill at
        # exact length.
        self.bucket_prompts = bucket_prompts and self._can_bucket(cfg)
        # jit once; XLA re-specializes per (batch, seq, max_len) shape.
        self._prefill = jax.jit(
            lambda p, toks, max_len, last: tfm.prefill(p, toks, cfg,
                                                       max_len=max_len,
                                                       last_pos=last),
            static_argnums=2)
        self._decode = jax.jit(
            lambda p, cache, tok, pos: tfm.decode_step(p, cache, tok, pos,
                                                       cfg))
        # the unified serving step (models.transformer.serve_step):
        # static in (capacity, span width, max blocks/seq); the span
        # width is power-of-two bucketed, so one jitted function in
        # O(log chunk_tokens) shapes serves the whole
        # admit/chunk/decode/evict loop. Everything per-step is fused
        # into this single dispatch — splicing the previous step's
        # device-resident sampled tokens into decode rows, the forward
        # pass, per-row temperature/top-k/top-p sampling, and the
        # eos/stop/max-tokens finished mask — because serving
        # throughput on small steps is bounded by host dispatch
        # overhead, not FLOPs. One variant traces per static
        # (any-row-samples, any-stop-criteria) pair; the (False, False)
        # variant is the bare greedy step (no sort, no PRNG, no ring).
        self._unified_cache: dict[tuple, object] = {}
        if self._tp:
            from repro.launch import sharding as shd

            shd.check_tp_geometry(cfg, self._tp)
        # greedy sampling is the rectangular-generate hot path: one fused
        # jitted argmax instead of a chain of eager ops per step; the
        # sampled path is the SAME fused sampler the serve step uses,
        # keyed by (seed, row, emission counter) so generate() and
        # serve() agree token-for-token under a shared seed.
        self._argmax = jax.jit(
            lambda lg: jnp.argmax(lg[:, -1], axis=-1)[:, None]
            .astype(jnp.int32))
        self._sample = jax.jit(_generate_pick)
        # copy-on-write block duplication for fully-cached prompts; block
        # indices are traced scalars so one trace covers every copy, and
        # the op moves along the (unsharded) block axis so it is TP-inert.
        self._cow_copy = jax.jit(kvblocks.copy_block)
        tracing.trace_collections()

    def _unified_fn(self, sample: bool, stop: bool,
                    budget: int | None = None):
        """The jitted fused serving step for one static (sample, stop)
        pair and token budget, traced on first use and cached for the
        engine's lifetime. The budget sizes a MoE step's routing slots
        (`moe.moe_routed`); a dense step does not read it, so one
        program serves every budget there (`transformer.step_budget`)."""
        budget = tfm.step_budget(self.cfg, budget)
        fn = self._unified_cache.get((sample, stop, budget))
        if fn is not None:
            return fn
        cfg = self.cfg
        # the step is a named function: a profile names each compiled
        # program after the function jitted (`jit_serving_step`)
        if self._tp:
            # shard_map the SAME fused step: each shard runs it with the
            # per-shard config (its slice of heads / hidden columns) over
            # its head-slice of the pool; tokens / tables / buffers are
            # replicated. tp_axis binds at trace time, so the boundary
            # psums in transformer.unified_step land in this jaxpr only.
            # Sampling runs identically on every shard: the residual —
            # hence the logits and the per-row keys — is replicated
            # after the boundary psums, so toks/finished come out
            # replicated too (out_specs P()), exactly like the greedy
            # argmax before.
            from jax.sharding import PartitionSpec as P

            from repro.launch import sharding as shd
            from repro.runtime import shardctx

            lcfg = shd.tp_local_config(cfg, self._tp)
            pspecs = shd.tp_param_specs(self.params, self._tp)
            pool_specs = kvblocks.pool_pspecs(cfg)

            def serving_step(p, pool, bt, buf, prev, recent, stops):
                with shardctx.tp_axis("model"):
                    return tfm.serve_step(p, pool, bt, buf, prev, recent,
                                          stops, lcfg, sample=sample,
                                          stop=stop, token_budget=budget)

            fn = jax.jit(shardctx.tp_shard_map(
                serving_step, self.mesh,
                in_specs=(pspecs, pool_specs, P(), P(), P(), P(), P()),
                out_specs=(P(), P(), P(), pool_specs)))
        else:
            def serving_step(p, pool, bt, buf, prev, recent, stops):
                return tfm.serve_step(p, pool, bt, buf, prev, recent, stops,
                                      cfg, sample=sample, stop=stop,
                                      token_budget=budget)

            fn = jax.jit(serving_step)
        self._unified_cache[(sample, stop, budget)] = fn
        return fn

    @staticmethod
    def _can_bucket(cfg) -> bool:
        return (cfg.layout == "dense"
                and not cfg.attn_window and not cfg.local_global_period)

    def weight_hbm_bytes(self) -> int:
        """Bytes the parameter arrays actually occupy in device memory —
        the number the packed-W4 residency work shrinks. Measured
        residency (`.nbytes` per leaf), not an accounting claim."""
        return _tree_nbytes(self.params)

    # ------------------------------------------------------------- build --
    @classmethod
    def build(cls, arch, plan=None, *, mesh=None, params=None,
              smoke: bool = False, seed: int = 0, verbose: bool = False,
              max_batch: int = 8, block_size: int = 16,
              chunk_tokens: int = 256,
              paged_attn: str | None = None,
              speculate=None, prefix_cache: bool = True
              ) -> "InferenceEngine":
        """arch: config name (see repro.configs) or a ModelConfig.
        plan: CompressionPlan | legacy CompressionConfig | None (dense).
        params: pre-trained weights; freshly initialized when omitted.
        mesh: optional jax Mesh — weights are placed per launch.sharding.
        max_batch / block_size / chunk_tokens: serving defaults for
        serve() — batch rows, KV block size, per-step token budget.
        paged_attn: override cfg.paged_attn_impl for the serving
        attention backend — "auto" (Pallas kernel on TPU, jnp gather
        oracle on CPU), "kernel", or "ref".
        speculate: self-speculative decoding config. None defers to
        `plan.draft`; a `DraftSpec` (or int draft depth k, or True for
        the defaults) turns it on regardless of the plan; False/0 forces
        it off even when the plan carries a draft spec.
        prefix_cache: serve() default for KV prefix sharing (overridable
        per serve call)."""
        cfg = get_config(arch, smoke=smoke) if isinstance(arch, str) else arch
        if paged_attn is not None:
            cfg = dataclasses.replace(cfg, paged_attn_impl=paged_attn)
        if params is None:
            params = tfm.init_params(jax.random.PRNGKey(seed), cfg)

        report = None
        if isinstance(plan, CompressionConfig):
            plan = (None if plan.method == "none"
                    else CompressionPlan.from_config(params, plan))
        if plan is not None:
            t0 = time.time()
            params, report = compress_params(params, plan)
            plan = report.plan
            if verbose:
                print(f"[engine] compressed in {time.time()-t0:.1f}s: "
                      f"{report.summary()} "
                      f"resident={_tree_nbytes(params)/2**20:.1f}MiB")

        if mesh is not None:
            from repro.launch import sharding as shd

            if "model" in mesh.axis_names:
                # tensor-parallel serving placement: literal shard_map
                # slices (launch.sharding._TP_RULES), so every leaf is
                # already where its shard needs it and no per-dispatch
                # resharding happens. Geometry must divide exactly.
                shd.check_tp_geometry(cfg, int(mesh.shape["model"]))
                params = jax.device_put(params,
                                        shd.tp_param_shardings(params, mesh))
            else:
                params = jax.device_put(
                    params, shd.param_shardings(params, mesh, cfg))
        if isinstance(speculate, DraftSpec):
            spec = speculate
        elif speculate is None:
            spec = plan.draft if plan is not None else None
        elif speculate is True:
            spec = (plan.draft if plan is not None and plan.draft is not None
                    else DraftSpec())
        elif not speculate:             # False / 0: explicit off
            spec = None
        else:
            spec = DraftSpec(k=int(speculate))
        return cls(cfg, params, plan=plan, report=report, mesh=mesh,
                   max_batch=max_batch, block_size=block_size,
                   chunk_tokens=chunk_tokens, speculate=spec,
                   prefix_cache=prefix_cache)

    # ---------------------------------------------------------- generate --
    def generate(self, requests, sampling: SamplingParams | None = None
                 ) -> GenerationResult:
        """Generate continuations for a batch of requests.

        requests: (B, S) int tokens — array or list of token lists. Equal
        lengths run the rectangular lockstep path; ragged lengths are
        served by the in-flight batching scheduler (`serve`) through the
        unified token-budget step. Either way the result is the generated
        continuation only, (B, max_tokens), in request order — greedy
        outputs are token-identical between the two paths and to running
        each prompt alone, seeded sampled outputs likewise (both paths
        share the fused sampler and counter-based keys,
        runtime/sampling.py). Stop criteria (sampling.eos_id / .stop)
        truncate inclusively; rows that stop early are zero-padded to
        max_tokens to keep the result rectangular.
        """
        sampling = sampling or SamplingParams()
        toks = _as_token_batch(requests)
        if isinstance(toks, list):          # ragged -> continuous batching
            res = self.serve(toks, sampling)
            out = np.zeros((len(res.outputs), sampling.max_tokens), np.int32)
            for i, o in enumerate(res.outputs):
                out[i, :o.size] = o         # stop-shortened rows: zero tail
            return GenerationResult(
                tokens=out,
                prompt_len=max(res.prompt_lens), seconds=res.seconds,
                prompt_lens=list(res.prompt_lens))
        s = toks.shape[1]
        padded = _pow2_bucket(s) if self.bucket_prompts else s
        if padded != s:
            toks = jnp.pad(toks, ((0, 0), (0, padded - s)))
        max_len = padded + sampling.max_tokens

        from repro.runtime import shardctx

        ctx = (shardctx.use_mesh(self.mesh) if self.mesh is not None
               else contextlib.nullcontext())
        t0 = time.time()
        with ctx:
            logits, cache = self._prefill(self.params, toks, max_len,
                                          jnp.asarray(s - 1))
            out = []
            tok = self._pick(logits, sampling, 0)
            for i in range(sampling.max_tokens):
                out.append(tok)
                if i + 1 == sampling.max_tokens:
                    break
                logits, cache = self._decode(self.params, cache, tok,
                                             jnp.asarray(s + i))
                tok = self._pick(logits, sampling, i + 1)
            gen = jax.block_until_ready(jnp.concatenate(out, axis=1))
        arr = np.asarray(gen)
        if sampling.eos_id is not None or sampling.stop:
            # lockstep decode runs every row to max_tokens; apply the
            # shared stop oracle post-hoc (inclusive match, zero tail)
            # so the rectangular and ragged paths return the same thing
            from repro.runtime import sampling as smp
            arr = arr.copy()
            for i in range(arr.shape[0]):
                keep = smp.match_stop_host(arr[i], sampling.eos_id,
                                           sampling.stop,
                                           sampling.max_tokens)
                if keep is not None:
                    arr[i, keep:] = 0
        return GenerationResult(tokens=arr, prompt_len=s,
                                seconds=time.time() - t0)

    # ------------------------------------------------------------- serve --
    def serve(self, requests, sampling: SamplingParams | None = None, *,
              max_batch: int | None = None, block_size: int | None = None,
              num_blocks: int | None = None,
              chunk_tokens: int | None = None,
              speculate: bool | None = None,
              prefix_cache: bool | None = None,
              on_token=None) -> ServeResult:
        """In-flight batching with chunked prefill: ragged prompts,
        per-request max_tokens, one jitted token-budget step.

        requests: list of token sequences or `runtime.scheduler.Request`s
        (the latter carry their own max_tokens; otherwise
        `sampling.max_tokens` applies). Requests are admitted FCFS into a
        fixed-capacity batch; each step the scheduler splits
        `chunk_tokens` of budget between one decode token for every
        in-flight row (decode always advances) and prompt chunks for
        newly admitted rows, and a single forward pass processes the
        whole mix. Finished rows free their blocks immediately and the
        next waiting request takes the slot mid-flight. Overflow (rows or
        blocks) queues — it never crashes the batch.

        The loop is software-pipelined two steps deep: scheduling depends
        only on token *counts* (per-request max_tokens, no early
        stopping), so later steps are dispatched — decode rows fed the
        previous step's sampled tokens device-to-device — before earlier
        steps' values are read back. The host consumes a step's tokens
        while the device runs the next two, which both hides the
        per-step sync and timestamps each token at true completion
        (TTFT/TPOT in the result).

        num_blocks defaults to enough for max_batch worst-case sequences,
        i.e. admission is then only row-limited. Pass a smaller pool to
        exercise block-limited admission.

        When the engine carries a draft model (`build(speculate=...)` or
        `plan.draft`), decode rows additionally propose up to `spec.k`
        draft tokens per step with the truncated cascade and the full
        model verifies the whole span in the same dispatch — greedy
        acceptance keeps the outputs token-identical to non-speculative
        serve (see runtime/speculation.py). `speculate=False` disables
        it for this call; `speculate=True` requires the engine to have a
        draft model. This path is synchronous (acceptance is
        value-dependent), trading the 2-deep pipeline for >1 token per
        dispatch. Rows decoding with temperature > 0 never draft —
        greedy acceptance verifies an argmax chain — but they sample in
        the same fused dispatch, so mixed greedy+sampled batches keep
        speculating on their greedy rows.

        Sampling is per request and fused into the dispatch: each
        request's temperature / top_k / top_p / seed (its Request
        fields, else `sampling`) travel as packed metadata columns in
        the one per-step buffer upload, and tokens are sampled on
        device with counter-based PRNG keys (runtime/sampling.py) — so
        seeded sampled runs replay token-identically across repeats,
        prefix-cache on/off, and TP mesh sizes; rows with temperature
        <= 0 stay bit-identical to greedy serve; and an all-greedy call
        still traces the bare argmax program (no sort, no PRNG).

        Stop criteria (eos_id / stop token sequences, per request or
        call-wide) are evaluated on device in the same dispatch; the
        per-row finished mask rides the already-pipelined readback, so
        the loop learns of a stop at most two steps late (those zombie
        steps' tokens are discarded), then frees the row's blocks.
        Matching is inclusive: the matched tokens stay in the (possibly
        shorter than max_tokens) output.

        on_token, if given, is called as `on_token(TokenEvent(...))`
        the moment the pipelined readback confirms each token — the
        async streaming front door (`launch.serve.serve_stream`)
        bridges it onto an event loop. Callbacks run between dispatches
        on the serve thread, so keep them cheap.

        prefix_cache (default: the engine's build-time setting) shares
        KV blocks between requests with equal full-block prompt
        prefixes: admission maps cached blocks by reference and prefill
        starts at the first uncached position. Greedy serve is
        token-identical with the cache on or off — K/V at position p
        depends only on tokens <= p, never on how prefill was chunked,
        so a cached block holds bit-for-bit what recomputation would
        write (int8 KV quantizes per (token, head), which block
        boundaries preserve). The cache lives for this serve call (the
        pool is per-call); hit/COW/eviction counts land in the result.
        """
        sampling = sampling or SamplingParams()
        with tracing.span("serve.setup"):
            ctl = self.speculation
            if speculate is False:
                ctl = None
            elif speculate is True and ctl is None:
                raise ValueError(
                    "speculate=True but the engine has no draft model — "
                    "build with speculate=DraftSpec(...) or a plan carrying "
                    ".draft")
            # resolve every per-request sampling/stop field against the
            # call-level SamplingParams BEFORE submission: the scheduler
            # and the packed-buffer build only ever see concrete values.
            reqs: list[Request] = []
            for i, r in enumerate(requests):
                if not isinstance(r, Request):
                    r = Request(tokens=r)
                repl: dict = {"rid": i}
                if r.max_tokens is None:
                    repl["max_tokens"] = sampling.max_tokens
                if r.temperature is None:
                    repl["temperature"] = sampling.temperature
                if r.top_k is None:
                    repl["top_k"] = sampling.top_k
                if r.top_p is None:
                    repl["top_p"] = sampling.top_p
                if r.seed is None:
                    repl["seed"] = sampling.seed
                if r.eos_id is None:
                    repl["eos_id"] = sampling.eos_id
                if not r.stop:
                    repl["stop"] = sampling.stop
                reqs.append(dataclasses.replace(r, **repl))
            if not reqs:
                raise ValueError("empty request batch")
            kvblocks.check_paged_support(self.cfg)
            # serve-call statics: which fused-step variant traces, and the
            # stop-buffer geometry (ring width S, stop slots NS)
            do_sample = any(r.temperature > 0.0 for r in reqs)
            do_stop = any(r.eos_id is not None or r.stop for r in reqs)
            n_stops = max([len(r.stop) for r in reqs] + [1])
            stop_len = max([len(s) for r in reqs for s in r.stop] + [1])

            bs = block_size or self.block_size
            cap = min(max_batch or self.max_batch, len(reqs))
            budget = chunk_tokens or self.chunk_tokens
            need = [kvblocks.blocks_needed(r.tokens.size, r.max_tokens, bs)
                    for r in reqs]
            mb = max(max(need), 1)              # block-table width (static)
            if num_blocks is None:
                num_blocks = cap * mb + 1       # +1: reserved trash block
            use_cache = (self.prefix_cache if prefix_cache is None
                         else prefix_cache)
            pool_alloc = kvblocks.BlockPool(num_blocks, bs)
            sched = Scheduler(pool_alloc, cap, prefix_cache=use_cache,
                              fingerprint=self._cache_fingerprint)
            for r in reqs:
                sched.submit(r)

            pool = kvblocks.init_paged_cache(self.cfg, num_blocks, bs)
            if self._tp:
                from jax.sharding import NamedSharding

                pool = jax.device_put(
                    pool, {k: NamedSharding(self.mesh, s) for k, s in
                           kvblocks.pool_pspecs(self.cfg).items()})
            tables = np.zeros((cap, mb), np.int32)
            out_vals: list[list[int]] = [[] for _ in reqs]
            first_tok_t = [None] * len(reqs)
            finish_t = [0.0] * len(reqs)
            queue_t = [0.0] * len(reqs)
            steps = prefill_chunks = prefill_tokens = mixed_steps = 0
            drafted = accepted = spec_rounds = 0
            spec_stopped = 0

            from repro.runtime import sampling as smp
            from repro.runtime import shardctx

            # TP serving must NOT install the GSPMD mesh: the step is a
            # shard_map program over manual axes, where maybe_shard's
            # with_sharding_constraint is meaningless (and errors).
            ctx = (shardctx.use_mesh(self.mesh)
                   if self.mesh is not None and not self._tp
                   else contextlib.nullcontext())
        t0 = time.time()
        # rids whose device stop criterion fired before max_tokens: the
        # pipeline learns (at most two steps late, at consume time) and
        # discards the zombie steps' tokens; that step frees the row.
        stopped: set[int] = set()

        def consume(emits, toks_dev, fin_dev):
            """Read back one step's sampled tokens + finished mask
            (blocks until the device finishes that step) and credit
            them to requests. Returns that step's `experts_hit` while a
            profiler session records and the tokens carry it in a second
            column (`transformer.step_token_cols`), else 0."""
            with tracing.span("serve.consume"):
                with tracing.span("serve.readback"):
                    vals = np.asarray(toks_dev)
                    fins = None if fin_dev is None else np.asarray(fin_dev)
                hits = (int(vals[0, 1]) if vals.shape[1] > 1
                        and tracing.active() else 0)
                now = time.time()
                for rid, r in emits:
                    if rid in stopped:
                        continue    # zombie tokens dispatched past the stop
                    out_vals[rid].append(int(vals[r, 0]))
                    if first_tok_t[rid] is None:
                        first_tok_t[rid] = now
                    done = len(out_vals[rid]) >= reqs[rid].max_tokens
                    if fins is not None and fins[r]:
                        if not done:            # eos / stop sequence fired
                            stopped.add(rid)    # before the token budget ran
                        done = True
                    if done:
                        finish_t[rid] = now
                    if on_token is not None:
                        on_token(TokenEvent(rid=rid,
                                            token=out_vals[rid][-1],
                                            index=len(out_vals[rid]) - 1,
                                            time=now, final=done))
            return hits

        with ctx:
            if ctl is not None:
                (steps, prefill_chunks, prefill_tokens, mixed_steps,
                 drafted, accepted, spec_rounds, spec_stopped) = \
                    self._spec_loop(
                        reqs, sched, pool, tables, cap, budget, ctl,
                        out_vals, first_tok_t, finish_t, queue_t, t0,
                        do_sample, on_token)
                sched_done = True
            else:
                sched_done = False
            step_fn = self._unified_fn(do_sample, do_stop, budget)
            tables_dev = None       # device-safe copy, refreshed on change
            stops_dev = None        # ditto, for the stop-sequence buffer
            stop_buf = np.full((cap, n_stops, stop_len), -1, np.int32)
            no_stops = jnp.zeros((cap, 1, 1), jnp.int32)  # stop=False dummy
            inflight = collections.deque()  # (emits, toks, fin), oldest
            prev_toks = jnp.zeros((cap, tfm.step_token_cols(self.cfg)),
                                  jnp.int32)
            recent = jnp.zeros((cap, stop_len), jnp.int32)
            while not sched_done and sched.has_work():
                with tracing.span("serve.step") as step_span:
                    with tracing.span("serve.schedule"):
                        plan = sched.schedule(budget)
                        for r in plan.preempted:  # victim rows: table to
                            tables[r] = 0         # trash (before any
                            tables_dev = None     # admission reusing it)
                        for seq in plan.admitted:
                            tables[seq.row] = 0
                            tables[seq.row, :len(seq.block_ids)] = \
                                seq.block_ids
                            tables_dev = None
                            queue_t[seq.req.rid] = time.time() - t0
                            if do_stop:
                                stop_buf[seq.row] = smp.pack_stop_seqs(
                                    seq.req.stop, n_stops, stop_len)
                                stops_dev = None
                            if seq.cow_dst is not None:
                                # fully-cached prompt: materialize a
                                # private copy of the last matched block
                                # before this step's span write
                                # recomputes its final position
                                pool = self._cow_copy(
                                    pool, jnp.int32(seq.cow_src),
                                    jnp.int32(seq.cow_dst))
                                sched.release_cow(seq)
                        if not plan.prefill and not plan.decode:
                            raise RuntimeError(
                                "scheduler returned an empty step with "
                                "work pending — admission deadlock")
                    # ---- build the (cap, W + meta) span batch ------------
                    # one fresh packed buffer per step: span tokens, the
                    # (ctx, q_len, use_prev) scheduling columns, then the
                    # packed per-row sampling/stop metadata — still ONE
                    # upload. Handed to the jitted step as numpy — never
                    # mutated after dispatch, so jax's zero-copy aliasing
                    # of host buffers is safe here.
                    with tracing.span("serve.build"):
                        w = _pow2_bucket(plan.max_span)
                        m = smp.SAMP_COLS
                        buf = np.zeros((cap, w + 3 + m), np.int32)
                        for r, width in plan.prefill.items():
                            seq = sched.rows[r]
                            lo = seq.prefilled
                            buf[r, :width] = seq.req.tokens[lo:lo + width]
                            buf[r, -(m + 3)] = lo
                            buf[r, -(m + 2)] = width
                        for r in plan.decode:
                            seq = sched.rows[r]
                            # the input token is the one sampled last
                            # step; it is still on device (prev_toks),
                            # spliced in by the step. pool holds prompt +
                            # all but that newest token.
                            buf[r, -(m + 3)] = (seq.prompt_len
                                                + seq.n_emitted - 1)
                            buf[r, -(m + 2)] = 1
                            buf[r, -(m + 1)] = 1
                        for r in list(plan.prefill) + plan.decode:
                            seq = sched.rows[r]
                            smp.write_row_meta(buf, r, seq.req,
                                               seq.n_emitted)
                    if tracing.active():
                        step_span.set_metadata(**_step_counters(
                            buf, -(m + 3), bs, mb))
                    # ---- ONE fused dispatch for the prefill/decode mix ---
                    with tracing.span("serve.dispatch"):
                        if tables_dev is None:
                            # a private copy: `tables` is mutated by later
                            # admissions/evictions while earlier
                            # dispatched steps may still be reading the
                            # (possibly aliased) upload
                            tables_dev = tables.copy()
                        if do_stop and stops_dev is None:
                            stops_dev = stop_buf.copy()
                        toks_dev, fin_dev, recent, pool = step_fn(
                            self.params, pool, tables_dev, buf, prev_toks,
                            recent, stops_dev if do_stop else no_stops)
                    steps += 1
                    prefill_chunks += len(plan.prefill)
                    prefill_tokens += sum(plan.prefill.values())
                    mixed_steps += plan.is_mixed
                    prev_toks = toks_dev
                    # ---- count-based bookkeeping at dispatch time --------
                    # (scheduling never waits on token values — eviction
                    # and admission run ahead of the device;
                    # value-dependent stops arrive via the pipelined
                    # finished mask above)
                    emits = []
                    for r, width in plan.prefill.items():
                        # advance + register newly completed full prompt
                        # blocks into the content index (dispatch order =
                        # device order, so later readers see the writes)
                        sched.advance_prefill(sched.rows[r], width)
                    for r in list(plan.prefill) + plan.decode:
                        seq = sched.rows[r]
                        if not seq.prefill_done:
                            continue            # mid-prompt: logits unused
                        seq.n_emitted += 1
                        emits.append((seq.req.rid, r))
                        if seq.done:
                            sched.finish(seq)
                            tables[r] = 0
                            tables_dev = None
                    # ---- consume an older step while this one runs -------
                    # (two steps of lookahead keep the device queue busy
                    # through the host's scheduling + readback work)
                    inflight.append((emits, toks_dev,
                                     fin_dev if do_stop else None))
                    hits = 0
                    if len(inflight) > 2:
                        hits += consume(*inflight.popleft())
                    if stopped:
                        # rows whose stop fired (discovered at consume):
                        # retire them before the next schedule so the
                        # row + blocks free now
                        with tracing.span("serve.schedule"):
                            for seq in list(sched.rows):
                                if seq is not None and seq.req.rid in stopped:
                                    sched.finish(seq)
                                    tables[seq.row] = 0
                                    tables_dev = None
                    if not sched.has_work():
                        # the last dispatch: read back what is in flight
                        with tracing.span("serve.drain"):
                            while inflight:
                                hits += consume(*inflight.popleft())
                    if tracing.active() and toks_dev.shape[1] > 1:
                        # experts hit by the steps read back in this one
                        step_span.set_metadata(experts_hit=hits)
            # stops discovered in the final drain: the rows already
            # finished by count, but late-stopped outputs stay truncated
            if not sched_done:
                for seq in list(sched.rows):
                    if seq is not None and seq.req.rid in stopped:
                        sched.finish(seq)
                        tables[seq.row] = 0
        if pool_alloc.available != pool_alloc.capacity:
            raise RuntimeError(
                f"leaked KV blocks: {pool_alloc.capacity - pool_alloc.available}"
                f" of {pool_alloc.capacity} still allocated after drain")
        outputs = [np.asarray(v, np.int32) for v in out_vals]
        ttft = [first_tok_t[i] - t0 for i in range(len(reqs))]
        tpot = [(finish_t[i] - first_tok_t[i]) / (len(out_vals[i]) - 1)
                if len(out_vals[i]) > 1 else 0.0
                for i in range(len(reqs))]
        return ServeResult(
            outputs=outputs, prompt_lens=[r.tokens.size for r in reqs],
            seconds=time.time() - t0, steps=steps,
            prefill_chunks=prefill_chunks, prefill_tokens=prefill_tokens,
            mixed_steps=mixed_steps, chunk_tokens=budget,
            max_queue_depth=sched.max_queue_depth, max_batch=cap,
            block_size=bs, num_blocks=num_blocks, ttft=ttft, tpot=tpot,
            spec_k=(ctl.spec.k if ctl is not None else 0),
            drafted=drafted, accepted=accepted, spec_rounds=spec_rounds,
            prefix_cache=use_cache,
            cache_lookup_blocks=sched.cache_lookup_blocks,
            cache_hit_blocks=sched.cache_hit_blocks,
            cache_hit_tokens=sched.cache_hit_tokens,
            cache_cow_blocks=sched.cache_cow_blocks,
            cache_evictions=pool_alloc.evictions,
            preemptions=sched.preemptions,
            queue_times=queue_t,
            finish_times=[finish_t[i] - t0 for i in range(len(reqs))],
            stopped_early=len(stopped) + spec_stopped)

    def _spec_loop(self, reqs, sched, pool, tables, cap, budget, ctl,
                   out_vals, first_tok_t, finish_t, queue_t, t0,
                   do_sample, on_token):
        """The speculative serve loop: one fused draft->verify->accept
        dispatch per step (runtime.speculation.speculative_step).

        Synchronous by design — how many tokens a row advanced is
        value-dependent (the accept count), so the next step's schedule
        must wait for this step's readback. The throughput win comes
        from E[accepted + 1] tokens per dispatch, not from pipelining;
        in the dispatch-bound small-step regime that IS the serving
        bottleneck. Only two step variants ever trace per sampling
        mode: draft width spec.k (any drafting row this step) and 0
        (none — e.g. a prefill-only step), mirroring the
        non-speculative path's power-of-two span bucketing.

        Rows with temperature > 0 never draft (the scheduler skips them
        in the spec offer) but sample their one token inside the same
        fused dispatch. Stop criteria are evaluated host-side with the
        shared oracle (`sampling.match_stop_host`) — this loop reads
        every token back synchronously anyway, so the device mask would
        buy nothing.

        Mutates out_vals / first_tok_t / finish_t / queue_t in place
        (same contract as serve's consume()); returns the step
        counters."""
        from repro.runtime import sampling as smp

        steps = prefill_chunks = prefill_tokens = mixed_steps = 0
        drafted = accepted = spec_rounds = 0
        stopped_early = 0
        m = smp.SAMP_COLS
        tables_dev = None
        prev_toks = jnp.zeros((cap, 1), jnp.int32)
        while sched.has_work():
            with tracing.span("serve.step") as step_span:
                with tracing.span("serve.schedule"):
                    plan = sched.schedule(budget, spec_k=ctl.spec.k)
                    for r in plan.preempted:
                        tables[r] = 0
                        tables_dev = None
                    for seq in plan.admitted:
                        tables[seq.row] = 0
                        tables[seq.row, :len(seq.block_ids)] = seq.block_ids
                        tables_dev = None
                        queue_t[seq.req.rid] = time.time() - t0
                        if seq.cow_dst is not None:
                            pool = self._cow_copy(pool,
                                                  jnp.int32(seq.cow_src),
                                                  jnp.int32(seq.cow_dst))
                            sched.release_cow(seq)
                    # draft-block reservations can grow a row's table
                    # mid-flight (only when admission could not
                    # pre-reserve the worst case)
                    for r in plan.spec:
                        seq = sched.rows[r]
                        if seq.draft_blocks:
                            tables[r, :len(seq.block_ids)] = seq.block_ids
                            tables_dev = None
                    if not plan.prefill and not plan.decode:
                        raise RuntimeError(
                            "scheduler returned an empty step with work "
                            "pending — admission deadlock")
                # ---- (cap, W + meta) span batch; meta gains spec_lens ---
                with tracing.span("serve.build"):
                    k_step = ctl.spec.k if plan.spec else 0
                    w = _pow2_bucket(max(plan.max_span, k_step + 1))
                    buf = np.zeros((cap, w + 4 + m), np.int32)
                    for r, width in plan.prefill.items():
                        seq = sched.rows[r]
                        lo = seq.prefilled
                        buf[r, :width] = seq.req.tokens[lo:lo + width]
                        buf[r, -(m + 4)] = lo
                        buf[r, -(m + 3)] = width
                    for r in plan.decode:
                        seq = sched.rows[r]
                        kr = plan.spec.get(r, 0)
                        # span: [prev (device-spliced), kr draft slots]
                        buf[r, -(m + 4)] = seq.prompt_len + seq.n_emitted - 1
                        buf[r, -(m + 3)] = 1 + kr
                        buf[r, -(m + 2)] = 1
                        buf[r, -(m + 1)] = kr
                    for r in list(plan.prefill) + plan.decode:
                        seq = sched.rows[r]
                        smp.write_row_meta(buf, r, seq.req, seq.n_emitted)
                if tracing.active():
                    step_span.set_metadata(**_step_counters(
                        buf, -(m + 4), sched.pool.block_size,
                        tables.shape[1]))
                with tracing.span("serve.dispatch"):
                    if tables_dev is None:
                        tables_dev = tables.copy()
                    full_toks, n_acc, prev_toks, pool = ctl.step_fn(
                        k_step, do_sample)(
                        self.params, ctl.draft_params, pool, tables_dev,
                        buf, prev_toks)
                steps += 1
                spec_rounds += bool(plan.spec)
                prefill_chunks += len(plan.prefill)
                prefill_tokens += sum(plan.prefill.values())
                mixed_steps += plan.is_mixed
                # acceptance decides how far each row advanced: read back
                # now
                with tracing.span("serve.readback"):
                    fv = np.asarray(full_toks)
                    na = np.asarray(n_acc)
                now = time.time()
                for r, width in plan.prefill.items():
                    sched.advance_prefill(sched.rows[r], width)
                for r in list(plan.prefill) + plan.decode:
                    seq = sched.rows[r]
                    if not seq.prefill_done:
                        continue        # mid-prompt: logits unused
                    if r in plan.prefill:
                        # prompt finished this step: emit the last-valid-
                        # position token (appended verify column
                        # k_step + 1)
                        toks = fv[r, k_step + 1:k_step + 2]
                    else:
                        # decode: accepted draft prefix + the full model's
                        # own token at the first divergence (or the bonus)
                        toks = fv[r, :int(na[r]) + 1]
                    rid = seq.req.rid
                    prev_len = len(out_vals[rid])
                    out_vals[rid].extend(int(t) for t in toks)
                    if first_tok_t[rid] is None:
                        first_tok_t[rid] = now
                    seq.n_emitted += len(toks)
                    kr = plan.spec.get(r, 0)
                    if kr:
                        drafted += kr
                        accepted += len(toks) - 1
                        if sched.commit_speculation(seq):
                            # rollback released tail blocks: rewind the
                            # table
                            tables[r] = 0
                            tables[r, :len(seq.block_ids)] = seq.block_ids
                            tables_dev = None
                    # host-side stop check (shared oracle; tokens already
                    # read back). Inclusive semantics: keep through the
                    # matching token, drop anything verified past it.
                    keep = smp.match_stop_host(out_vals[rid],
                                               seq.req.eos_id,
                                               seq.req.stop, seq.max_tokens)
                    if keep is not None:
                        del out_vals[rid][keep:]
                    if on_token is not None:
                        for j in range(prev_len, len(out_vals[rid])):
                            on_token(TokenEvent(
                                rid=rid, token=out_vals[rid][j], index=j,
                                time=now,
                                final=(keep is not None
                                       and j == len(out_vals[rid]) - 1)))
                    if keep is not None:
                        stopped_early += len(out_vals[rid]) < seq.max_tokens
                        finish_t[rid] = now
                        sched.finish(seq)
                        tables[r] = 0
                        tables_dev = None
        return (steps, prefill_chunks, prefill_tokens, mixed_steps,
                drafted, accepted, spec_rounds, stopped_early)

    def _pick(self, logits, sampling: SamplingParams,
              counter: int) -> jnp.ndarray:
        """(B, 1) next tokens from (B, ..., V) last-position logits —
        greedy argmax, or the shared counter-keyed sampler (see
        runtime/sampling.py; `counter` is the output-token index)."""
        if sampling.temperature <= 0.0:
            return self._argmax(logits)
        return self._sample(logits, np.float32(sampling.temperature),
                            np.int32(sampling.top_k),
                            np.float32(sampling.top_p),
                            np.int32(sampling.seed), np.int32(counter))

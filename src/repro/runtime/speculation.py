"""Self-speculative decoding: the low-rank cascade as a free draft model.

ITERA-LLM's iterative decomposition (core/itera.py, paper §III) has a
property no post-hoc quantization stack has: a rank-r cascade's first
r' < r components ARE the rank-r' ITERA model (greedy prefix
consistency — `itera.truncate`). Every compressed layer therefore
already contains a cheaper approximation of itself, which is exactly a
draft model for speculative decoding — same resident weights, no second
checkpoint, no extra HBM:

  1. **draft** — for each in-flight decode row, run k single-token steps
     with the TRUNCATED cascade (and/or a lower activation word length),
     chaining greedy argmax tokens. Draft K/V lands in the same blocked
     pool at the positions the tokens would occupy.
  2. **verify** — ONE full-model `unified_step` over the (k+1)-wide span
     [last committed token, d_1 .. d_k]. The span scatter overwrites
     every draft-written K/V slot with full-model values
     (write-then-attend), so the pool never retains draft numerics.
  3. **accept/reject** — greedy acceptance: the longest prefix of drafts
     matching the full model's argmax chain is kept, plus the full
     model's own token at the first mismatch (or the bonus token after a
     full accept). Emitted tokens are always the FULL model's argmax, so
     speculative serve is token-identical to non-speculative serve; a
     rejected draft costs nothing but the wasted draft compute —
     rejected positions are masked out of every later read and
     overwritten by the next span.

The whole round — k draft passes + the verify pass + acceptance — is a
single jitted dispatch (`speculative_step`); only (tokens, n_accept) is
read back per step. Scheduling (per-row clamping, provisional KV-block
reserve/rollback) lives in `runtime.scheduler`; the serve-loop driver in
`api.engine`. `hw/tpu_model.speculation_point` prices the trade for the
DSE; docs/serving.md walks the whole round.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.itera import LowRankQ, truncate
from repro.core.quant import QuantizedTensor, pack_weights, unpack_weights


@dataclasses.dataclass(frozen=True)
class DraftSpec:
    """How to derive the draft model from the served weights.

    k             : draft tokens proposed per decode row per round.
    rank_fraction : the draft keeps round(rank_fraction * r) components
                    of every rank-r cascade node (prefix consistency
                    makes this the lower-rank ITERA model, not an ad-hoc
                    approximation). 1.0 keeps the full cascade.
    act_wl        : optional activation word length override for the
                    draft pass (e.g. A8 serve, A6 draft); None inherits
                    the plan's act_wl.

    Carried on `CompressionPlan.draft` (serialized with the plan) or
    passed to `InferenceEngine.build(speculate=...)`.
    """

    k: int = 4
    rank_fraction: float = 0.5
    act_wl: int | None = None

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"draft k must be >= 1, got {self.k}")
        if not 0.0 < self.rank_fraction <= 1.0:
            raise ValueError(f"rank_fraction must be in (0, 1], got "
                             f"{self.rank_fraction}")
        if self.act_wl is not None and not 2 <= self.act_wl <= 8:
            raise ValueError(f"draft act_wl={self.act_wl} outside [2, 8]")

    def to_dict(self) -> dict:
        d = {"k": self.k, "rank_fraction": self.rank_fraction}
        if self.act_wl is not None:
            d["act_wl"] = int(self.act_wl)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "DraftSpec":
        return cls(k=int(d.get("k", 4)),
                   rank_fraction=float(d.get("rank_fraction", 0.5)),
                   act_wl=None if d.get("act_wl") is None
                   else int(d["act_wl"]))


def draft_rank(rank: int, fraction: float) -> int:
    """Draft rank for a full cascade rank: round(fraction * rank),
    floored to the kernels' 64-lane rank granularity when the full rank
    is large enough to care (mirrors CompressionConfig.rank_for, so a
    draft rank is always one the cascade kernels accept)."""
    rd = max(1, int(round(fraction * rank)))
    if rank >= 256 and rd >= 64:
        rd = (rd // 64) * 64
    return min(rd, rank)


def derive_draft_params(params, spec: DraftSpec):
    """The "free draft model": a parameter tree for the draft pass that
    SHARES every dense array (embeddings, lm head, norms, un-decomposed
    quantized weights) with the served tree by reference, and replaces
    each `LowRankQ` cascade node with its first-`draft_rank` components
    (`itera.truncate` on the unpacked carrier, repacked if the serving
    node was packed). With `spec.act_wl` set, quantized leaves are
    restamped to the draft activation word length — an aux-only change
    that copies no device memory.

    A tree with no LowRankQ nodes and act_wl=None derives an exact copy
    (acceptance 1.0, zero draft savings) — allowed, because it exercises
    the machinery on dense engines, but pointless in production; the
    engine warns in that case.
    """

    def is_node(x):
        return isinstance(x, (LowRankQ, QuantizedTensor))

    def f(leaf):
        if isinstance(leaf, LowRankQ):
            lr = LowRankQ(unpack_weights(leaf.w1), unpack_weights(leaf.w2))
            # logical rank from the w2 carrier: (..., r, N) — robust for
            # scan-stacked (L, r, N) leaves where `.rank` (== shape[1] of
            # w1) would read the K axis
            r = int(lr.w2.values.shape[-2])
            rd = draft_rank(r, spec.rank_fraction)
            if rd < r:
                lr = truncate(lr, rd)
            w1, w2 = lr.w1, lr.w2
            if spec.act_wl is not None:
                w1 = dataclasses.replace(w1, act_wl=spec.act_wl)
                w2 = dataclasses.replace(w2, act_wl=spec.act_wl)
            if leaf.w1.packed:
                w1 = pack_weights(w1)
            if leaf.w2.packed:
                w2 = pack_weights(w2)
            return LowRankQ(w1, w2)
        if isinstance(leaf, QuantizedTensor) and spec.act_wl is not None:
            return dataclasses.replace(leaf, act_wl=spec.act_wl)
        return leaf

    return jax.tree_util.tree_map(f, params, is_leaf=is_node)


def is_exact_draft(params, draft_params) -> bool:
    """True when the derived draft is semantically identical to the
    served tree (no cascade was truncated, no act_wl changed) — i.e.
    speculation will accept everything and save nothing."""
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(draft_params)):
        if a is not b:
            return False
    la = [l for l in jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, QuantizedTensor))
        if isinstance(l, QuantizedTensor)]
    lb = [l for l in jax.tree_util.tree_leaves(
        draft_params, is_leaf=lambda x: isinstance(x, QuantizedTensor))
        if isinstance(l, QuantizedTensor)]
    return all(x.act_wl == y.act_wl for x, y in zip(la, lb))


def speculative_step(params, draft_params, pool, block_tables, step_buf,
                     prev, cfg, k: int, sample: bool = False):
    """One fused draft->verify->accept serving dispatch.

    step_buf: (B, W + 4 + sampling.SAMP_COLS) int32 — span tokens (B, W)
    with four metadata columns appended — ctx_lens, q_lens, use_prev,
    spec_lens — followed by the packed per-row sampling block
    (`runtime.sampling.write_row_meta`). Decode rows carry
    q_lens = 1 + spec_lens (the previous token plus their draft span);
    prefill rows carry their chunk width and spec_lens = 0. W is
    bucketed by the driver and must be >= k + 1 when k > 0.

    With `sample=True` (a static trace variant, like k), rows whose
    packed temperature is > 0 replace their emitted token with a
    temperature/top-k/top-p sample from the verify pass's last-valid
    logits, keyed by the same counter-based derivation as the plain
    serve step. Sampled rows never draft (the scheduler gives them
    spec_lens = 0), so their accept count is naturally 0 and the one
    sampled token is the round's whole emission; greedy rows are
    untouched — bit-identical to sample=False.

    Phases (all inside one jit, so the host pays ONE dispatch per round):
      draft  — k unrolled width-1 `unified_step` calls with
               `draft_params` over the SAME pool; row r participates in
               draft step i iff i < spec_lens[r] (others idle through
               the trash block). The chain starts from `prev` (the
               row's last committed token, device-resident) and each
               step feeds its argmax to the next.
      verify — one full-model `unified_step` over the whole span batch:
               decode rows' spans are [prev, d_1 .. d_k'], prefill rows
               their prompt chunk. The span scatter overwrites every
               draft-written K/V position with full-model values.
               `verify_width = k + 1` returns logits at span positions
               0..k PLUS each row's last-valid position.
      accept — n_acc[r] = length of the matching draft prefix;
               full_toks[r, 0 : n_acc+1] are the row's emitted tokens
               (greedy: always the full model's argmax chain).

    Returns (full_toks (B, k+2), n_acc (B,), next_prev (B, 1), pool):
      * decode rows emit full_toks[r, :n_acc[r]+1] (n_acc == 0 for
        rows with spec_lens == 0 — the plain decode degenerate case);
      * prefill-finishing rows emit full_toks[r, k+1] (the appended
        last-valid-position column);
      * next_prev is each row's newest token (not yet in the pool).

    k == 0 degenerates to the plain serving step in this calling
    convention (no draft passes, verify_width 1).
    """
    from repro.models import transformer as tfm
    from repro.runtime import sampling as smp

    b = step_buf.shape[0]
    m = smp.SAMP_COLS
    tokens = step_buf[:, :-(4 + m)]
    ctx_lens, q_lens, use_prev, spec_lens = (
        step_buf[:, -(m + 4)], step_buf[:, -(m + 3)],
        step_buf[:, -(m + 2)], step_buf[:, -(m + 1)])

    # ---- draft: k chained single-token passes with the truncated model
    drafts = []
    d = prev
    for i in range(k):
        ql = (spec_lens > i).astype(jnp.int32)
        dlogits, pool = tfm.unified_step(draft_params, pool, block_tables,
                                         ctx_lens + i, ql, d, cfg)
        d = jnp.argmax(dlogits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        drafts.append(d)

    # ---- verify: splice prev + drafts into the span, one full pass
    tokens = tokens.at[:, 0].set(
        jnp.where(use_prev.astype(bool), prev[:, 0], tokens[:, 0]))
    if k:
        draft_mat = jnp.concatenate(drafts, axis=1)              # (B, k)
        spec_cols = jnp.arange(k)[None, :] < spec_lens[:, None]  # (B, k)
        tokens = tokens.at[:, 1:k + 1].set(
            jnp.where(spec_cols, draft_mat, tokens[:, 1:k + 1]))
    logits, pool = tfm.unified_step(params, pool, block_tables, ctx_lens,
                                    q_lens, tokens, cfg, verify_width=k + 1)
    full_toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)    # (B, k+2)
    if sample:
        # sampled rows (temperature > 0; never drafting, so n_acc will
        # be 0) emit one token drawn from the last-valid-position logits
        # — column k+1, which for a q = 1 decode row is the same
        # position as column 0. Override both emission columns so the
        # host readback and next_prev agree whichever one a row uses.
        meta = smp.unpack_meta(step_buf)
        keys = smp.row_keys(meta["seed"], meta["rid"], meta["counter"])
        samp = smp.sample_tokens(logits[:, -1], meta["temperature"],
                                 meta["top_k"], meta["top_p"], keys)
        srow = meta["temperature"] > 0.0
        full_toks = full_toks.at[:, 0].set(
            jnp.where(srow, samp, full_toks[:, 0]))
        full_toks = full_toks.at[:, k + 1].set(
            jnp.where(srow, samp, full_toks[:, k + 1]))

    # ---- accept: longest matching draft prefix (cumprod of matches)
    if k:
        match = (draft_mat == full_toks[:, :k]) & spec_cols
        n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                        axis=1).astype(jnp.int32)
    else:
        n_acc = jnp.zeros((b,), jnp.int32)
    # newest token: accepted-prefix end for decode rows, the last-valid
    # column (k+1) for prefill rows — plain decode rows (n_acc == 0,
    # q_lens == 1) read column 0, which IS their last-valid position
    last_idx = jnp.where(use_prev.astype(bool), n_acc,
                         jnp.full((b,), k + 1, jnp.int32))
    next_prev = jnp.take_along_axis(full_toks, last_idx[:, None], axis=1)
    return full_toks, n_acc, next_prev, pool


class SpeculationController:
    """Engine-side owner of the draft execution mode: derives and holds
    the draft parameter tree and hands the serve loop a jitted
    `speculative_step` per static draft width. Stateless across serve()
    calls — per-serve acceptance stats live in `ServeResult`."""

    def __init__(self, spec: DraftSpec, cfg, params, draft_params=None, *,
                 mesh=None):
        self.spec = spec
        self.cfg = cfg
        self.draft_params = (derive_draft_params(params, spec)
                             if draft_params is None else draft_params)
        self.exact = is_exact_draft(params, self.draft_params)
        self._steps: dict[tuple[int, bool], object] = {}
        # tensor-parallel speculation: same recipe as the engine's plain
        # TP step — shard-map the whole fused round (draft chain +
        # verify + accept), draft params sliced with the SAME rules as
        # the served params (truncate acts on the rank axis, the TP
        # slice on heads/hidden columns — they commute), pool
        # head-sliced, accept bookkeeping replicated.
        self.mesh = mesh
        self._tp = (int(mesh.shape["model"])
                    if mesh is not None and "model" in mesh.axis_names
                    else 0)
        if self._tp:
            from repro.launch import sharding as shd

            shd.check_tp_geometry(cfg, self._tp)
            self._local_cfg = shd.tp_local_config(cfg, self._tp)
            self._pspecs = shd.tp_param_specs(params, self._tp)
            self._dspecs = shd.tp_param_specs(self.draft_params, self._tp)
            from jax.sharding import NamedSharding, PartitionSpec as P

            self.draft_params = jax.device_put(
                self.draft_params,
                jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s), self._dspecs,
                    is_leaf=lambda x: isinstance(x, P)))

    def step_fn(self, k: int, sample: bool = False):
        """Jitted speculative_step specialized on draft width k and the
        sampling mode (the serve loop uses k == spec.k on rounds with
        any drafting row and k == 0 otherwise, and one sample flag per
        serve call, so at most two variants trace per serve)."""
        fn = self._steps.get((k, sample))
        if fn is None:
            if self._tp:
                from jax.sharding import PartitionSpec as P

                from repro.runtime import kvblocks, shardctx

                pool_specs = kvblocks.pool_pspecs(self.cfg)

                def body(p, dp, pool, bt, buf, prev, _k=k, _s=sample):
                    with shardctx.tp_axis("model"):
                        return speculative_step(p, dp, pool, bt, buf, prev,
                                                self._local_cfg, _k,
                                                sample=_s)

                # a profile names the compiled program after this
                body.__name__ = "speculative_step"
                fn = jax.jit(shardctx.tp_shard_map(
                    body, self.mesh,
                    in_specs=(self._pspecs, self._dspecs, pool_specs,
                              P(), P(), P()),
                    out_specs=(P(), P(), P(), pool_specs)))
            else:
                def body(p, dp, pool, bt, buf, prev, _k=k, _s=sample):
                    return speculative_step(p, dp, pool, bt, buf, prev,
                                            self.cfg, _k, sample=_s)

                body.__name__ = "speculative_step"
                fn = jax.jit(body)
            self._steps[(k, sample)] = fn
        return fn

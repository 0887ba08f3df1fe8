"""Bring-up check on the TPU: serve full-width opus-mt through the engine's
own entry points (`InferenceEngine.build` + `engine.serve`, the calls
`repro.launch.serve` makes) and hold every serve to a reference on the
same chip.

    python chip_smoke.py             # one chip
    python chip_smoke.py --tp 4      # (1, 4) tensor-parallel mesh only

One chip: the 12-layer d=512 proxy (`configs/opus_mt.py`, random weights
from --seed) serves 16 ragged requests (prompts of 64-512 tokens, up to 32
new tokens, one of them sampled) twice — dense, and under a uniform
packed ITERA-W4 plan (rank_fraction 0.5). For each it checks that:
  * the serving step compiled the Pallas kernels (`tpu_custom_call`
    named paged_attention, and quant_matmul / lowrank_qmm under the plan);
  * a second serve replays the first token for token;
  * greedy tokens equal the same weights served through the jnp
    reference path (`models.set_linear_mode("ref")`, `paged_attn="ref"`),
    both paths served with f32 matmuls at full precision (REF_PRECISION).
    Where a row differs, two weaker checks must both hold instead: the
    matmul kernels alone (jnp linears, the same attention kernel) give
    identical greedy tokens, and the first-step logits of the kernel and
    reference paths agree to LOGIT_RTOL (relative L2). The line says
    which criterion held.

--tp N: the same requests on a (1, N) serving mesh against the one-chip
engine, dense and under an ITERA-W4 plan on the N-sliced sites; greedy
tokens must be identical or, where a row differs, the first-step logits
of both must agree to LOGIT_RTOL.

Earlier lines print compile seconds, steady tok/s and the device: these
are bring-up observations, not benchmark numbers. The last line is the
JSON verdict, printed only when every check passed; any failure raises
and the process exits non-zero. Without a TPU it fails at once.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "opus-mt"
N_REQUESTS = 16
PROMPT_LENS = (64, 512)
MAX_NEW = 32
SAMPLED = 5                 # index of the one sampled request
SERVE = dict(max_batch=8, block_size=16, chunk_tokens=256)
# The kernel and reference paths are compared with f32 matmuls at full
# precision. At the TPU's default precision every f32 dot rounds its
# operands to bf16, the attention kernel and XLA's gather oracle at
# different points (first-step logits of the dense model then differ by
# a relative L2 of 3.5e-3 on a v5e; 4.3e-7 at full precision). The
# integer dots of the matmul kernels are exact at any precision.
REF_PRECISION = "highest"
# Fallback bound on ||logits - ref_logits||_2 / ||ref_logits||_2 of the
# first prefill step, both at REF_PRECISION. A W4 plan requantizes every
# linear's activations to int8, so even an f32 summation-order difference
# in attention flips some codes and grows, layer by layer, to the plan's
# own quantization noise: 2.2e-2 under ITERA-W4 on a v5e, with both
# matmul kernels bit-exact to their references. A wrong kernel gives O(1).
LOGIT_RTOL = 5e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def make_requests(vocab: int, seed: int):
    from repro.api import Request

    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(N_REQUESTS):
        n = int(rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1))
        prompt = rng.integers(1, vocab, size=n).astype(np.int32)
        kw = dict(max_tokens=int(rng.integers(MAX_NEW // 2, MAX_NEW + 1)))
        if i == SAMPLED:
            kw.update(temperature=0.8, top_k=64, seed=seed)
        reqs.append(Request(tokens=prompt, **kw))
    return reqs


def greedy_rows(reqs):
    return [i for i, r in enumerate(reqs) if not r.temperature]


def serve_twice(engine, reqs, tag: str):
    """Cold serve (compiles every span-width bucket) then a warm replay,
    which must match token for token. Returns the warm result."""
    from repro.api import SamplingParams

    sp = SamplingParams(max_tokens=MAX_NEW)
    t0 = time.perf_counter()
    cold = engine.serve(reqs, sp)
    t_cold = time.perf_counter() - t0
    warm = engine.serve(reqs, sp)
    for i, (a, b) in enumerate(zip(cold.outputs, warm.outputs)):
        if not np.array_equal(a, b):
            raise AssertionError(f"{tag}: request {i} differs on replay")
    for o in warm.outputs:
        if o.size == 0 or o.min() < 0 or o.max() >= engine.cfg.vocab_size:
            raise AssertionError(f"{tag}: token out of vocabulary range")
    log(f"{tag}: cold serve {t_cold:.1f}s (compile ~"
        f"{t_cold - warm.seconds:.1f}s), steady {warm.tokens_per_second:.1f}"
        f" tok/s over {warm.total_tokens} tokens in {warm.steps} steps")
    return warm


def step_kernels(engine) -> dict:
    """Pallas kernels in the engine's compiled serving step (W=1 decode
    shape, greedy variant), by name."""
    import jax.numpy as jnp

    from repro.runtime import kvblocks, sampling as smp

    cap, mb, bs = SERVE["max_batch"], 4, SERVE["block_size"]
    pool = kvblocks.init_paged_cache(engine.cfg, cap * mb + 1, bs)
    args = (engine.params, pool, np.zeros((cap, mb), np.int32),
            np.zeros((cap, 1 + 3 + smp.SAMP_COLS), np.int32),
            jnp.zeros((cap, 1), jnp.int32), jnp.zeros((cap, 1), jnp.int32),
            jnp.zeros((cap, 1, 1), jnp.int32))
    text = engine._unified_fn(False, False).lower(*args).compile().as_text()
    found = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        for name in ("paged_attention", "quant_matmul", "lowrank_qmm"):
            if f"%{name}" in line.split("=")[0]:
                found[name] = found.get(name, 0) + 1
    return found


def first_step_logits(cfg, params, reqs, impl: str, mesh=None):
    """Last-position logits of one unified prefill step over the first
    `max_batch` prompts (first 256 tokens each), through the kernels
    (impl="kernel") or the jnp reference (impl="ref"), at REF_PRECISION;
    with `mesh`, shard-mapped over its "model" axis as the TP engine
    runs its step."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch import sharding as shd
    from repro.models import set_linear_mode
    from repro.models import transformer as tfm
    from repro.runtime import kvblocks, shardctx

    b, w, bs = SERVE["max_batch"], SERVE["chunk_tokens"], SERVE["block_size"]
    mb = w // bs
    cfg = dataclasses.replace(cfg, paged_attn_impl=impl)
    toks = np.zeros((b, w), np.int32)
    q_lens = np.zeros((b,), np.int32)
    for r, req in enumerate(reqs[:b]):
        n = min(req.tokens.size, w)
        toks[r, :n], q_lens[r] = req.tokens[:n], n
    tables = 1 + np.arange(b * mb, dtype=np.int32).reshape(b, mb)
    pool = kvblocks.init_paged_cache(cfg, b * mb + 1, bs)

    def step(p, pl, t, c, q, x):
        return tfm.unified_step(p, pl, t, c, q, x, cfg)

    if mesh is not None:
        tp = mesh.shape["model"]
        lcfg = shd.tp_local_config(cfg, tp)

        def body(p, pl, t, c, q, x):
            with shardctx.tp_axis("model"):
                return tfm.unified_step(p, pl, t, c, q, x, lcfg)

        pool_specs = kvblocks.pool_pspecs(cfg)
        step = shardctx.tp_shard_map(
            body, mesh, in_specs=(shd.tp_param_specs(params, tp), pool_specs,
                                  P(), P(), P(), P()),
            out_specs=(P(), pool_specs))
    set_linear_mode("ref" if impl == "ref" else "auto")
    try:
        with jax.default_matmul_precision(REF_PRECISION):
            logits, _ = jax.jit(step)(
                params, pool, tables, jnp.zeros((b,), jnp.int32), q_lens,
                toks)
        return np.asarray(logits[:, -1], np.float64)
    finally:
        set_linear_mode("auto")


def serve_reference(engine, reqs, linears: str, attention: str):
    """Serve `reqs` with the engine's weights through a fresh engine whose
    linears ("auto" kernels or "ref" jnp) and paged attention ("auto" or
    "ref") are chosen here."""
    from repro.api import InferenceEngine, SamplingParams
    from repro.models import set_linear_mode

    set_linear_mode(linears)
    try:
        ref = InferenceEngine.build(engine.cfg, None, params=engine.params,
                                    paged_attn=attention, **SERVE)
        return ref.serve(reqs, SamplingParams(max_tokens=MAX_NEW))
    finally:
        set_linear_mode("auto")


def differing_rows(reqs, a, b) -> list:
    return [i for i in greedy_rows(reqs)
            if not np.array_equal(a.outputs[i], b.outputs[i])]


def check_against_reference(engine, reqs, served, tag: str) -> None:
    """Hold the engine's kernels to the jnp reference path (module
    docstring), first exactly at REF_PRECISION, else by the fallback;
    `served` is the engine's own serve of `reqs` at default precision."""
    import jax

    from repro.api import SamplingParams

    n = len(greedy_rows(reqs))
    with jax.default_matmul_precision(REF_PRECISION):
        res = engine.serve(reqs, SamplingParams(max_tokens=MAX_NEW))
        ref = serve_reference(engine, reqs, "ref", "ref")
    differ = differing_rows(reqs, res, ref)
    if not differ:
        sampled = np.array_equal(res.outputs[SAMPLED], ref.outputs[SAMPLED])
        log(f"{tag}: greedy tokens identical to the jnp reference path at "
            f"{REF_PRECISION} matmul precision ({n} rows; criterion: exact;"
            f" sampled row {'identical' if sampled else 'differs'})")
        return
    log(f"{tag}: {len(differ)}/{n} greedy rows differ from the jnp "
        f"reference path at {REF_PRECISION} matmul precision (rows {differ})")
    lin = serve_reference(engine, reqs, "ref", "auto")
    lin_differ = differing_rows(reqs, served, lin)
    lk = first_step_logits(engine.cfg, engine.params, reqs, "kernel")
    lr = first_step_logits(engine.cfg, engine.params, reqs, "ref")
    rel = float(np.linalg.norm(lk - lr) / np.linalg.norm(lr))
    log(f"{tag}: matmul kernels alone: {len(lin_differ)}/{n} greedy rows "
        f"differ from jnp linears; first-step logits relative L2 {rel:.3e} "
        f"(criterion: matmul kernels exact and logits within "
        f"{LOGIT_RTOL:g})")
    if lin_differ:
        raise AssertionError(f"{tag}: matmul kernels change greedy tokens "
                             f"in rows {lin_differ}")
    if not rel <= LOGIT_RTOL:
        raise AssertionError(f"{tag}: kernel and reference logits disagree "
                             f"({rel:.3e} > {LOGIT_RTOL:g})")


def itera_plan(params, **selection):
    from repro.api import CompressionPlan

    return CompressionPlan.uniform(params, method="itera", weight_wl=4,
                                   rank_fraction=0.5, label="itera_w4",
                                   **selection)


def one_chip(seed: int) -> None:
    import jax

    from repro.api import InferenceEngine
    from repro.configs import get_config
    from repro.models import init_params

    cfg = get_config(ARCH)
    reqs = make_requests(cfg.vocab_size, seed)
    log(f"{len(reqs)} requests, prompt lens {[r.tokens.size for r in reqs]}")
    params = init_params(jax.random.PRNGKey(seed), cfg)
    for tag, plan in (("dense", None), ("itera-w4", itera_plan(params))):
        t0 = time.perf_counter()
        engine = InferenceEngine.build(cfg, plan, params=params, **SERVE)
        jax.block_until_ready(engine.params)
        log(f"{tag}: built in {time.perf_counter() - t0:.1f}s, weights "
            f"{engine.weight_hbm_bytes() / 2**20:.1f} MiB")
        kernels = step_kernels(engine)
        log(f"{tag}: serving-step Pallas kernels {kernels}")
        if not kernels.get("paged_attention"):
            raise AssertionError(f"{tag}: no paged_attention kernel in the "
                                 f"serving step")
        if plan is not None:
            if not (kernels.get("quant_matmul") or kernels.get("lowrank_qmm")):
                raise AssertionError(f"{tag}: no matmul kernel in the "
                                     f"serving step")
            if not any(lp.packed for lp in engine.report.layers):
                raise AssertionError(f"{tag}: the W4 plan packed nothing")
        served = serve_twice(engine, reqs, tag)
        check_against_reference(engine, reqs, served, tag)


def tensor_parallel(seed: int, tp: int) -> None:
    import jax
    from jax.sharding import NamedSharding

    from repro.api import InferenceEngine, SamplingParams
    from repro.configs import get_config
    from repro.launch.mesh import make_serving_mesh
    from repro.models import init_params
    from repro.runtime import kvblocks

    cfg = get_config(ARCH)
    reqs = make_requests(cfg.vocab_size, seed)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    mesh = make_serving_mesh(tp)
    if len(set(mesh.devices.flat)) != tp or mesh.shape["model"] != tp:
        raise AssertionError(f"serving mesh spans {mesh.devices.shape}")
    pool = jax.device_put(
        kvblocks.init_paged_cache(cfg, 9, SERVE["block_size"]),
        {k: NamedSharding(mesh, s)
         for k, s in kvblocks.pool_pspecs(cfg).items()})
    shard = pool["k"].sharding.shard_shape(pool["k"].shape)
    if shard[2] != cfg.num_kv_heads // tp or \
            len(pool["k"].addressable_shards) != tp:
        raise AssertionError(f"KV pool head axis not split {tp} ways: "
                             f"shard shape {shard}")
    log(f"mesh {dict(mesh.shape)} over {tp} devices; KV pool shard shape "
        f"{shard} (head axis 2 split {tp} ways)")
    sp = SamplingParams(max_tokens=MAX_NEW)
    # packed columns slice per shard only in whole pack tiles: the MLP
    # up projection (d_ff / tp columns per shard) packs, the attention
    # projections (d_model / tp) would not, so the plan takes the N-sliced
    # site whose packed shards stay whole (launch.sharding._TP_RULES)
    plans = (("dense", None),
             ("itera-w4-up", itera_plan(params, include=r"/up$")))
    for tag, plan in plans:
        single = InferenceEngine.build(cfg, plan, params=params, **SERVE)
        r1 = single.serve(reqs, sp)
        eng = InferenceEngine.build(cfg, None, params=single.params,
                                    mesh=mesh, **SERVE)
        eng.serve(reqs, sp)                           # compile
        r4 = eng.serve(reqs, sp)
        differ = differing_rows(reqs, r1, r4)
        sampled = np.array_equal(r1.outputs[SAMPLED], r4.outputs[SAMPLED])
        log(f"{tag}: tp={tp} steady {r4.tokens_per_second:.1f} tok/s vs one "
            f"chip {r1.tokens_per_second:.1f} tok/s (cold, compile "
            f"included); greedy rows differing from one chip: {differ}; "
            f"sampled row {'identical' if sampled else 'differs'}")
        if not differ:
            continue
        # the sharded wo/down sum their f32 partials in another order
        # than the one-chip dot, and a W4 plan's requantization grows
        # that to its quantization noise (LOGIT_RTOL)
        l1 = first_step_logits(cfg, single.params, reqs, "kernel")
        l4 = first_step_logits(cfg, single.params, reqs, "kernel", mesh)
        rel = float(np.linalg.norm(l4 - l1) / np.linalg.norm(l1))
        log(f"{tag}: tp={tp} first-step logits relative L2 {rel:.3e} to one "
            f"chip (criterion: tolerance {LOGIT_RTOL:g})")
        if not rel <= LOGIT_RTOL:
            raise AssertionError(f"{tag}: tp={tp} logits disagree with one "
                                 f"chip ({rel:.3e} > {LOGIT_RTOL:g})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tp", type=int, default=0, metavar="N",
                    help="run only the (1, N) tensor-parallel check")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"no TPU: JAX runs on {dev.platform}")
        return 1
    from repro.launch.compile_cache import use_checkout_cache

    log(f"device {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}, compile cache {use_checkout_cache()}")
    if args.tp:
        tensor_parallel(args.seed, args.tp)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Fused in-device sampling, stop criteria, and streaming.

The contracts under test (runtime/sampling.py + the serve/generate
paths that consume it):

  * **Greedy bit-identity** — temperature-0 rows take the raw-logits
    argmax inside the SAME fused step as sampled rows, so a greedy
    request's tokens are bit-identical whether it rides a greedy-only
    serve, a mixed batch, or a speculative round.
  * **Seeded reproducibility** — keys are a pure function of
    (seed, rid, counter), so seeded serves replay token-for-token
    across repeats, prefix-cache on/off, TP mesh sizes (subprocess
    matrix), and the generate()/serve() split.
  * **Stop truncation** — device-side eos / stop-sequence / max_tokens
    evaluation agrees with the `match_stop_host` numpy oracle applied
    to the unstopped stream, inclusively.
  * **Streaming + SLO** — on_token delivers every token in order with
    exactly one final event per request; ServeResult's queue/goodput/
    attainment metrics are consistent with the outputs.

The dtype x kv x mesh x cache determinism matrix runs in ONE
subprocess under XLA_FLAGS=--xla_force_host_platform_device_count=8
(this process must keep seeing 1 device), same as test_tp_serving.
"""
import asyncio
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.engine import InferenceEngine, SamplingParams, TokenEvent
from repro.configs import get_config
from repro.core.compress import CompressionConfig
from repro.hw import tpu_model
from repro.launch.serve import serve_stream
from repro.models.transformer import init_params
from repro.runtime import sampling as smp
from repro.runtime.scheduler import Request
from repro.runtime.speculation import DraftSpec

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


@pytest.fixture(scope="module")
def base():
    cfg = get_config("opus-mt", smoke=True)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def engine(base):
    cfg, params = base
    return InferenceEngine(cfg, params, max_batch=3, block_size=4,
                           chunk_tokens=8)


def _prompts(vocab, seed=0, lens=(5, 11, 3, 14, 8)):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).astype(np.int32) for n in lens]


SAMPLED = SamplingParams(max_tokens=6, temperature=0.9, top_k=20,
                         top_p=0.9, seed=7)


# ------------------------------------------------------------ params --

def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(eos_id=-2)
    with pytest.raises(ValueError):
        SamplingParams(stop=((1, 2), ()))
    assert SamplingParams(top_p=1.0).top_p == 1.0


def test_sampling_params_json_roundtrip():
    sp = SamplingParams(max_tokens=9, temperature=0.7, top_k=5, top_p=0.85,
                        seed=3, eos_id=2, stop=((4, 5), (6,)))
    d = json.loads(json.dumps(sp.to_dict()))
    assert SamplingParams.from_dict(d) == sp
    # defaults survive a round trip through a sparse dict too
    assert SamplingParams.from_dict(
        json.loads(json.dumps(SamplingParams().to_dict()))) \
        == SamplingParams()


# ------------------------------------------------------- unit: keys --

def test_row_keys_pure_in_request_and_counter():
    seed = jnp.array([7, 7, 9], jnp.int32)
    rid = jnp.array([0, 1, 0], jnp.int32)
    ctr = jnp.array([3, 3, 3], jnp.int32)
    keys = np.asarray(smp.row_keys(seed, rid, ctr))
    # same (seed, rid, counter) gives the same key at any batch row
    solo = np.asarray(smp.row_keys(seed[:1], rid[:1], ctr[:1]))
    assert np.array_equal(keys[0], solo[0])
    # rid and seed both separate streams
    assert not np.array_equal(keys[0], keys[1])
    assert not np.array_equal(keys[0], keys[2])
    # consecutive counters separate draws within a stream
    nxt = np.asarray(smp.row_keys(seed[:1], rid[:1], ctr[:1] + 1))
    assert not np.array_equal(keys[0], nxt[0])


def test_f32_bits_roundtrip():
    for x in (0.0, 1.0, 0.9, 1e-3, 3.5):
        bits = smp.f32_bits(x)
        back = np.int32(bits).view(np.float32)
        assert back == np.float32(x)


# ---------------------------------------------------- unit: sampler --

def test_sample_tokens_greedy_and_degenerate_knobs():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4, 33)).astype(np.float32))
    keys = smp.row_keys(jnp.arange(4), jnp.arange(4), jnp.zeros(4, jnp.int32))
    argmax = np.asarray(jnp.argmax(logits, axis=-1))

    temp0 = smp.sample_tokens(logits, jnp.zeros(4), jnp.zeros(4, jnp.int32),
                              jnp.ones(4), keys)
    assert np.array_equal(np.asarray(temp0), argmax)
    # top_k = 1 collapses the distribution to the argmax even when hot
    k1 = smp.sample_tokens(logits, jnp.full(4, 2.0),
                           jnp.ones(4, jnp.int32), jnp.ones(4), keys)
    assert np.array_equal(np.asarray(k1), argmax)
    # a vanishing top_p keeps only the top token
    p0 = smp.sample_tokens(logits, jnp.full(4, 2.0),
                           jnp.zeros(4, jnp.int32), jnp.full(4, 1e-6), keys)
    assert np.array_equal(np.asarray(p0), argmax)
    # top_k bounds the support of actual sampling
    order = np.asarray(jnp.argsort(logits, axis=-1)[:, ::-1])
    k3 = smp.sample_tokens(logits, jnp.full(4, 5.0),
                           jnp.full(4, 3, jnp.int32), jnp.ones(4), keys)
    for r, t in enumerate(np.asarray(k3)):
        assert t in order[r, :3]


def test_sample_tokens_mixed_rows_independent():
    """A greedy row's output is unaffected by sampled neighbors, and a
    sampled row draws the same token at any batch position (the
    per-row-key property the serve path relies on)."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(3, 16)).astype(np.float32))
    keys = smp.row_keys(jnp.full(3, 7, jnp.int32),
                        jnp.array([0, 1, 2], jnp.int32),
                        jnp.zeros(3, jnp.int32))
    temps = jnp.array([0.0, 1.0, 0.0])
    out = np.asarray(smp.sample_tokens(
        logits, temps, jnp.zeros(3, jnp.int32), jnp.ones(3), keys))
    assert out[0] == int(jnp.argmax(logits[0]))
    assert out[2] == int(jnp.argmax(logits[2]))
    solo = np.asarray(smp.sample_tokens(
        logits[1:2], jnp.ones(1), jnp.zeros(1, jnp.int32), jnp.ones(1),
        smp.row_keys(jnp.full(1, 7, jnp.int32), jnp.ones(1, jnp.int32),
                     jnp.zeros(1, jnp.int32))))
    assert out[1] == solo[0]


# ------------------------------------------------- unit: stop oracle --

def test_match_stop_host_semantics():
    toks = [5, 3, 9, 3, 9, 2]
    assert smp.match_stop_host(toks, None, (), None) is None
    assert smp.match_stop_host(toks, 2, (), None) == 6
    assert smp.match_stop_host(toks, 9, (), None) == 3       # first hit
    # inclusive multi-token match
    assert smp.match_stop_host(toks, None, ((3, 9),), None) == 3
    assert smp.match_stop_host(toks, None, ((9, 3, 9),), None) == 5
    # max_tokens is a stop like any other; earliest criterion wins
    assert smp.match_stop_host(toks, None, (), 4) == 4
    assert smp.match_stop_host(toks, 3, ((5,),), 4) == 1
    # a stop longer than the stream so far never fires
    assert smp.match_stop_host([3], None, ((9, 3),), None) is None


def test_finished_mask_counter_guard_ignores_stale_ring():
    """A stop sequence fully present in the ring but longer than this
    request's own emissions (counter + 1) must not fire — that content
    belongs to the row's previous occupant."""
    recent = jnp.asarray([[4, 5, 6]], jnp.int32)
    stop = jnp.asarray(smp.pack_stop_seqs(((4, 5, 6),), 1, 3))[None]
    meta = {"counter": jnp.array([1], jnp.int32),      # only 2 own tokens
            "eos": jnp.array([-1], jnp.int32),
            "max_tokens": jnp.array([0], jnp.int32)}
    toks = jnp.array([6], jnp.int32)
    assert int(smp.finished_mask(toks, recent, meta, stop)[0]) == 0
    meta["counter"] = jnp.array([2], jnp.int32)        # now it's all ours
    assert int(smp.finished_mask(toks, recent, meta, stop)[0]) == 1


# ------------------------------------------------- serve: identity --

def test_temperature_zero_serve_bit_identical_to_greedy(engine):
    prompts = _prompts(engine.cfg.vocab_size)
    greedy = engine.serve(prompts, SamplingParams(max_tokens=6))
    # temperature=0 with sampling knobs set still reduces to argmax
    t0 = engine.serve(prompts, SamplingParams(
        max_tokens=6, temperature=0.0, top_k=5, top_p=0.5, seed=11))
    for i, (a, b) in enumerate(zip(greedy.outputs, t0.outputs)):
        np.testing.assert_array_equal(b, a, err_msg=f"request {i}")


def test_mixed_batch_temp0_rows_match_greedy(engine):
    """Greedy rows inside a mixed sampled batch (the fused sample-branch
    step, do_sample=True) stay bit-identical to the greedy-only serve."""
    prompts = _prompts(engine.cfg.vocab_size, seed=2)
    greedy = engine.serve(prompts, SamplingParams(max_tokens=6))
    reqs = [Request(tokens=p,
                    temperature=0.0 if i % 2 == 0 else 0.8,
                    top_k=10, top_p=0.9, seed=5)
            for i, p in enumerate(prompts)]
    mixed = engine.serve(reqs, SamplingParams(max_tokens=6))
    changed = 0
    for i, (a, b) in enumerate(zip(greedy.outputs, mixed.outputs)):
        if i % 2 == 0:
            np.testing.assert_array_equal(
                b, a, err_msg=f"greedy row {i} perturbed by sampled batch")
        else:
            changed += not np.array_equal(a, b)
    assert changed > 0, "sampling never diverged from greedy (degenerate)"


def test_seeded_serve_reproducible_and_seed_sensitive(engine):
    prompts = _prompts(engine.cfg.vocab_size, seed=3)
    a = engine.serve(prompts, SAMPLED)
    b = engine.serve(prompts, SAMPLED)
    for i, (x, y) in enumerate(zip(a.outputs, b.outputs)):
        np.testing.assert_array_equal(y, x, err_msg=f"request {i}")
    other = engine.serve(prompts, dataclasses.replace(SAMPLED, seed=8))
    assert any(not np.array_equal(x, y)
               for x, y in zip(a.outputs, other.outputs))


def test_generate_matches_serve_under_shared_seed(engine):
    """The rectangular generate() path and the continuous-batching serve
    path derive identical keys (rid = batch row = submission order), so
    a seeded sampled run agrees token-for-token."""
    prompts = _prompts(engine.cfg.vocab_size, seed=4, lens=(6, 6, 6))
    g = engine.generate(np.stack(prompts), SAMPLED)
    s = engine.serve(prompts, SAMPLED)
    for i in range(len(prompts)):
        np.testing.assert_array_equal(
            np.asarray(s.outputs[i]), g.tokens[i],
            err_msg=f"request {i}: generate != serve")


def test_prefix_cache_on_off_sampled_identity(base):
    """Seeded sampled outputs are invariant to prefix-cache hits — keys
    depend on the emission counter, not on how much prefill was skipped.
    The cache must actually engage for the test to mean anything."""
    cfg, params = base
    eng = InferenceEngine(cfg, params, max_batch=3, block_size=4,
                          chunk_tokens=8)
    rng = np.random.default_rng(5)
    prefix = rng.integers(1, cfg.vocab_size, size=12).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.integers(
        1, cfg.vocab_size, size=2 + i % 4).astype(np.int32)])
        for i in range(5)]
    off = eng.serve(prompts, SAMPLED, prefix_cache=False)
    on = eng.serve(prompts, SAMPLED, prefix_cache=True)
    assert on.cache_hit_blocks > 0
    for i, (a, b) in enumerate(zip(off.outputs, on.outputs)):
        np.testing.assert_array_equal(b, a, err_msg=f"request {i}")


# ----------------------------------------------------- serve: stops --

def test_stop_truncation_matches_host_oracle(engine):
    """Device-side stop truncation == the numpy oracle applied to the
    unstopped stream, for eos and multi-token stop sequences, on both
    greedy and sampled rows. Counter-based keys make the sampled stream
    itself invariant to the stop config, so the oracle diff is exact."""
    prompts = _prompts(engine.cfg.vocab_size, seed=6)
    for sp in (SamplingParams(max_tokens=10),
               dataclasses.replace(SAMPLED, max_tokens=10)):
        full = engine.serve(prompts, sp)
        stream = [np.asarray(o) for o in full.outputs]
        eos = int(stream[0][1])
        stops = ((int(stream[1][2]), int(stream[1][3])),
                 (int(stream[2][0]),))
        sp_stop = dataclasses.replace(sp, eos_id=eos, stop=stops)
        res = engine.serve(prompts, sp_stop)
        hit = 0
        for i, out in enumerate(res.outputs):
            keep = smp.match_stop_host(stream[i], eos, stops, 10)
            assert keep is not None
            hit += keep < 10
            np.testing.assert_array_equal(
                np.asarray(out), stream[i][:keep],
                err_msg=f"request {i}: device stop != oracle")
        assert hit > 0, "no row actually stopped early (degenerate pick)"
        assert res.stopped_early == hit


def test_per_request_stop_overrides(engine):
    """Request-level eos/stop fields override the call-level params, and
    rows finishing early free their slots for waiting requests."""
    prompts = _prompts(engine.cfg.vocab_size, seed=7, lens=(5, 7, 4, 9))
    full = engine.serve(prompts, SamplingParams(max_tokens=8))
    s0 = np.asarray(full.outputs[0])
    eos0 = int(s0[1])
    stop2 = ((int(np.asarray(full.outputs[2])[0]),),)
    reqs = [Request(tokens=prompts[0], eos_id=eos0),
            Request(tokens=prompts[1]),
            Request(tokens=prompts[2], stop=stop2),
            Request(tokens=prompts[3])]
    res = engine.serve(reqs, SamplingParams(max_tokens=8))
    keep0 = smp.match_stop_host(s0, eos0, (), 8)
    assert keep0 < 8 and len(res.outputs[0]) == keep0
    assert len(res.outputs[2]) == 1
    np.testing.assert_array_equal(res.outputs[1], full.outputs[1])
    np.testing.assert_array_equal(res.outputs[3], full.outputs[3])
    assert res.stopped_early == 2


# ----------------------------------------------- serve: speculation --

def test_mixed_greedy_sampled_with_speculation(base):
    """Speculation composes with sampling: greedy rows keep drafting
    (token-identical to non-speculative serve), sampled rows are never
    drafted but sample the identical stream off the verify logits."""
    cfg, _ = base
    plan = CompressionConfig(method="itera", weight_wl=8, rank_fraction=0.75)
    eng = InferenceEngine.build(cfg, plan, max_batch=3, block_size=4,
                                chunk_tokens=8,
                                speculate=DraftSpec(k=3, rank_fraction=0.7))
    prompts = _prompts(cfg.vocab_size, seed=8)
    reqs = lambda: [Request(tokens=p,                       # noqa: E731
                            temperature=0.0 if i % 2 else 0.9,
                            top_k=15, top_p=0.95, seed=13)
                    for i, p in enumerate(prompts)]
    sp = SamplingParams(max_tokens=6)
    plain = eng.serve(reqs(), sp, speculate=False)
    spec = eng.serve(reqs(), sp)
    assert spec.spec_rounds > 0 and spec.drafted > 0
    for i, (a, b) in enumerate(zip(plain.outputs, spec.outputs)):
        np.testing.assert_array_equal(
            b, a, err_msg=f"request {i}: speculative != plain")


def test_speculative_stop_sequences_match_oracle(base):
    """The speculative loop's host-side stop matching truncates exactly
    like the fused device path (shared oracle semantics)."""
    cfg, _ = base
    plan = CompressionConfig(method="itera", weight_wl=8, rank_fraction=0.75)
    eng = InferenceEngine.build(cfg, plan, max_batch=3, block_size=4,
                                chunk_tokens=8,
                                speculate=DraftSpec(k=3, rank_fraction=0.7))
    prompts = _prompts(cfg.vocab_size, seed=9)
    full = eng.serve(prompts, SamplingParams(max_tokens=8))
    stream = [np.asarray(o) for o in full.outputs]
    # stop on the token whose first appearance in any stream comes
    # latest: rows then decode (and draft) before the stop can fire,
    # where a token that opens most streams would stop every row first
    eos = max((j, int(t)) for s in stream for j, t in enumerate(s)
              if t not in s[:j])[1]
    sp = SamplingParams(max_tokens=8, eos_id=eos)
    res = eng.serve(prompts, sp)
    assert res.spec_rounds > 0
    for i, out in enumerate(res.outputs):
        keep = smp.match_stop_host(stream[i], eos, (), 8)
        np.testing.assert_array_equal(np.asarray(out), stream[i][:keep],
                                      err_msg=f"request {i}")


# -------------------------------------------------------- streaming --

def test_on_token_event_stream(engine):
    prompts = _prompts(engine.cfg.vocab_size, seed=10, lens=(5, 9, 3))
    events = []
    res = engine.serve(prompts, SAMPLED, on_token=events.append)
    by_rid = {}
    for e in events:
        assert isinstance(e, TokenEvent)
        by_rid.setdefault(e.rid, []).append(e)
    assert sorted(by_rid) == [0, 1, 2]
    for rid, evs in by_rid.items():
        assert [e.index for e in evs] == list(range(len(evs)))
        np.testing.assert_array_equal(
            [e.token for e in evs], np.asarray(res.outputs[rid]),
            err_msg=f"rid {rid}: streamed tokens != outputs")
        assert [e.final for e in evs] == \
            [False] * (len(evs) - 1) + [True]
        assert all(b.time >= a.time for a, b in zip(evs, evs[1:]))


def test_serve_stream_async_front_door(engine):
    prompts = _prompts(engine.cfg.vocab_size, seed=11, lens=(4, 7))

    async def drive():
        events, result = [], None
        async for item in serve_stream(engine, prompts, SAMPLED):
            if isinstance(item, TokenEvent):
                assert result is None, "event after final result"
                events.append(item)
            else:
                result = item
        return events, result

    events, res = asyncio.run(drive())
    assert res is not None and len(res.outputs) == 2
    assert len(events) == sum(len(o) for o in res.outputs)
    finals = [e for e in events if e.final]
    assert sorted(e.rid for e in finals) == [0, 1]


# ------------------------------------------------------ SLO metrics --

def test_slo_metrics_consistent(engine):
    prompts = _prompts(engine.cfg.vocab_size, seed=12)
    full = engine.serve(prompts, SamplingParams(max_tokens=8))
    eos = int(np.asarray(full.outputs[0])[1])
    res = engine.serve(prompts, SamplingParams(max_tokens=8, eos_id=eos))
    n = len(prompts)
    assert len(res.queue_times) == n and len(res.finish_times) == n
    assert all(t >= 0.0 for t in res.queue_times)
    assert all(f > 0.0 for f in res.finish_times)
    assert res.queue_p95 >= res.queue_p50 >= 0.0
    assert res.stopped_early >= 1
    # goodput is monotone in the deadline and saturates at full
    # throughput once every request makes it
    deadlines = [0.0, max(res.finish_times) / 2, max(res.finish_times) + 1]
    gp = [res.goodput(d) for d in deadlines]
    assert gp == sorted(gp) and gp[0] == 0.0
    assert gp[-1] == pytest.approx(res.tokens_per_second)
    assert res.slo_attainment(1e9, 1e9) == 1.0
    assert 0.0 <= res.slo_attainment(
        max(res.finish_times) / 2, 1e-9) <= 1.0


# --------------------------------------------------- hardware model --

def test_sampling_point_pricing():
    p = tpu_model.sampling_point(batch=8, vocab=32000)
    g = tpu_model.sampling_point(batch=8, vocab=32000, sampled_frac=0.0)
    assert g.overhead_vs_greedy == 1.0
    assert p.overhead_vs_greedy > 1.0
    # the fused path beats the PCIe logits round-trip by a wide margin
    assert p.speedup_vs_host > 10.0
    prev = None
    for v in (1024, 8192, 32000, 128000):
        pt = tpu_model.sampling_point(batch=8, vocab=v)
        if prev is not None:
            assert pt.host_s > prev.host_s
            assert pt.fused_s > prev.fused_s
        assert pt.speedup_vs_host > 10.0
        prev = pt
    # sampled_frac interpolates between argmax-only and full-sort cost
    half = tpu_model.sampling_point(batch=8, vocab=32000, sampled_frac=0.5)
    assert g.fused_s < half.fused_s < p.fused_s
    for bad in (dict(batch=0, vocab=8), dict(batch=1, vocab=1),
                dict(batch=1, vocab=8, sampled_frac=-0.1)):
        with pytest.raises(ValueError):
            tpu_model.sampling_point(**bad)


# --------------------------------------- subprocess: the full matrix --

def test_seeded_determinism_matrix():
    """fp32/bf16 x bf16/int8-KV x mesh 1/2 x prefix-cache on/off: a
    seeded sampled serve emits the SAME tokens in all 16 cells (and on a
    repeat run), because keys are a pure function of (seed, rid,
    counter) — none of model dtype's logits permutations, KV rounding,
    TP sharding, or skipped prefill enter the derivation. Within a
    (dtype, kv) pair every mesh/cache variant is token-identical; across
    dtypes the logits differ so streams may too."""
    out = run_sub("""
        import dataclasses
        import numpy as np
        import jax
        from repro.api.engine import InferenceEngine, SamplingParams
        from repro.configs import get_config
        from repro.launch.mesh import make_serving_mesh
        from repro.models import transformer as tfm

        rng = np.random.default_rng(0)
        sp = SamplingParams(max_tokens=5, temperature=0.8, top_k=20,
                            top_p=0.9, seed=7)
        cfg0 = get_config("opus-mt", smoke=True)
        prefix = rng.integers(1, cfg0.vocab_size, size=12).astype(np.int32)
        prompts = [np.concatenate([prefix, rng.integers(
            1, cfg0.vocab_size, size=2 + i % 4).astype(np.int32)])
            for i in range(5)]
        for dtype in ("float32", "bfloat16"):
            for kv_bits in (16, 8):
                cfg = dataclasses.replace(cfg0, dtype=dtype,
                                          kv_cache_bits=kv_bits)
                params = tfm.init_params(jax.random.PRNGKey(0), cfg)
                ref = None
                for tp in (1, 2):
                    eng = InferenceEngine.build(
                        cfg, params=params,
                        mesh=make_serving_mesh(tp) if tp > 1 else None,
                        max_batch=3, block_size=4, chunk_tokens=8)
                    for cache in (False, True):
                        r = eng.serve(prompts, sp, prefix_cache=cache)
                        if cache:
                            assert r.cache_hit_blocks > 0, (dtype, kv_bits)
                        if ref is None:
                            ref = r.outputs
                            rep = eng.serve(prompts, sp, prefix_cache=cache)
                            for i, (a, b) in enumerate(
                                    zip(ref, rep.outputs)):
                                assert np.array_equal(a, b), (
                                    f"repeat drift request {i}")
                        else:
                            for i, (a, b) in enumerate(
                                    zip(ref, r.outputs)):
                                assert np.array_equal(a, b), (
                                    f"{dtype}/kv{kv_bits}/tp{tp}/"
                                    f"cache={cache} request {i}: "
                                    f"{b} != {a}")
                        print(f"OK {dtype} kv{kv_bits} tp{tp} "
                              f"cache={int(cache)}")
        print("SAMPLING_MATRIX_DONE")
        """)
    assert "SAMPLING_MATRIX_DONE" in out
    assert out.count("OK ") == 16

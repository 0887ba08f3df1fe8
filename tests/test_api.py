"""Plan→engine API tests: JSON round-trips, per-layer mixed-precision
plans, validation, and the end-to-end DSE→deployment loop
(co_design -> DesignPoint -> from_design_point -> JSON -> Engine -> tokens).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    CompressionPlan, InferenceEngine, LayerPlan, SamplingParams, merge_plans,
)
from repro.configs import get_config
from repro.core.compress import CompressionConfig, compress_params
from repro.hw import dse
from repro.models import init_params
from repro.models.transformer import forward


@pytest.fixture(scope="module")
def smoke():
    cfg = get_config("opus-mt", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _leaves_equal(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


# ------------------------------------------------------------------ plan --
def test_uniform_plan_matches_config_shim(smoke):
    """CompressionConfig is a thin shim: lowering it to a uniform plan and
    executing either one must produce bit-identical compressed trees."""
    cfg, params = smoke
    ccfg = CompressionConfig(method="quant", weight_wl=4)
    plan = CompressionPlan.uniform(params, method="quant", weight_wl=4)
    cp_plan, rep_plan = compress_params(params, plan)
    cp_cfg, rep_cfg = compress_params(params, ccfg)
    assert _leaves_equal(cp_plan, cp_cfg)
    # both reports carry per-layer plan provenance
    assert rep_cfg.plan is not None
    assert [lp.to_dict() for lp in rep_cfg.plan] == \
           [lp.to_dict() for lp in rep_plan.plan]


def test_json_roundtrip_bit_identical(smoke):
    """serialize -> deserialize -> compress must be bit-identical to
    compressing with the original plan (the deployment artifact is exact)."""
    cfg, params = smoke
    plan = CompressionPlan.uniform(params, method="itera", weight_wl=4,
                                   rank_fraction=0.3, label="rt")
    restored = CompressionPlan.loads(plan.dumps())
    assert restored == plan
    cp1, _ = compress_params(params, plan)
    cp2, _ = compress_params(params, restored)
    assert _leaves_equal(cp1, cp2)


def test_plan_file_roundtrip(tmp_path, smoke):
    _, params = smoke
    plan = CompressionPlan.uniform(params, method="quant", weight_wl=6,
                                   label="disk")
    p = tmp_path / "plan.json"
    plan.save(str(p))
    assert CompressionPlan.load(str(p)) == plan


def test_mixed_precision_plan(smoke):
    """W4 attention / W8 MLP with differing ranks — inexpressible by the
    single-method CompressionConfig — compresses and runs end-to-end."""
    cfg, params = smoke
    base = CompressionPlan.uniform(params, method="itera", weight_wl=8,
                                   rank_fraction=0.5)
    mixed = base.replace(label="w4attn_w8mlp", layers=tuple(
        LayerPlan(lp.path, "itera",
                  4 if "attn" in lp.path else 8,
                  max(1, lp.rank // 2) if "attn" in lp.path else lp.rank)
        for lp in base.layers))
    assert len({lp.wl for lp in mixed.layers}) == 2, \
        "smoke model must yield both attn and mlp plan entries"
    cp, rep = compress_params(params, mixed)
    assert {lr.wl for lr in rep.layers} == {4, 8}
    assert len({lr.rank for lr in rep.layers}) > 1
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    h, _ = forward(cp, toks, cfg)
    assert bool(jnp.isfinite(h).all())


def test_merge_plans(smoke):
    _, params = smoke
    base = CompressionPlan.uniform(params, method="quant", weight_wl=8)
    override = LayerPlan(base.layers[0].path, "quant", 4)
    merged = merge_plans(base, [override])
    assert merged.layers[0].wl == 4
    assert all(lp.wl == 8 for lp in merged.layers[1:])
    assert len(merged) == len(base)


def test_validate_rejects_bad_plans(smoke):
    _, params = smoke
    good = CompressionPlan.uniform(params, method="itera", weight_wl=4,
                                   rank_fraction=0.5)
    path = good.layers[0].path
    with pytest.raises(ValueError, match="not found"):
        CompressionPlan(layers=(LayerPlan("no/such/weight", "quant", 8),)
                        ).validate(params)
    with pytest.raises(ValueError, match="exceeds"):
        CompressionPlan(layers=(LayerPlan(path, "itera", 4, rank=10_000),)
                        ).validate(params)
    with pytest.raises(ValueError, match="duplicate"):
        CompressionPlan(layers=(LayerPlan(path, "quant", 8),
                                LayerPlan(path, "quant", 4))).validate()
    with pytest.raises(ValueError, match="rank"):
        CompressionPlan(layers=(LayerPlan(path, "itera", 4),)).validate()
    with pytest.raises(ValueError, match="wl"):
        CompressionPlan(layers=(LayerPlan(path, "quant", 16),)).validate()
    with pytest.raises(ValueError, match="method"):
        CompressionPlan(layers=(LayerPlan(path, "magic", 8),)).validate()


# ---------------------------------------------------------------- engine --
def test_engine_greedy_deterministic(smoke):
    cfg, params = smoke
    eng = InferenceEngine.build(cfg, None, params=params)
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0,
                                 cfg.vocab_size)
    a = eng.generate(prompts, SamplingParams(max_tokens=6))
    b = eng.generate(prompts, SamplingParams(max_tokens=6))
    np.testing.assert_array_equal(a.tokens, b.tokens)
    assert a.tokens.shape == (2, 6) and a.prompt_len == 12


def test_engine_sampling_modes(smoke):
    cfg, params = smoke
    eng = InferenceEngine.build(
        cfg, CompressionConfig(method="quant", weight_wl=8), params=params)
    prompts = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0,
                                 cfg.vocab_size)
    out = eng.generate(prompts, SamplingParams(
        max_tokens=5, temperature=0.7, top_k=13, seed=7))
    assert out.tokens.shape == (2, 5)
    assert out.tokens.min() >= 0 and out.tokens.max() < cfg.vocab_size
    # same seed -> same sample; different seed -> (almost surely) different
    out2 = eng.generate(prompts, SamplingParams(
        max_tokens=5, temperature=0.7, top_k=13, seed=7))
    np.testing.assert_array_equal(out.tokens, out2.tokens)


def test_engine_prompt_length_bucketing(smoke):
    """Prompts are right-padded to power-of-two buckets before prefill:
    tokens stay identical to the unbucketed path, and N distinct prompt
    lengths compile O(log N) prefill variants instead of N."""
    cfg, params = smoke
    eng = InferenceEngine.build(cfg, None, params=params)
    ref = InferenceEngine(cfg, eng.params, bucket_prompts=False)
    assert eng.bucket_prompts and not ref.bucket_prompts
    rng = np.random.default_rng(11)
    lens = [5, 6, 7, 9, 11, 13, 15]                 # buckets: 8 and 16
    for n in lens:
        prompts = rng.integers(0, cfg.vocab_size, size=(2, n))
        a = eng.generate(prompts, SamplingParams(max_tokens=4))
        b = ref.generate(prompts, SamplingParams(max_tokens=4))
        np.testing.assert_array_equal(a.tokens, b.tokens)
    assert eng._prefill._cache_size() == 2          # one per bucket
    assert ref._prefill._cache_size() == len(lens)


def test_engine_accepts_ragged_requests(smoke):
    """Ragged prompt lists route through the continuous-batching scheduler
    and come back per-request, greedy-identical to solo generation."""
    cfg, params = smoke
    eng = InferenceEngine.build(cfg, None, params=params)
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
    res = eng.generate(prompts, SamplingParams(max_tokens=3))
    assert res.tokens.shape == (3, 3)
    assert res.prompt_lens == [3, 2, 4] and res.prompt_len == 4
    for p, got in zip(prompts, res.tokens):
        solo = eng.generate(np.asarray([p]), SamplingParams(max_tokens=3))
        np.testing.assert_array_equal(got, solo.tokens[0])
    with pytest.raises(ValueError, match="empty"):
        eng.generate([], SamplingParams(max_tokens=2))
    with pytest.raises(ValueError, match="1-D"):    # no silent flattening
        eng.generate([np.zeros((2, 3), np.int32)], SamplingParams(max_tokens=2))


def test_co_design_rejects_dict_candidates(smoke):
    """Legacy dict candidates must fail loudly, not score at wrong wl."""
    _, params = smoke
    with pytest.raises(TypeError, match="CompressionPlan"):
        dse.co_design([{"label": "quant_W4", "wl": 4}],
                      quality_fn=lambda c: 0.0, params=params)


def test_serve_cli_consumes_plan_file(tmp_path, smoke):
    """launch.serve is a thin CLI over the engine: --plan plan.json."""
    from repro.launch import serve as serve_mod

    _, params = smoke
    plan = CompressionPlan.uniform(params, method="quant", weight_wl=6,
                                   label="cli")
    p = tmp_path / "plan.json"
    plan.save(str(p))
    toks = serve_mod.main([
        "--arch", "opus-mt", "--smoke", "--plan", str(p),
        "--prompt-len", "12", "--gen", "4", "--batch", "2",
    ])
    assert toks.shape == (2, 4)
    assert np.asarray(toks).min() >= 0


# ------------------------------------------------- DSE -> deployment loop --
def test_design_point_to_engine_end_to_end(smoke):
    """The ISSUE acceptance demo: co_design over plan candidates -> pick a
    Pareto DesignPoint -> CompressionPlan.from_design_point -> JSON round
    trip -> Engine.build -> generate returns tokens."""
    cfg, params = smoke
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0,
                              cfg.vocab_size)
    h_ref, _ = forward(params, toks, cfg)

    base = CompressionPlan.uniform(params, method="itera", weight_wl=4,
                                   rank_fraction=0.5, label="itera_W4")
    mixed = base.replace(label="mixed_w4_w8", layers=tuple(
        LayerPlan(lp.path, "itera",
                  4 if "attn" in lp.path else 8,
                  max(1, lp.rank // 2) if "attn" in lp.path else lp.rank)
        for lp in base.layers))
    candidates = [
        CompressionPlan.uniform(params, method="quant", weight_wl=8),
        base, mixed,
    ]

    def quality(plan):
        cp, rep = compress_params(params, plan)
        plan.meta["ratio"] = rep.compression_ratio
        h, _ = forward(cp, toks, cfg)
        return -float(jnp.linalg.norm(h - h_ref) / jnp.linalg.norm(h_ref))

    front = dse.co_design(candidates, quality, params=params, batch_m=64)
    assert front, "co_design returned an empty Pareto front"
    assert all(dp.plan is not None for dp in front)

    dp = front[-1]                              # highest-quality point
    plan = CompressionPlan.from_design_point(dp)
    assert plan.meta["design_point"] == dp.label
    assert plan.meta["latency"] == pytest.approx(dp.latency)
    restored = CompressionPlan.loads(plan.dumps())

    engine = InferenceEngine.build(cfg, restored, params=params)
    res = engine.generate(toks[:, :12], SamplingParams(max_tokens=4))
    assert res.tokens.shape == (2, 4)
    assert res.tokens.min() >= 0 and res.tokens.max() < cfg.vocab_size
    assert engine.report is not None and engine.report.plan is not None

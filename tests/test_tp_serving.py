"""Tensor-parallel sharded serving tests.

The contract under test: `engine.build(mesh=make_serving_mesh(N))`
serves greedy outputs TOKEN-IDENTICAL to the single-device engine —
across dtypes, KV-cache precisions, mesh sizes, and with speculative
decoding — because the shard_map step computes the same math, just
split over heads/hidden columns with one psum per layer boundary.

Mesh cases run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (tests in this
process must keep seeing 1 device); the whole dtype x kv x mesh matrix
runs in ONE subprocess to amortize import + compile cost. Pure-rule
cases (TP spec rules, geometry errors) run in-process; the sampled-
serving TP identity case rides its own subprocess.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


# ------------------------------------------------------------- identity --

def test_tp_serve_token_identity_matrix():
    """Greedy serve on forced 2- and 4-device meshes (and the degenerate
    1-device mesh, which runs the same shard_map path) is token-identical
    to the single-device engine for fp32/bf16 models with bf16 and int8
    KV, on a mixed prefill/decode batch (ragged prompts, chunked prefill
    forced by a small token budget)."""
    out = run_sub("""
        import dataclasses
        import numpy as np
        import jax
        from repro.api.engine import InferenceEngine, SamplingParams
        from repro.configs import get_config
        from repro.launch.mesh import make_serving_mesh
        from repro.models import transformer as tfm

        rng = np.random.default_rng(0)
        sp = SamplingParams(max_tokens=6)
        for dtype in ("float32", "bfloat16"):
            for kv_bits in (16, 8):
                cfg = dataclasses.replace(
                    get_config("opus-mt", smoke=True),
                    dtype=dtype, kv_cache_bits=kv_bits)
                params = tfm.init_params(jax.random.PRNGKey(0), cfg)
                prompts = [rng.integers(1, cfg.vocab_size, size=n)
                           .astype(np.int32) for n in (5, 11, 3, 16, 8)]
                solo = InferenceEngine.build(
                    cfg, params=params, max_batch=3, block_size=4,
                    chunk_tokens=8)
                r0 = solo.serve(prompts, sp)
                for tp in (1, 2, 4):
                    eng = InferenceEngine.build(
                        cfg, params=params, mesh=make_serving_mesh(tp),
                        max_batch=3, block_size=4, chunk_tokens=8)
                    r1 = eng.serve(prompts, sp)
                    # small budget + more requests than rows => chunked
                    # prefill overlapping decode, the regime under test
                    assert r1.mixed_steps > 0, (dtype, kv_bits, tp)
                    for i, (a, b) in enumerate(zip(r0.outputs, r1.outputs)):
                        assert np.array_equal(a, b), (
                            f"{dtype}/kv{kv_bits}/tp{tp} request {i}: "
                            f"{b} != {a}")
                    print(f"OK {dtype} kv{kv_bits} tp{tp}")
        print("MATRIX_DONE")
        """)
    assert "MATRIX_DONE" in out
    assert out.count("OK ") == 12          # 2 dtypes x 2 kv x 3 meshes


def test_tp_speculative_identity():
    """Speculative decoding under TP: the same truncated-cascade draft +
    verify + accept round, shard-mapped, emits tokens identical to both
    the single-device speculative engine and plain non-speculative serve.
    Compression is restricted to N-sliced sites (wq/wk/wv/gate/up) whose
    TP slice is bit-exact — see launch.sharding._TP_RULES."""
    out = run_sub("""
        import numpy as np
        import jax
        from repro.api.engine import InferenceEngine, SamplingParams
        from repro.api.plan import CompressionPlan
        from repro.configs import get_config
        from repro.core.compress import CompressionConfig, shape_spectra
        from repro.launch.mesh import make_serving_mesh
        from repro.models import transformer as tfm
        from repro.runtime.speculation import DraftSpec

        cfg = get_config("opus-mt", smoke=True)
        params = shape_spectra(
            tfm.init_params(jax.random.PRNGKey(0), cfg), alpha=3.0)
        cc = CompressionConfig(method="svd", weight_wl=8,
                               rank_fraction=0.75,
                               include=r"/(wq|wk|wv|gate|up)$")
        plan = CompressionPlan.from_config(params, cc)
        spec = DraftSpec(k=4, rank_fraction=0.25)
        rng = np.random.default_rng(2)
        prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
                   for n in (5, 11, 3, 16, 8)]
        sp = SamplingParams(max_tokens=8)
        kw = dict(params=params, max_batch=3, block_size=4, chunk_tokens=8)
        r_plain = InferenceEngine.build(cfg, plan, **kw).serve(prompts, sp)
        r_solo = InferenceEngine.build(cfg, plan, speculate=spec,
                                       **kw).serve(prompts, sp)
        for tp in (2, 4):
            eng = InferenceEngine.build(cfg, plan, speculate=spec,
                                        mesh=make_serving_mesh(tp), **kw)
            r_tp = eng.serve(prompts, sp)
            assert r_tp.drafted > 0 and r_tp.spec_rounds > 0
            for i in range(len(prompts)):
                assert np.array_equal(r_plain.outputs[i], r_tp.outputs[i])
                assert np.array_equal(r_solo.outputs[i], r_tp.outputs[i])
            print(f"OK tp{tp} accept={r_tp.accept_rate:.2f}")
        print("SPEC_DONE")
        """)
    assert "SPEC_DONE" in out


def test_tp_generate_ragged_identity():
    """engine.generate on a ragged batch routes through serve — the TP
    engine must match there too (the public API most callers use)."""
    run_sub("""
        import numpy as np
        import jax
        from repro.api.engine import InferenceEngine, SamplingParams
        from repro.configs import get_config
        from repro.launch.mesh import make_serving_mesh
        from repro.models import transformer as tfm

        cfg = get_config("opus-mt", smoke=True)
        params = tfm.init_params(jax.random.PRNGKey(3), cfg)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
                   for n in (7, 13, 4)]
        sp = SamplingParams(max_tokens=5)
        g0 = InferenceEngine.build(cfg, params=params).generate(prompts, sp)
        g1 = InferenceEngine.build(
            cfg, params=params,
            mesh=make_serving_mesh(2)).generate(prompts, sp)
        assert np.array_equal(g0.tokens, g1.tokens)
        """)


# ----------------------------------------------------- geometry / errors --

def test_tp_geometry_divisibility_errors():
    """GQA head counts (and d_ff) that don't divide the mesh raise a
    descriptive error naming the offending ModelConfig field — shard_map
    has no GSPMD padding to hide behind."""
    import dataclasses

    from repro.configs import get_config
    from repro.launch.sharding import check_tp_geometry

    cfg = get_config("opus-mt", smoke=True)
    # smoke geometry: 4 heads, 4 kv heads, d_ff 256 — divides 1/2/4
    for tp in (1, 2, 4):
        check_tp_geometry(cfg, tp)

    gqa = dataclasses.replace(cfg, num_kv_heads=2)
    with pytest.raises(ValueError, match=r"num_kv_heads=2"):
        check_tp_geometry(gqa, 4)
    with pytest.raises(ValueError, match=r"no GSPMD padding"):
        check_tp_geometry(gqa, 4)
    check_tp_geometry(gqa, 2)       # 2 kv heads over 2 shards is fine

    odd = dataclasses.replace(cfg, num_heads=6, num_kv_heads=6)
    with pytest.raises(ValueError, match=r"num_heads=6"):
        check_tp_geometry(odd, 4)

    ssm = dataclasses.replace(cfg, layout="mamba1")
    with pytest.raises(NotImplementedError, match=r"dense"):
        check_tp_geometry(ssm, 2)


def test_tp_spec_rules_unit():
    """TP param slicing rules, no mesh needed: N-sites column-sliced,
    K-sites row-sliced with replicated per-output-column scales, LowRankQ
    w1 replicated / w2 column-sliced on N-sites, everything else
    replicated. Leading scan-stack dims stay unsharded."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch.sharding import tp_spec_for

    z = jnp.zeros
    # dense sites (leading L stack dim)
    assert tp_spec_for("layers/attn/wq", z((2, 64, 64)), 2) == \
        P(None, None, "model")
    assert tp_spec_for("layers/attn/wo", z((2, 64, 64)), 2) == \
        P(None, "model", None)
    assert tp_spec_for("layers/mlp/up", z((2, 64, 256)), 2) == \
        P(None, None, "model")
    assert tp_spec_for("layers/mlp/down", z((2, 256, 64)), 2) == \
        P(None, "model", None)
    # quantized dense: values follow the site, K-site scales replicate
    assert tp_spec_for("layers/attn/wq/values", z((2, 64, 64)), 2) == \
        P(None, None, "model")
    assert tp_spec_for("layers/attn/wq/scale", z((2, 1, 64)), 2) == \
        P(None, None, "model")
    assert tp_spec_for("layers/mlp/down/values", z((2, 256, 64)), 2) == \
        P(None, "model", None)
    assert tp_spec_for("layers/mlp/down/scale", z((2, 1, 64)), 2) == \
        P(None, None, None)
    # low-rank cascade on an N-site: w1 fully replicated, w2 col-sliced,
    # w2's per-rank-row scale replicated
    assert tp_spec_for("layers/attn/wk/w1/values", z((2, 64, 48)), 2) == \
        P(None, None, None)
    assert tp_spec_for("layers/attn/wk/w1/scale", z((2, 1, 48)), 2) == \
        P(None, None, None)
    assert tp_spec_for("layers/attn/wk/w2/values", z((2, 48, 64)), 2) == \
        P(None, None, "model")
    assert tp_spec_for("layers/attn/wk/w2/scale", z((2, 48, 1)), 2) == \
        P(None, None, None)
    # low-rank on a K-site: w1 rows sliced, everything else replicated
    assert tp_spec_for("layers/mlp/down/w1/values", z((2, 256, 48)), 2) == \
        P(None, "model", None)
    assert tp_spec_for("layers/mlp/down/w2/values", z((2, 48, 64)), 2) == \
        P(None, None, None)
    # replicated leaves
    assert tp_spec_for("embed", z((100, 64)), 2) == P(None, None)
    assert tp_spec_for("lm_head", z((64, 100)), 2) == P(None, None)
    assert tp_spec_for("final_norm/gamma", z((64,)), 2) == P(None)
    # tp=1: everything replicated, same code path
    assert tp_spec_for("layers/attn/wq", z((2, 64, 64)), 1) == \
        P(None, None, None)
    # non-divisible slice dim is a hard error naming the path
    with pytest.raises(ValueError, match=r"layers/mlp/up"):
        tp_spec_for("layers/mlp/up", z((2, 64, 250)), 4)


def test_tp_packed_slices_keep_whole_pack_tiles():
    """A packed W4 leaf is column-sliced only where every shard keeps
    whole 256-column pack tiles — then a shard's bytes are exactly the
    packed form of its own columns. A slice that would cut tiles (each
    shard's high nibbles would hold another shard's columns) is refused
    with the path named; row-sliced K-sites never cut the packed axis."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.quant import pack_weights, quantize
    from repro.launch.sharding import tp_param_specs

    def packed(k, n):
        q = pack_weights(quantize(jnp.ones((2, k, n)), 4, axis=1))
        assert q.packed
        return q

    params = {"layers": {"mlp": {"up": packed(64, 1024),
                                 "down": packed(1024, 256)}}}
    specs = tp_param_specs(params, 4)["layers"]["mlp"]
    assert specs["up"].values == P(None, None, "model")   # 256 cols/shard
    assert specs["down"].values == P(None, "model", None)
    with pytest.raises(ValueError, match=r"layers/attn/wq/values.*128"):
        tp_param_specs({"layers": {"attn": {"wq": packed(64, 512)}}}, 4)


def test_serving_mesh_needs_devices():
    """make_serving_mesh raises with the XLA_FLAGS recipe when the host
    has too few devices (this process sees exactly 1)."""
    from repro.launch.mesh import make_serving_mesh

    with pytest.raises(ValueError, match=r"xla_force_host_platform"):
        make_serving_mesh(4)
    with pytest.raises(ValueError, match=r">= 1"):
        make_serving_mesh(0)
    mesh = make_serving_mesh(1)
    assert mesh.shape["model"] == 1 and mesh.shape["data"] == 1


def test_build_rejects_bad_tp_geometry():
    """engine.build(mesh=...) runs the geometry check up front — a
    non-dividing GQA config fails at build, not mid-serve."""
    run_sub("""
        import dataclasses
        from repro.api.engine import InferenceEngine
        from repro.configs import get_config
        from repro.launch.mesh import make_serving_mesh

        cfg = dataclasses.replace(get_config("opus-mt", smoke=True),
                                  num_kv_heads=2)
        try:
            InferenceEngine.build(cfg, mesh=make_serving_mesh(4))
        except ValueError as e:
            assert "num_kv_heads=2" in str(e), str(e)
        else:
            raise AssertionError("bad GQA geometry built successfully")
        """)


# ---------------------------------------------------------- temperature --

def test_serve_sampled_tp_matches_single_device():
    """Seeded sampled serving is token-identical across TP mesh sizes:
    the residual (hence logits and per-row PRNG keys) is replicated
    after the boundary psums, so every shard samples the same token —
    and the counter-based keys make mesh 1 and mesh 2 draw the same
    stream for the same (seed, rid, counter)."""
    run_sub("""
        import numpy as np
        from repro.api.engine import InferenceEngine, SamplingParams
        from repro.launch.mesh import make_serving_mesh

        prompts = [list(range(1, 8)), list(range(3, 15)), [5, 4, 3]]
        sp = SamplingParams(max_tokens=6, temperature=0.8, top_k=20,
                            top_p=0.9, seed=7)
        ref = InferenceEngine.build("opus-mt", smoke=True).serve(prompts, sp)
        tp = InferenceEngine.build(
            "opus-mt", smoke=True, mesh=make_serving_mesh(2)
        ).serve(prompts, sp)
        for a, b in zip(ref.outputs, tp.outputs):
            assert np.array_equal(a, b), (a, b)
        print("TP_SAMPLED_OK")
        """)

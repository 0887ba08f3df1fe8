"""The weights the benchmark makes from its seed have exactly the tree,
shapes, dtypes and static aux that `compress_params` emits for the same
uniform packed W4 plan, ITERA or quantization alone, and the reference
regenerates them bit for bit, layer by layer."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import synth
from families import dense

SMOKE = [
    dict(num_layers=2, d_model=512, num_heads=4, num_kv_heads=2,
         head_dim=128, d_ff=1024, vocab_size=1000, mlp_act="swiglu",
         norm="rmsnorm", dtype="bfloat16",
         plan=dict(method="itera", weight_wl=4, act_wl=8,
                   rank_fraction=0.5, pack=True)),
    dict(num_layers=2, d_model=256, num_heads=4, num_kv_heads=4,
         head_dim=64, d_ff=1024, vocab_size=1024, mlp_act="gelu",
         norm="layernorm", dtype="float32",
         plan=dict(method="itera", weight_wl=4, act_wl=8,
                   rank_fraction=0.5, pack=True)),
    dict(num_layers=2, d_model=512, num_heads=4, num_kv_heads=2,
         head_dim=128, d_ff=1024, vocab_size=1000, mlp_act="swiglu",
         norm="rmsnorm", dtype="bfloat16",
         plan=dict(method="quant", weight_wl=4, act_wl=8, pack=True)),
]


def _model_config(cfg):
    from repro.configs.base import ModelConfig

    keys = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
            "d_ff", "vocab_size", "mlp_act", "norm", "dtype")
    return ModelConfig(name="smoke", **{k: cfg[k] for k in keys})


@pytest.mark.parametrize("cfg", SMOKE, ids=["swiglu-bf16", "gelu-f32",
                                            "quant-swiglu-bf16"])
def test_tree_matches_compress_params(cfg):
    from repro.api.plan import CompressionPlan
    from repro.core.compress import compress_params
    from repro.models import transformer as tfm

    params = tfm.init_params(jax.random.PRNGKey(0), _model_config(cfg))
    p = cfg["plan"]
    plan = CompressionPlan.uniform(
        params, method=p["method"], weight_wl=4, act_wl=8,
        rank_fraction=p.get("rank_fraction", 0.5), power_iters=1)
    want, _ = compress_params(params, plan)
    got = dense.program_tree(3_000_000_017, cfg)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_pack_matches_program_layout():
    from repro.core.quant import pack_int4, unpack_int4

    codes = jax.random.randint(jax.random.PRNGKey(1), (3, 512), -7, 8,
                               jnp.int8)
    np.testing.assert_array_equal(synth.pack(codes), pack_int4(codes))
    np.testing.assert_array_equal(synth.unpack(synth.pack(codes)), codes)
    np.testing.assert_array_equal(unpack_int4(synth.pack(codes)), codes)


def _regenerates_layer_slices(cfg, n_arrays):
    """Every layer is made one at a time (`lax.map`), and one layer made
    alone reads the same bits, both compiled as a run compiles them: the
    tree in one jitted call, the reference's layer in another (a scale
    divided by a constant rounds alike only where both are)."""
    key = synth.seed_key(5)
    raw = dense._make_raw_jit(key, json.dumps(cfg, sort_keys=True))
    for site in ("attn/wk", "mlp/down"):
        shp = dense.site_shapes(cfg, site)
        one = jax.jit(lambda k, layer, s=site, p=shp: synth.site_arrays(
            k, p, s, layer))(key, 1)
        assert len(one) == len(raw[site]) == n_arrays
        for a, b in zip(one, raw[site]):
            np.testing.assert_array_equal(a, b[1])


def test_site_regenerates_layer_slices():
    _regenerates_layer_slices(SMOKE[0], 4)


def test_quant_site_regenerates_layer_slices():
    _regenerates_layer_slices(SMOKE[2], 2)


def test_seed_changes_weights():
    cfg = SMOKE[1]
    a = dense.make_raw(synth.seed_key(1), cfg)["attn/wq"][0]
    b = dense.make_raw(synth.seed_key(2), cfg)["attn/wq"][0]
    assert not np.array_equal(a, b)


"""The weights the benchmark makes from its seed have exactly the tree,
shapes, dtypes and static aux that `compress_params` emits for the same
uniform packed ITERA-W4 plan, and the reference regenerates them bit for
bit, layer by layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import synth

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
]


def _model_config(cfg):
    from repro.configs.base import ModelConfig

    keys = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
            "d_ff", "vocab_size", "mlp_act", "norm", "dtype")
    return ModelConfig(name="smoke", **{k: cfg[k] for k in keys})


@pytest.mark.parametrize("cfg", SMOKE, ids=["swiglu-bf16", "gelu-f32"])
def test_tree_matches_compress_params(cfg):
    from repro.api.plan import CompressionPlan
    from repro.core.compress import compress_params
    from repro.models import transformer as tfm

    dense = tfm.init_params(jax.random.PRNGKey(0), _model_config(cfg))
    plan = CompressionPlan.uniform(
        dense, method="itera", weight_wl=4, act_wl=8,
        rank_fraction=cfg["plan"]["rank_fraction"], power_iters=1)
    want, _ = compress_params(dense, plan)
    got = synth.build_program_tree(3_000_000_017, cfg)
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


def test_site_regenerates_layer_slices():
    cfg = SMOKE[0]
    key = synth.seed_key(5)
    raw = synth.make_raw(key, cfg)
    for site in ("attn/wk", "mlp/down"):
        shp = synth.site_shapes(cfg, site)
        one = synth.site_arrays(key, shp, site, 1)
        for a, b in zip(one, raw[site]):
            np.testing.assert_array_equal(a, b[1])


def test_seed_changes_weights():
    cfg = SMOKE[1]
    a = synth.make_raw(synth.seed_key(1), cfg)["attn/wq"][0]
    b = synth.make_raw(synth.seed_key(2), cfg)["attn/wq"][0]
    assert not np.array_equal(a, b)

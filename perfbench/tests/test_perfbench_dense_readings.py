"""The dense family reads what the harness read before model families and
plan methods were files of their own. For both configurations' model and
plan, at a small size with their own MLP, norm, positions and dtype,
`dense_readings.npz` holds what the earlier harness made from one seed: a
digest of every array of the served tree, the reference logits of two
sequences, and the work of each kernel for fixed token counts. The dense
family must make the same, bit for bit."""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

import work
from families import dense

READINGS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "dense_readings.npz")
SMALL = {
    "phi3m-itera-w4": dict(
        num_layers=2, d_model=512, num_heads=4, num_kv_heads=2,
        head_dim=128, d_ff=1536, vocab_size=320, mlp_act="swiglu",
        norm="rmsnorm", norm_eps=1e-05, pos_emb="rope", rope_theta=10000.0,
        dtype="bfloat16", tie_embeddings=False, family="dense",
        plan=dict(method="itera", weight_wl=4, act_wl=8, rank_fraction=0.2,
                  pack=True)),
    "opus-mt-itera-w4": dict(
        num_layers=2, d_model=256, num_heads=4, num_kv_heads=4,
        head_dim=64, d_ff=1024, vocab_size=512, mlp_act="gelu",
        norm="layernorm", norm_eps=1e-05, pos_emb="sinusoidal",
        rope_theta=10000.0, dtype="bfloat16", tie_embeddings=False,
        family="dense",
        plan=dict(method="itera", weight_wl=4, act_wl=8, rank_fraction=0.5,
                  pack=True)),
}
SEED = 3000001501
TOK = work.Tokens(prompt_lens=[5, 17], decode_positions=[5, 6, 17, 18, 19],
                  emitted=7)


@pytest.fixture(scope="module")
def recorded():
    with np.load(READINGS) as f:
        return dict(f)


def _digest(a) -> str:
    a = np.asarray(a)
    return hashlib.sha256(f"{a.dtype}{a.shape}".encode()
                          + a.tobytes()).hexdigest()


@pytest.mark.parametrize("name", sorted(SMALL))
def test_tree_is_unchanged(recorded, name):
    tree = dense.program_tree(SEED, SMALL[name])
    got = {jax.tree_util.keystr(p): _digest(v)
           for p, v in jax.tree_util.tree_leaves_with_path(tree)}
    assert got == json.loads(str(recorded[f"{name}/tree"]))


@pytest.mark.parametrize("name", sorted(SMALL))
def test_reference_logits_are_unchanged(recorded, name):
    cfg = SMALL[name]
    rng = np.random.default_rng(7)
    seqs = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
            for n in (11, 7)]
    for i, h in enumerate(dense.hidden_states(SEED, cfg, seqs)):
        np.testing.assert_array_equal(dense.logits(SEED, cfg, h),
                                      recorded[f"{name}/logits{i}"])


@pytest.mark.parametrize("name", sorted(SMALL))
def test_work_is_unchanged(recorded, name):
    got = dense.work(SMALL[name], TOK, 3, SEED)
    assert got == json.loads(str(recorded[f"{name}/work"]))
    assert list(got) == ["lowrank_qmm", "paged_attention"]

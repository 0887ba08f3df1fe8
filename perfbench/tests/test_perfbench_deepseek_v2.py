"""The deepseek_v2 family at a tiny size on the CPU: its tree is the
program's parameter layout, its cell runs end to end through the harness
(traced, so its routed `work` reads the window's rows) and reads
`correct`, a token fault reads false, and its expert reads are the
reference router's picks, whatever width the sequences are padded to."""
import json

import jax
import numpy as np
import pytest

import cpu_cell
import methods
import work
from families import deepseek_v2 as ds

TINY_DS = {
    "name": "tiny-ds", "source": "test", "reduced": [],
    "family": "deepseek_v2",
    "model": {"num_layers": 3, "d_model": 256, "num_heads": 4,
              "num_kv_heads": 4, "head_dim": 48, "d_ff": 512,
              "vocab_size": 512, "mlp_act": "swiglu", "norm": "rmsnorm",
              "norm_eps": 1e-06, "pos_emb": "rope", "rope_theta": 10000.0,
              "dtype": "float32", "tie_embeddings": False,
              "kv_lora_rank": 64, "qk_nope_head_dim": 32,
              "qk_rope_head_dim": 16, "v_head_dim": 32,
              "rope_scaling": {"factor": 40.0,
                               "original_max_position_embeddings": 4096,
                               "beta_fast": 32.0, "beta_slow": 1.0,
                               "mscale": 0.707, "mscale_all_dim": 0.707},
              "moe": {"num_experts": 8, "top_k": 3, "num_shared": 1,
                      "d_expert": 256, "first_dense": 1,
                      "norm_topk_prob": False,
                      "routed_scaling_factor": 1.0}},
    "plan": {"method": "itera", "weight_wl": 4, "act_wl": 8,
             "rank_fraction": 0.5, "pack": True},
    "serving": {"max_batch": 4, "block_size": 16, "chunk_tokens": 32,
                "matmul_precision": "highest", "num_blocks": 33},
    "max_positions": 512,
}
SEED = 3000001601


def _leaves(tree, path=""):
    """{path: node} down to arrays and linear nodes."""
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(_leaves(v, f"{path}/{k}" if path else k))
        return out
    return {path: tree}


def test_tree_is_the_programs_layout():
    """Every path of the program's own parameter tree is in the family's
    tree, with the same leading (layer[, expert]) axes and the same
    logical (K, N) where the plan made a linear node."""
    from repro.core.itera import LowRankQ
    from repro.models import transformer as tfm

    cfg = cpu_cell.cfg(TINY_DS)
    pcfg = ds.program_config(TINY_DS)
    want = _leaves(jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), pcfg)))
    got = _leaves(jax.eval_shape(lambda: ds.program_tree(SEED, cfg)))
    assert set(got) == set(want)
    for path, node in got.items():
        shape = want[path].shape
        if isinstance(node, LowRankQ):
            assert node.w1.shape[:-1] == shape[:-1], path
            assert node.w2.shape[:-2] + node.w2.shape[-1:] == \
                shape[:-2] + shape[-1:], path
        else:
            assert node.shape == shape, path
    router = got["layers/moe/router"]
    assert router.dtype == np.float32 and router.shape == (2, 256, 8)


@pytest.mark.parametrize("fault", [None, "token"])
def test_cell_runs_through_the_harness(tmp_path, fault):
    _, cell = cpu_cell.make_copy(
        str(tmp_path), "tiny_bursts", cpu_cell.BURSTS, config=TINY_DS,
        metrics=("output_tok_s", "tokens_per_step", "step_mfu",
                 "lowrank_gmm_roofline"))
    res, err = cpu_cell.run(str(tmp_path), cell, seconds=1.0,
                            trace=0 if fault else 1, fault=fault)
    gap = res["check"]["max_gap"]
    if fault:
        assert res["correct"] is False and gap["value"] > gap["limit"]
        return
    assert res["correct"] is True, err[-2000:]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["compiles_in_window"] == 0
    assert res["metrics"]["step_mfu"]["value"] > 0
    assert res["metrics"]["tokens_per_step"]["value"] > 0


def test_expert_reads_are_the_reference_routers_picks():
    """`routing` over two requests whose rows span three steps: each
    (step, MoE layer)'s experts are the top-k picks of the reference run
    over each sequence alone and unpadded; `work` prices each read at
    one expert's factors and each routed pair at its two products."""
    cfg = cpu_cell.cfg(TINY_DS)
    rng = np.random.default_rng(0)
    ids = [rng.integers(1, 512, 13).astype(np.int32),
           rng.integers(1, 512, 40).astype(np.int32)]
    reqs = [work.Rows(ids[0], list(range(10)) + [10, 11],
                      [0] * 10 + [1, 2]),
            work.Rows(ids[1], list(range(38)) + [38], [1] * 38 + [2])]
    tok = work.Tokens([10, 38], [10, 11, 38], 5, reqs)
    got = ds.routing(SEED, cfg, tok)
    want = {}
    for r in reqs:
        picks = []
        ds._run(SEED, cfg, [r.ids], picks=picks)
        for layer, p in enumerate(picks):
            for pos, step in zip(r.positions, r.steps):
                want.setdefault((step, layer), set()).update(
                    int(e) for e in p[0][pos])
    assert got == want and len(got) == 3 * 2
    w = ds.work(cfg, tok, 3, SEED)
    assert list(w) == ["lowrank_qmm", "lowrank_gmm", "paged_attention"]
    shp = [ds.site_shapes(cfg, f"moe/experts/{k}") for k in ("gate", "up",
                                                             "down")]
    m = methods.load("itera")
    pairs = 2 * tok.rows * 3
    reads = sum(len(v) for v in want.values())
    assert w["lowrank_gmm"]["ops"] == sum(2 * pairs * m.row_macs(s)
                                          for s in shp)
    assert w["lowrank_gmm"]["bytes"] == sum(
        reads * m.weight_bytes(s) + pairs * (s["k"] + 4 + s["n"] * 4)
        for s in shp)
    json.dumps(w)


def test_routing_groups_by_width_and_runs_once(monkeypatch):
    """Requests cut after their last processed position and padded in
    groups of their own width read the same picks as each sequence run
    alone; a second read of the same window reuses the first."""
    monkeypatch.setattr(ds, "ROUTE_WIDTH", 16)
    cfg = cpu_cell.cfg(TINY_DS)
    rng = np.random.default_rng(1)
    ids = [rng.integers(1, 512, 30).astype(np.int32),
           rng.integers(1, 512, 21).astype(np.int32)]
    reqs = [work.Rows(ids[0], [3, 4, 5], [0, 1, 2]),
            work.Rows(ids[1], list(range(20)), [0] * 19 + [2])]
    tok = work.Tokens([20], [3, 4, 5], 4, reqs)
    got = ds.routing(SEED, cfg, tok)
    want = {}
    for r in reqs:
        picks = []
        ds._run(SEED, cfg, [r.ids], picks=picks)
        for layer, p in enumerate(picks):
            for pos, step in zip(r.positions, r.steps):
                want.setdefault((step, layer), set()).update(
                    int(e) for e in p[0][pos])
    assert got == want
    monkeypatch.setattr(ds, "_run", None)      # a second pass would fail
    assert ds.routing(SEED, cfg, tok) is got

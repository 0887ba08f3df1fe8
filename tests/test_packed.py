"""True sub-8-bit residency, end to end: W4 plans materialize packed HBM
storage (halved device bytes, asserted against `.nbytes`), packed and
carrier engines generate identical tokens through `engine.serve`, the
honest accounting reports what is actually resident, and checkpoints
round-trip the packed layout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import CompressionPlan, InferenceEngine, SamplingParams
from repro.configs import get_config
from repro.core.compress import CompressionConfig, compress_params
from repro.core.itera import LowRankQ
from repro.core.quant import QuantizedTensor
from repro.models import init_params


@pytest.fixture(scope="module")
def smoke():
    cfg = get_config("opus-mt", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _quant_nodes(tree):
    out = []

    def visit(leaf):
        if isinstance(leaf, LowRankQ):
            out.extend([leaf.w1, leaf.w2])
        elif isinstance(leaf, QuantizedTensor):
            out.append(leaf)
        return leaf

    jax.tree_util.tree_map(
        visit, tree,
        is_leaf=lambda x: isinstance(x, (LowRankQ, QuantizedTensor)))
    return out


# -------------------------------------------------------- material packing --
def test_w4_plan_materially_packed(smoke):
    """The acceptance bar: a W4 plan's device arrays really occupy
    wl/8 · K · N bytes (+ fp32 scales) — packed nibbles, not an int8
    carrier with pretend accounting. Packing is gated per axis by
    `quant.packed_pad_ok`: a last dim whose packed padding would exceed
    its carrier's (e.g. the smoke model's 64-wide heads) stays an int8
    carrier, because the kernels would stream the same padded bytes for
    double the padded MXU work."""
    from repro.core.quant import packed_pad_ok

    _, params = smoke
    plan = CompressionPlan.uniform(params, method="quant", weight_wl=4)
    assert plan.pack
    cp, rep = compress_params(params, plan)
    nodes = _quant_nodes(cp)
    assert nodes, "smoke model produced no quantized nodes"
    n_packed = 0
    for q in nodes:
        n_codes = int(np.prod(q.shape))
        if packed_pad_ok(q.shape[-1]):
            assert q.packed, "W4 pad-ok weight left unpacked"
            assert q.values.nbytes == n_codes // 2  # wl/8 · K · N, exactly
            n_packed += 1
        else:
            assert not q.packed, "pad-inflating axis must stay carrier"
            assert q.values.nbytes == n_codes
        assert q.values.nbytes + q.scale.nbytes == q.storage_bits() // 8
    assert n_packed, "smoke model has no pad-ok W4 axis — test is vacuous"
    assert any(l.packed for l in rep.layers)
    assert (sum(l.packed for l in rep.layers)
            == sum(packed_pad_ok(q.shape[-1]) for q in nodes))
    # carrier build of the same plan doubles the PACKED nodes' bytes and
    # leaves the demoted ones alone
    cpc, _ = compress_params(params, plan.replace(pack=False))
    for q, qc in zip(_quant_nodes(cp), _quant_nodes(cpc)):
        assert qc.values.nbytes == (q.values.nbytes * 2 if q.packed
                                    else q.values.nbytes)


def test_w6_stays_carrier_and_is_labeled(smoke):
    """W6 has no byte-aligned packing: it stays int8-resident and the
    report says so — packed=False, bits charged at 8/code."""
    _, params = smoke
    cp, rep = compress_params(
        params, CompressionPlan.uniform(params, method="quant", weight_wl=6))
    for q in _quant_nodes(cp):
        assert not q.packed
        assert q.values.nbytes == int(np.prod(q.shape))
    assert not any(l.packed for l in rep.layers)
    for l in rep.layers:
        mult, k, n = (l.shape if len(l.shape) == 3 else (1, *l.shape))
        assert l.bits == (8 * k * n + 32 * n) * mult


def test_itera_w4_factors_packed(smoke):
    """ITERA factors pack per axis: W1 along R, W2 along N — each only
    when the axis is even AND pad-ok. The smoke model's rank-32 W1s and
    64-wide W2s stay carriers while the 256/512-wide W2s pack; a
    512-wide layer with rank 256 packs both factors."""
    from repro.core.quant import packed_pad_ok

    _, params = smoke
    cp, _ = compress_params(
        params, CompressionPlan.uniform(params, method="itera", weight_wl=4,
                                        rank_fraction=0.5))
    nodes = _quant_nodes(cp)
    assert nodes and all(q.act_wl == 8 for q in nodes)
    for q in nodes:
        assert q.packed == (q.shape[-1] % 2 == 0
                            and packed_pad_ok(q.shape[-1]))
    assert any(q.packed for q in nodes) and not all(q.packed for q in nodes)
    big = {"proj": {"w": jnp.asarray(
        np.random.default_rng(0).normal(size=(512, 512)), jnp.float32)}}
    cpb, _ = compress_params(
        big, CompressionPlan.uniform(big, method="itera", weight_wl=4,
                                     rank_fraction=0.5))
    (lr,) = [l for l in jax.tree_util.tree_leaves(
        cpb, is_leaf=lambda x: isinstance(x, LowRankQ))
        if isinstance(l, LowRankQ)]
    assert lr.w1.shape == (512, 256) and lr.w1.packed   # R=256: pad-ok
    assert lr.w2.shape == (256, 512) and lr.w2.packed   # N=512: pad-ok


# --------------------------------------------------------- token identity --
def test_packed_vs_carrier_serve_token_identical(smoke):
    """Nibble unpack is exact, so packed and carrier engines must emit
    the same tokens through the in-flight batching serve loop (ragged
    prompts, chunked prefill) and through rectangular generate."""
    cfg, params = smoke
    plan = CompressionPlan.uniform(params, method="quant", weight_wl=4,
                                   label="w4")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=s).astype(np.int32)
               for s in (5, 11, 8)]
    sp = SamplingParams(max_tokens=6)
    outs = {}
    for pack in (True, False):
        eng = InferenceEngine.build(cfg, plan.replace(pack=pack),
                                    params=params)
        res = eng.serve(prompts, sp)
        outs[pack] = (np.stack(res.outputs),
                      eng.generate(np.stack([prompts[0], prompts[0]]),
                                   sp).tokens,
                      eng.weight_hbm_bytes())
    np.testing.assert_array_equal(outs[True][0], outs[False][0])
    np.testing.assert_array_equal(outs[True][1], outs[False][1])
    assert outs[True][2] < outs[False][2]   # and the packed engine is smaller


@pytest.mark.parametrize("kernel", ["qmm", "lrmm"])
def test_packed_kernels_match_carrier_at_tile_widths(kernel):
    """At opus-mt's tile-aligned widths (d=512, d_ff=2048, rank 256) the
    packed W4 layout streams through both Pallas kernels (interpret mode)
    bit-identically to the int8 carrier: the in-kernel half-tile unpack
    reads exactly what core.quant.pack_int4 wrote."""
    from repro.core.itera import svd_decompose
    from repro.core.quant import pack_weights, quantize
    from repro.kernels import ops

    key = jax.random.PRNGKey(21)
    x = jax.random.normal(key, (8, 512), jnp.float32)
    w = jax.random.normal(key, (512, 2048), jnp.float32) * 0.05
    if kernel == "qmm":
        carrier = quantize(w, 4, axis=0)
        packed = pack_weights(carrier)
        assert packed.packed and packed.values.shape == (512, 1024)
        run = ops.qmm
    else:
        carrier = svd_decompose(w, 256, 4)
        packed = LowRankQ(pack_weights(carrier.w1), pack_weights(carrier.w2))
        assert packed.w1.packed and packed.w2.packed
        run = ops.lrmm
    y_carrier = run(x, carrier, use_kernel=True, interpret=True)
    y_packed = run(x, packed, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_packed),
                                  np.asarray(y_carrier))
    np.testing.assert_array_equal(
        np.asarray(run(x, packed, use_kernel=False)),
        np.asarray(run(x, carrier, use_kernel=False)))


def test_act_wl_plan_changes_tokens(smoke):
    """act_wl is honored at runtime: an A4 engine's logits diverge from
    the A8 engine's (same weights, same prompts)."""
    cfg, params = smoke
    base = CompressionPlan.uniform(params, method="quant", weight_wl=8)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 10), 1,
                              cfg.vocab_size)
    from repro.models.transformer import forward

    cp8, _ = compress_params(params, base)
    cp4, _ = compress_params(params, base.replace(act_wl=4, label="a4"))
    nodes = _quant_nodes(cp4)
    assert nodes and all(q.act_wl == 4 for q in nodes)
    h8, _ = forward(cp8, toks, cfg)
    h4, _ = forward(cp4, toks, cfg)
    assert bool(jnp.isfinite(h4).all())
    assert not np.allclose(np.asarray(h8), np.asarray(h4))


# ---------------------------------------------------------- serialization --
def test_plan_pack_flag_roundtrips(smoke):
    _, params = smoke
    plan = CompressionPlan.uniform(params, method="quant", weight_wl=4)
    assert CompressionPlan.loads(plan.dumps()).pack is True
    off = plan.replace(pack=False)
    assert CompressionPlan.loads(off.dumps()).pack is False
    # legacy JSON without the key defaults to packed
    d = plan.to_dict()
    d.pop("pack")
    assert CompressionPlan.from_dict(d).pack is True


def test_ckpt_roundtrip_packed(tmp_path, smoke):
    """A packed compressed tree survives save/restore bit-exactly, and
    restoring into a tree with the wrong residency layout is refused."""
    from repro.checkpoint import ckpt

    _, params = smoke
    # quant: the smoke model's 256/512-wide axes really pack, so the
    # packed-vs-carrier layout refusal below has a layout to differ on
    plan = CompressionPlan.uniform(params, method="quant", weight_wl=4)
    cp, _ = compress_params(params, plan)
    ckpt.save(str(tmp_path), 7, cp)
    restored, step = ckpt.restore(str(tmp_path), cp)
    assert step == 7
    la, lb = jax.tree_util.tree_leaves(cp), jax.tree_util.tree_leaves(restored)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    carrier, _ = compress_params(params, plan.replace(pack=False))
    with pytest.raises(ValueError, match="quant layout"):
        ckpt.restore(str(tmp_path), carrier)
    # act_wl is runtime-only aux — it never changes the stored arrays, so
    # restoring into an A4 tree of the same layout is legitimate
    a4, _ = compress_params(params, plan.replace(act_wl=4))
    restored_a4, _ = ckpt.restore(str(tmp_path), a4)
    for a, b in zip(jax.tree_util.tree_leaves(cp),
                    jax.tree_util.tree_leaves(restored_a4)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ckpt_refuses_old_pack_layout(tmp_path, smoke):
    """Packed bytes saved under the earlier interleaved nibble order have
    the same shapes as today's, so the manifest's pack_layout is what
    tells them apart: a packed node whose manifest entry lacks it, or
    names another layout, is refused instead of restoring wrong weights.
    Carrier nodes carry no pack layout and are unaffected."""
    import json
    import os

    from repro.checkpoint import ckpt
    from repro.core.quant import PACK_LAYOUT

    _, params = smoke
    plan = CompressionPlan.uniform(params, method="quant", weight_wl=4)
    cp, _ = compress_params(params, plan)
    ckpt.save(str(tmp_path), 3, cp)
    path = os.path.join(str(tmp_path), "step_00000003", "manifest.json")
    with open(path) as f:
        manifest = json.load(f)
    fmts = manifest["quant_formats"]
    assert {f["pack_layout"] for f in fmts.values()} == {PACK_LAYOUT, None}
    for old in (lambda f: f.pop("pack_layout"),
                lambda f: f.update(pack_layout="int4-interleaved")):
        for f in fmts.values():
            if f["packed"]:
                old(f)
        with open(path, "w") as f:
            json.dump(manifest, f)
        with pytest.raises(ValueError, match="quant layout"):
            ckpt.restore(str(tmp_path), cp)
    carrier, _ = compress_params(params, plan.replace(pack=False))
    ckpt.save(str(tmp_path), 4, carrier)
    ckpt.restore(str(tmp_path), carrier, step=4)


# ------------------------------------------------------- honest accounting --
def test_skipped_params_counted_at_itemsize():
    """A bf16 leaf left uncompressed costs 16 bits/param in the totals,
    not an assumed 32."""
    params = {
        "proj": {"w": jnp.ones((64, 64), jnp.float32)},
        "embed": jnp.ones((128, 32), jnp.bfloat16),
    }
    cp, rep = compress_params(
        params, CompressionConfig(method="quant", weight_wl=8))
    assert rep.skipped_params == 128 * 32
    assert rep.skipped_bits == 128 * 32 * 16
    assert rep.total_bits == sum(l.bits for l in rep.layers) + 128 * 32 * 16


def test_none_method_skipped_bits_itemsize():
    params = {"embed": jnp.ones((16, 8), jnp.bfloat16)}
    _, rep = compress_params(
        params, CompressionConfig(method="none"))
    assert rep.skipped_bits == 16 * 8 * 16

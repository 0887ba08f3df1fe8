"""DeepSeek-V2-Lite's mechanisms on the serving path, at the smoke size in
float32: absorbed latent attention over the latent paged pool, YaRN,
a dense first layer, and dropless routed experts through the grouped
ITERA kernel — each held to a plain reference written here from the
model's published definition (non-absorbed attention with per-head K
and V decompressed from the latent, DeepSeek's own rotary layout, each
expert computed on the rows routed to it)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import InferenceEngine, Request
from repro.configs import get_config
from repro.core.itera import LowRankQ
from repro.core.quant import QuantizedTensor, pack_weights
from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import transformer as tfm
from repro.models.layers import yarn_inv_freq
from repro.runtime import kvblocks, tracing


@pytest.fixture(scope="module")
def model():
    cfg = get_config("deepseek-v2-lite", smoke=True)
    return cfg, tfm.init_params(jax.random.PRNGKey(0), cfg)


# ------------------------------------------------------------ reference --
def _rms(x, gamma, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * (1 + gamma)


def _yarn_freqs(dim, base, s):
    """DeepSeek-V2's YaRN frequencies, written from its reference code."""
    def corr(rot):
        return (dim * math.log(s.original_max_position_embeddings
                               / (rot * 2 * math.pi))) / (2 * math.log(base))
    lo = max(math.floor(corr(s.beta_fast)), 0)
    hi = min(math.ceil(corr(s.beta_slow)), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / s.factor
    mask = 1 - np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
    return inter * (1 - mask) + extra * mask


def _deepseek_rope(x, pos, inv):
    """DeepSeek's layout: de-interleave the pairs, then rotate halves."""
    d = x.shape[-1]
    x = x.reshape(*x.shape[:-1], d // 2, 2).swapaxes(-1, -2).reshape(x.shape)
    freqs = pos[:, None] * inv[None]
    emb = np.concatenate([freqs, freqs], -1)
    cos, sin = np.cos(emb), np.sin(emb)
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    rot = np.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _mlp(x, p):
    g, u = x @ np.asarray(p["gate"]), x @ np.asarray(p["up"])
    return (g / (1 + np.exp(-g)) * u) @ np.asarray(p["down"])


def reference_logits(cfg, params, toks):
    """Full forward pass over one whole sequence, float64 numpy."""
    P = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    H, dn, dr, C, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                        cfg.qk_rope_head_dim, cfg.kv_lora_rank,
                        cfg.v_head_dim)
    S = len(toks)
    pos = np.arange(S, dtype=np.float64)
    inv = _yarn_freqs(dr, cfg.rope_theta, cfg.rope_scaling)
    m = 0.1 * cfg.rope_scaling.mscale_all_dim * math.log(
        cfg.rope_scaling.factor) + 1
    scale = (dn + dr) ** -0.5 * m * m
    h = P["embed"][toks] * math.sqrt(cfg.d_model)
    layers = [jax.tree_util.tree_map(lambda a: a[i], P[g])
              for g in ("dense_layers", "layers")
              for i in range(P[g]["ln1"]["gamma"].shape[0])]
    for lp in layers:
        a = lp["attn"]
        x = _rms(h, lp["ln1"]["gamma"], cfg.norm_eps)
        q = (x @ a["wq"]).reshape(S, H, dn + dr)
        kv = x @ a["wkv_a"]
        c = _rms(kv[:, :C], a["kv_norm"]["gamma"], cfg.norm_eps)
        k_pe = _deepseek_rope(kv[:, C:], pos, inv)
        q_pe = _deepseek_rope(q[..., dn:], pos, inv)
        k_nope = np.einsum("sc,hnc->shn", c, a["w_uk"])
        v = np.einsum("sc,hcv->shv", c, a["w_uv"])
        s = (np.einsum("qhn,khn->hqk", q[..., :dn], k_nope)
             + np.einsum("qhr,kr->hqk", q_pe, k_pe)) * scale
        s = np.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        o = np.einsum("hqk,khv->qhv", p, v).reshape(S, H * dv)
        h = h + o @ a["wo"]
        x = _rms(h, lp["ln2"]["gamma"], cfg.norm_eps)
        if "mlp" in lp:
            h = h + _mlp(x, lp["mlp"])
            continue
        mo = lp["moe"]
        logit = x @ mo["router"]
        prob = np.exp(logit - logit.max(-1, keepdims=True))
        prob /= prob.sum(-1, keepdims=True)
        top = np.argsort(-prob, -1, kind="stable")[:, :cfg.moe.top_k]
        y = _mlp(x, mo["shared"])
        for e in range(cfg.moe.num_experts):
            rows = np.nonzero((top == e).any(-1))[0]     # routed to e
            if rows.size:
                ex = {k: mo["experts"][k][e] for k in ("gate", "up", "down")}
                y[rows] += prob[rows, e:e + 1] * _mlp(x[rows], ex)
        h = h + y
    h = _rms(h, P["final_norm"]["gamma"], cfg.norm_eps)
    return h @ P["lm_head"]


# -------------------------------------------------------------- model --
def test_prefill_then_paged_decode_matches_reference(model):
    """Two rows: chunked prefill through the latent pool (chunks of 7 and
    5, the second row idle in the first step), then three paged decode
    steps; every step's last-position logits against the reference's
    full forward pass. Tolerance 2e-3 absolute on logits of O(1): the
    served step runs float32 at "highest" against a float64 reference,
    and the absorbed form reassociates each score's sums."""
    cfg, params = model
    rng = np.random.default_rng(3)
    seqs = [rng.integers(1, cfg.vocab_size, 15), rng.integers(
        1, cfg.vocab_size, 9)]
    bs, mb = 4, 5
    pool = kvblocks.init_paged_cache(cfg, 2 * mb + 1, bs)
    assert set(pool) == {"dense_layers", "layers"}
    assert pool["layers"]["latent"].shape == (2, 2 * mb + 1, 1, bs, 128)
    tables = jnp.asarray(np.arange(1, 2 * mb + 1).reshape(2, mb), jnp.int32)
    step = jax.jit(lambda pl, ctx, ql, toks: tfm.unified_step(
        params, pl, tables, ctx, ql, toks, cfg))
    refs = [reference_logits(cfg, params, s) for s in seqs]
    # (row: (start, width)) per step
    plan = [{0: (0, 7)}, {0: (7, 5), 1: (0, 6)}, {0: (12, 1), 1: (6, 1)},
            {0: (13, 1), 1: (7, 1)}, {0: (14, 1), 1: (8, 1)}]
    with jax.default_matmul_precision("highest"):
        for spans in plan:
            w = max(n for _, n in spans.values())
            toks = np.zeros((2, w), np.int32)
            ctx, ql = np.zeros(2, np.int32), np.zeros(2, np.int32)
            for r, (lo, n) in spans.items():
                toks[r, :n] = seqs[r][lo:lo + n]
                ctx[r], ql[r] = lo, n
            lg, pool, _ = step(pool, jnp.asarray(ctx), jnp.asarray(ql),
                               jnp.asarray(toks))
            for r, (lo, n) in spans.items():
                err = np.abs(np.asarray(lg[r, 0]) - refs[r][lo + n - 1])
                assert err.max() < 2e-3, (spans, r, err.max())


def test_serve_matches_reference_tokens(model):
    """The same model through `InferenceEngine.serve` (scheduler, block
    pool, chunked prefill): greedy tokens equal the reference's argmax
    continuation."""
    cfg, params = model
    eng = InferenceEngine.build(cfg, None, params=params, max_batch=2,
                                block_size=4, chunk_tokens=8)
    rng = np.random.default_rng(5)
    reqs = [Request(tokens=rng.integers(1, cfg.vocab_size, n)
                    .astype(np.int32), max_tokens=g)
            for n, g in ((10, 4), (3, 5), (13, 3))]
    with jax.default_matmul_precision("highest"):
        res = eng.serve(reqs)
    for r, out in zip(reqs, res.outputs):
        seq = np.concatenate([r.tokens, out[:-1]])
        ref = reference_logits(cfg, params, seq)[len(r.tokens) - 1:]
        np.testing.assert_array_equal(out, ref.argmax(-1))


def test_serve_more_rows_than_budget_matches_reference(model):
    """Four rows served under a budget of two tokens a step (more rows
    than budget, the routed layer's slots sized from both): greedy
    tokens equal the reference's argmax continuation."""
    cfg, params = model
    eng = InferenceEngine.build(cfg, None, params=params, max_batch=4,
                                block_size=4, chunk_tokens=2)
    rng = np.random.default_rng(11)
    reqs = [Request(tokens=rng.integers(1, cfg.vocab_size, n)
                    .astype(np.int32), max_tokens=g)
            for n, g in ((5, 6), (3, 7), (4, 6), (2, 8))]
    with jax.default_matmul_precision("highest"):
        res = eng.serve(reqs)
    for r, out in zip(reqs, res.outputs):
        seq = np.concatenate([r.tokens, out[:-1]])
        ref = reference_logits(cfg, params, seq)[len(r.tokens) - 1:]
        np.testing.assert_array_equal(out, ref.argmax(-1))


def test_yarn_inv_freq_closed_form():
    """dim 64, base 10000, factor 40, original 4096: correction range
    [10, 23], inv_freq = f/40 (1 - mask) + f mask with
    mask = 1 - clip((i - 10) / 13, 0, 1)."""
    cfg = get_config("deepseek-v2-lite")
    inv = np.asarray(yarn_inv_freq(64, 10000.0, cfg.rope_scaling))
    f = lambda i: 10000.0 ** (-2 * i / 64)                  # noqa: E731
    for i in (0, 10, 16, 23, 31):
        mask = 1 - min(max((i - 10) / 13, 0), 1)
        want = f(i) / 40 * (1 - mask) + f(i) * mask
        np.testing.assert_allclose(inv[i], want, rtol=1e-6)
    assert attn.mla_softmax_scale(cfg) == pytest.approx(0.114722, abs=1e-6)


# ------------------------------------------------------------- routing --
def _routed_inputs(cfg, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((3, 4, cfg.d_model)), jnp.float32)
    q_lens = jnp.asarray([4, 1, 0], jnp.int32)
    return x, q_lens


def test_pad_and_idle_rows_change_no_valid_row(model):
    """Garbage in the pad slots and the idle row leaves every valid row's
    output bit for bit as it was, and invalid rows come back zero."""
    cfg, params = model
    mp = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["moe"])
    x, q_lens = _routed_inputs(cfg)
    valid = (jnp.arange(4)[None] < q_lens[:, None])[..., None]
    junk = jnp.where(valid, x, 1e3 * jnp.sign(x) + 7.0)
    for budget in (None, 5):
        y0, h0 = moe_mod.moe_routed(mp, jnp.where(valid, x, 0.0), q_lens,
                                    cfg, budget)
        y1, h1 = moe_mod.moe_routed(mp, junk, q_lens, cfg, budget)
        np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
        assert int(h0) == int(h1)
        assert not np.asarray(y1)[~np.asarray(valid[..., 0])].any()


def test_decode_rows_past_the_budget_are_routed(model):
    """Four one-token rows in a step whose budget is two: each row's
    output is what it is with no budget given (slots for every valid
    row, at most B*W), none comes back zero."""
    cfg, params = model
    mp = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["moe"])
    x = jnp.asarray(np.random.default_rng(6).standard_normal(
        (4, 1, cfg.d_model)), jnp.float32)
    q_lens = jnp.ones((4,), jnp.int32)
    y0, h0 = moe_mod.moe_routed(mp, x, q_lens, cfg)
    y1, h1 = moe_mod.moe_routed(mp, x, q_lens, cfg, 2)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    assert int(h0) == int(h1)
    assert np.asarray(y1).any(-1).all()


def test_routing_to_one_expert_drops_nothing(model):
    """A router that sends every row to the same top-k experts: every
    pair is computed (dropless), and `experts_hit` counts k experts."""
    cfg, params = model
    mp = dict(jax.tree_util.tree_map(lambda a: a[0],
                                     params["layers"]["moe"]))
    e, k = cfg.moe.num_experts, cfg.moe.top_k
    bias = np.zeros((cfg.d_model, e), np.float32)
    bias[:, :k] = np.arange(k, 0, -1)            # experts 0..k-1 win
    mp["router"] = jnp.asarray(bias)
    x = jnp.abs(jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, 8, cfg.d_model)), jnp.float32))
    q_lens = jnp.asarray([8, 8], jnp.int32)
    y, hits = moe_mod.moe_routed(mp, x, q_lens, cfg, 16)
    assert int(hits) == k
    P = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), mp)
    xs = np.asarray(x, np.float64).reshape(16, -1)
    logit = xs @ P["router"]
    prob = np.exp(logit - logit.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    want = _mlp(xs, P["shared"])
    for j in range(k):
        ex = {n: P["experts"][n][j] for n in ("gate", "up", "down")}
        want += prob[:, j:j + 1] * _mlp(xs, ex)
    np.testing.assert_allclose(np.asarray(y).reshape(16, -1), want,
                               rtol=1e-4, atol=1e-5)


# --------------------------------------------------------- lowrank_gmm --
def _expert_stack(rng, e, k, r, n):
    def qt(shape, axis):
        v = jnp.asarray(rng.integers(-7, 8, shape), jnp.int8)
        s = list(shape)
        s[-2 if axis == 0 else -1] = 1
        sc = jnp.asarray(rng.uniform(0.5, 1.5, s) / 40, jnp.float32)
        return pack_weights(QuantizedTensor(v, sc, 4, axis, False, 8))
    return LowRankQ(qt((e, k, r), 0), qt((e, r, n), 1))


@pytest.mark.parametrize("sizes", [
    [0, 0, 0, 0],            # nothing routed
    [1, 0, 0, 0],            # a single row
    [0, 1, 0, 1],            # single rows, empty experts between
    [250, 0, 3, 0],          # skewed: one expert spans two row tiles
    [60, 70, 80, 90],        # every boundary inside a tile
])
def test_lowrank_gmm_matches_oracle(sizes):
    """Interpret-mode kernel against the jnp oracle, bit for bit, and
    each expert's rows against `lowrank_qmm` on that expert alone."""
    rng = np.random.default_rng(sum(sizes))
    lr = _expert_stack(rng, 4, 256, 256, 512)
    x = jnp.asarray(rng.standard_normal((300, 256)), jnp.float32)
    off = jnp.asarray(np.concatenate([[0], np.cumsum(sizes)]), jnp.int32)
    n = int(off[-1])
    yk = ops.lrgmm(x, lr, off, use_kernel=True, interpret=True)
    yr = ops.lrgmm(x, lr, off, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(yk[:n]), np.asarray(yr[:n]))
    for e in range(4):
        lo, hi = int(off[e]), int(off[e + 1])
        if hi == lo:
            continue
        one = LowRankQ(*(QuantizedTensor(q.values[e], q.scale[e], q.wl,
                                         q.axis, q.packed, q.act_wl)
                         for q in (lr.w1, lr.w2)))
        ye = ops.lrmm(x[lo:hi], one, use_kernel=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(ye), np.asarray(yk[lo:hi]))


def test_lowrank_gmm_fetches_only_routed_experts():
    """The tile table names only experts with rows: an empty expert's
    factors are never a block the grid visits."""
    from repro.kernels.lowrank_gmm import tile_table

    off = jnp.asarray([0, 0, 130, 130, 131], jnp.int32)
    g, row, num = tile_table(off, 256, 128)
    g, row = np.asarray(g)[:int(num[0])], np.asarray(row)[:int(num[0])]
    assert g.tolist() == [1, 1, 3] and row.tolist() == [0, 1, 1]
    assert set(np.asarray(tile_table(off, 256, 128)[0]).tolist()) == {1, 3}


def _quant_stack(rng, e, k, n, packed):
    v = jnp.asarray(rng.integers(-7, 8, (e, k, n)), jnp.int8)
    sc = jnp.asarray(rng.uniform(0.5, 1.5, (e, 1, n)) / 40, jnp.float32)
    q = QuantizedTensor(v, sc, 4, 0, False, 8)
    return pack_weights(q) if packed else q


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("sizes", [
    [0, 0, 0, 0], [1, 0, 0, 0], [0, 1, 0, 1], [250, 0, 3, 0],
])
def test_quantized_grouped_linear_matches_qmm(sizes, packed):
    """`qgmm` (a quantized expert stack, as a `quant` plan makes it) on
    rows sorted by expert: each expert's rows what `qmm` gives on that
    expert alone, on the reference path and through the kernel
    (interpret mode); rows past the last group zero. The integer
    products are exact on both sides; rtol 1e-6 is the float32 rounding
    of the product of the two scales and the sum, which the compiler
    may group either way (2 ulp)."""
    rng = np.random.default_rng(sum(sizes) + packed)
    w = _quant_stack(rng, 4, 128, 256, packed)
    x = jnp.asarray(rng.standard_normal((300, 128)), jnp.float32)
    off = jnp.asarray(np.concatenate([[0], np.cumsum(sizes)]), jnp.int32)
    y = np.asarray(ops.qgmm(x, w, off))
    assert not y[int(off[-1]):].any()
    for e in range(4):
        lo, hi = int(off[e]), int(off[e + 1])
        if hi == lo:
            continue
        one = QuantizedTensor(w.values[e], w.scale[e], w.wl, w.axis,
                              w.packed, w.act_wl)
        for kernel in (False, True):
            ye = ops.qmm(x[lo:hi], one, use_kernel=kernel, interpret=True)
            np.testing.assert_allclose(np.asarray(ye), y[lo:hi],
                                       rtol=1e-6, atol=0)


@pytest.mark.parametrize("arch", ["deepseek-v2-lite", "deepseek-moe-16b"])
def test_quant_plan_serves_moe(arch):
    """A MoE model under a uniform `quant` W4 plan serves through the
    routed step (its experts as quantized stacks), and its greedy tokens
    do not depend on how the rows were batched: two rows under a budget
    of one token a step give the tokens of one row at a time."""
    from repro.api import CompressionPlan

    cfg = get_config(arch, smoke=True)
    params = tfm.init_params(jax.random.PRNGKey(1), cfg)
    plan = CompressionPlan.uniform(params, method="quant", weight_wl=4)
    eng = InferenceEngine.build(cfg, plan, params=params, max_batch=2,
                                block_size=4, chunk_tokens=1)
    assert isinstance(eng.params["layers"]["moe"]["experts"]["up"],
                      QuantizedTensor)
    rng = np.random.default_rng(4)
    reqs = [Request(tokens=rng.integers(1, cfg.vocab_size, n)
                    .astype(np.int32), max_tokens=4) for n in (5, 3)]
    both = eng.serve(reqs)
    for r, out in zip(reqs, both.outputs):
        alone = InferenceEngine(eng.cfg, eng.params, max_batch=1,
                                block_size=4, chunk_tokens=8).serve([r])
        np.testing.assert_array_equal(out, alone.outputs[0])


# ------------------------------------------------------ paged attention --
def _latent_case(rng, b, w, h, dp, bs, mb):
    nb_pool = b * mb + 1
    lat = jnp.asarray(rng.standard_normal((nb_pool, 1, bs, dp)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, w, h, dp)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, nb_pool)).reshape(
        b, mb), jnp.int32)
    return lat, q, tables


@pytest.mark.parametrize("budget", [None, 300_000])
def test_latent_mode_matches_gather_oracle(budget, monkeypatch):
    """Latent mode against the gather oracle on every row's valid
    positions: a long prefill row over several chunks, a decode row in a
    wide span, a row starting at 0, an idle row; with the VMEM budget
    cut so a span takes several query tiles."""
    if budget:
        monkeypatch.setattr(pa, "VMEM_BUDGET", budget)
    rng = np.random.default_rng(7)
    b, w, h, dp, bs, mb = 4, 16, 4, 128, 16, 20
    lat, q, tables = _latent_case(rng, b, w, h, dp, bs, mb)
    ctx = jnp.asarray([150, 290, 0, 40], jnp.int32)
    ql = jnp.asarray([16, 1, 9, 0], jnp.int32)
    p, wt = pa.latent_tile(w, h, dp, 96, bs, mb, itemsize=4)
    assert (wt < w) == bool(budget)
    o = pa.latent_paged_attention(q, lat, tables, ctx, ql, scale=0.2,
                                  v_width=96, interpret=True)
    pos = ctx[:, None] + jnp.arange(w)[None]
    ref = attn._mla_attend_gather(q, lat, tables, pos, 0.2, 96)
    for r in range(b):
        n = int(ql[r])
        np.testing.assert_allclose(np.asarray(o[r, :n]),
                                   np.asarray(ref[r, :n]), rtol=2e-5,
                                   atol=2e-5)


DENSE_DIGEST = ("1b276acb08bc9cacbcdf914db3566bba"
                "e39e8dd3430d5e19e4f10cad8d927270")


def test_dense_mode_outputs_unchanged():
    """The dense mode's outputs are bit for bit those of the kernel
    before the latent mode was added (digest recorded from it)."""
    import hashlib

    rng = np.random.default_rng(11)
    b, w, h, hk, dh, bs, mb = 3, 8, 4, 2, 64, 16, 12
    pool = {k: jnp.asarray(rng.standard_normal((b * mb + 1, hk, bs, dh)),
                           jnp.bfloat16) for k in ("k", "v")}
    q = jnp.asarray(rng.standard_normal((b, w, h, dh)), jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(np.arange(1, b * mb + 1)).reshape(
        b, mb), jnp.int32)
    o = pa.paged_attention(q, pool, tables, jnp.asarray([100, 3, 0]),
                           jnp.asarray([8, 1, 5]), interpret=True)
    got = hashlib.sha256(np.asarray(o.astype(jnp.float32)).tobytes())
    assert got.hexdigest() == DENSE_DIGEST


# ------------------------------------------------------------- tracing --
def test_experts_hit_rides_the_step_readback(model, tmp_path):
    """`experts_hit` on `repro.serve.step` while a profiler session
    records: the sum over step spans equals the sum the steps computed,
    outputs are those of an untraced call, and no step program is added
    for it."""
    from test_serve_trace import _host_spans

    cfg, params = model
    eng = InferenceEngine.build(cfg, None, params=params, max_batch=2,
                                block_size=4, chunk_tokens=8)
    rng = np.random.default_rng(2)
    reqs = [Request(tokens=rng.integers(1, cfg.vocab_size, n)
                    .astype(np.int32), max_tokens=g)
            for n, g in ((6, 3), (9, 4), (2, 2))]
    plain = eng.serve(reqs)
    (key, fn), = eng._unified_cache.items()
    seen = []

    def keep(*a):
        out = fn(*a)
        seen.append(out[0])
        return out

    eng._unified_cache[key] = keep
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = eng.serve(reqs)
    finally:
        jax.profiler.stop_trace()
    assert not tracing.active()
    assert list(eng._unified_cache) == [key]
    for a, b in zip(plain.outputs, res.outputs):
        np.testing.assert_array_equal(a, b)
    steps = [s for s in _host_spans(str(tmp_path))
             if s[1] == "repro.serve.step"]
    assert len(steps) == len(seen) == res.steps
    per_step = [int(np.asarray(t)[0, 1]) for t in seen]
    moe_layers = cfg.num_layers - cfg.first_dense
    assert all(0 < n <= cfg.moe.num_experts * moe_layers for n in per_step)
    assert sum(s[4]["experts_hit"] for s in steps) == sum(per_step)


def test_latent_pool_is_smaller_than_per_head_kv():
    cfg = get_config("deepseek-v2-lite")
    lat = jax.eval_shape(lambda: kvblocks.init_paged_cache(cfg, 3, 16))
    assert lat["layers"]["latent"].shape == (26, 3, 1, 16, 640)
    assert lat["dense_layers"]["latent"].shape == (1, 3, 1, 16, 640)
    with pytest.raises(NotImplementedError):
        tfm.forward(None, jnp.zeros((1, 2), jnp.int32), cfg)


def test_itera_plan_covers_expert_stacks(model):
    """A uniform ITERA-W4 plan compresses every expert stack with its
    leading (layer, expert) axes and leaves the router (float32) and the
    absorbed kv_b_proj halves plain; served through the grouped kernel
    (interpret mode) the tokens equal the jnp oracle path's."""
    from repro.api import CompressionPlan
    from repro.models.layers import get_linear_mode, set_linear_mode

    cfg, params = model
    plan = CompressionPlan.uniform(params, method="itera", weight_wl=4,
                                   rank_fraction=0.5)
    paths = {lp.path for lp in plan}
    assert {"layers/moe/experts/up", "layers/moe/experts/down",
            "dense_layers/mlp/up", "layers/attn/wkv_a"} <= paths
    assert not [p for p in paths if "router" in p or "w_u" in p]
    eng = InferenceEngine.build(cfg, plan, params=params, max_batch=2,
                                block_size=4, chunk_tokens=8)
    moe = eng.params["layers"]["moe"]
    assert isinstance(moe["experts"]["up"], LowRankQ)
    assert moe["experts"]["up"].w1.shape[:2] == (2, 8)
    assert moe["router"].dtype == jnp.float32
    rng = np.random.default_rng(9)
    reqs = [Request(tokens=rng.integers(1, cfg.vocab_size, n)
                    .astype(np.int32), max_tokens=3) for n in (7, 4)]
    ref = eng.serve(reqs)
    mode = get_linear_mode()
    set_linear_mode("kernel")
    try:
        got = InferenceEngine(eng.cfg, eng.params, max_batch=2, block_size=4,
                              chunk_tokens=8).serve(reqs)
    finally:
        set_linear_mode(mode)
    for a, b in zip(ref.outputs, got.outputs):
        np.testing.assert_array_equal(a, b)

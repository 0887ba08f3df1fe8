"""DeepSeek-V2 family (arXiv:2405.04434): multi-head latent attention
with YaRN rotary scaling, the first `moe.first_dense` layers a dense
SwiGLU MLP, every later layer a softmax router over routed SwiGLU
experts (greedy top-k, weights renormalized only under
`norm_topk_prob`, times `routed_scaling_factor`) plus shared experts
that act as one MLP. The program's `models/transformer.py` with
`kv_lora_rank > 0` and `layout="moe"`.

Linears (q_proj, kv_a_proj_with_mqa, o_proj, the dense and shared MLPs,
every expert, the output head) take the plan's method; kv_b_proj stays
plain bfloat16 as per-head W_UK (H, dn, C) and W_UV (H, C, dv), and the
router plain float32.

The reference is the paper's non-absorbed form: each head's key is
[c @ W_UK_h^T, rope(k_pe)] and its value c @ W_UV_h, decompressed from
the normed latent c, at "highest" in float32, one sequence at a time
with queries in blocks so that long sequences fit. Its experts are each
computed on the rows routed to them only: the (row, expert) pairs are
gathered and sorted by expert and each expert's rows go through its own
factors by a grouped product, with the row quantizations and exact
integer products of `methods/itera.py`'s `linear` (expert sites take an
ITERA plan).
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

import methods
import reference
import synth
from work import ITEMSIZE, linears

Q_BLOCK = 512        # reference query rows per attention block


def program_config(config: dict):
    from repro.configs.base import ModelConfig, MoEConfig, RopeScaling

    m = dict(config["model"])
    m["moe"] = MoEConfig(**m["moe"])
    m["rope_scaling"] = RopeScaling(**m["rope_scaling"])
    return ModelConfig(name=config["name"], layout="moe", **m)


def _dims(cfg: dict):
    return (cfg["num_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["kv_lora_rank"], cfg["v_head_dim"])


# ------------------------------------------------------------ weights --
def sites(cfg: dict) -> dict:
    """{site path: (K, N, group)} of every linear under the plan; group
    "all" (every layer), "dense" (the leading dense layers), "moe" (the
    MoE layers), "expert" (MoE layers x experts) or "head"."""
    d, f = cfg["d_model"], cfg["d_ff"]
    h, dn, dr, c, dv = _dims(cfg)
    m = cfg["moe"]
    fe = m["d_expert"]
    fs = fe * m["num_shared"]
    return {"attn/wq": (d, h * (dn + dr), "all"),
            "attn/wkv_a": (d, c + dr, "all"),
            "attn/wo": (h * dv, d, "all"),
            "mlp/gate": (d, f, "dense"), "mlp/up": (d, f, "dense"),
            "mlp/down": (f, d, "dense"),
            "moe/shared/gate": (d, fs, "moe"), "moe/shared/up": (d, fs, "moe"),
            "moe/shared/down": (fs, d, "moe"),
            "moe/experts/gate": (d, fe, "expert"),
            "moe/experts/up": (d, fe, "expert"),
            "moe/experts/down": (fe, d, "expert"),
            "lm_head": (d, cfg["vocab_size"], "head")}


def site_shapes(cfg: dict, site: str) -> dict:
    k, n, _ = sites(cfg)[site]
    return synth.shapes(cfg["plan"], k, n)


def _layers(cfg: dict, group: str) -> list:
    """Global layer indices of a group's layers."""
    fd, L = cfg["moe"]["first_dense"], cfg["num_layers"]
    return {"all": list(range(L)), "dense": list(range(fd)),
            "moe": list(range(fd, L))}[group]


def _expert_index(cfg: dict, layer, e):
    """The layer index an expert's arrays are drawn under: each (MoE
    layer, expert) pair its own."""
    return (layer - cfg["moe"]["first_dense"]) * cfg["moe"]["num_experts"] + e


def _plain(key, cfg: dict, layer):
    """kv_b_proj's halves (bfloat16) and the router (float32) of a layer."""
    h, dn, _, c, dv = _dims(cfg)
    k = jax.random.fold_in(jax.random.fold_in(key, 0x6b76), layer)
    ka, kb, kr = jax.random.split(k, 3)
    dt = jnp.dtype(cfg["dtype"])
    out = {"w_uk": (jax.random.normal(ka, (h, dn, c), jnp.float32)
                    * c ** -0.5).astype(dt),
           "w_uv": (jax.random.normal(kb, (h, c, dv), jnp.float32)
                    * c ** -0.5).astype(dt)}
    e = cfg["moe"]["num_experts"]
    out["router"] = jax.random.normal(kr, (cfg["d_model"], e),
                                      jnp.float32) * cfg["d_model"] ** -0.5
    return out


def _layer_arrays(key, cfg: dict, layer, kind: str) -> dict:
    """One layer's arrays: its sites as `synth.site_arrays` draws them
    (experts with a leading expert axis), the plain arrays, and, for
    the dense kind, no MoE sites."""
    out = {}
    for site, (_, _, group) in sites(cfg).items():
        shp = site_shapes(cfg, site)
        if group in ("all", kind):
            out[site] = synth.site_arrays(key, shp, site, layer)
        elif group == "expert" and kind == "moe":
            out[site] = jax.lax.map(
                lambda e, s=site, p=shp: synth.site_arrays(
                    key, p, s, _expert_index(cfg, layer, e)),
                jnp.arange(cfg["moe"]["num_experts"]))
    out.update(_plain(key, cfg, layer))
    return out


def _kv_norm(cfg: dict, lead=()):
    return synth.norm_params({**cfg, "d_model": cfg["kv_lora_rank"]}, lead)


def _group_raw(key, cfg: dict, kind: str) -> dict:
    layers = jnp.asarray(_layers(cfg, kind))
    n = layers.shape[0]
    raw = jax.lax.map(lambda l: _layer_arrays(key, cfg, l, kind), layers)
    raw["ln1"] = synth.norm_params(cfg, (n,))
    raw["ln2"] = synth.norm_params(cfg, (n,))
    raw["kv_norm"] = _kv_norm(cfg, (n,))
    return raw


@functools.partial(jax.jit, static_argnums=(1,))
def _make_raw_jit(key, cfg_json: str):
    cfg = json.loads(cfg_json)
    out = {"embed": synth.embed_table(key, cfg),
           "final_norm": synth.norm_params(cfg),
           "lm_head": synth.site_arrays(key, site_shapes(cfg, "lm_head"),
                                        "lm_head", 0),
           "layers": _group_raw(key, cfg, "moe")}
    if cfg["moe"]["first_dense"]:
        out["dense_layers"] = _group_raw(key, cfg, "dense")
    return out


def program_tree(seed: int, cfg: dict):
    """The served parameter tree, made on the device in one jitted call,
    in the layout the program's transformer reads."""
    raw = _make_raw_jit(synth.seed_key(seed), json.dumps(cfg, sort_keys=True))
    node = lambda r, s: synth.node(r[s], site_shapes(cfg, s),  # noqa: E731
                                   cfg["plan"])

    def group(r, kind):
        a = {"wq": node(r, "attn/wq"), "wkv_a": node(r, "attn/wkv_a"),
             "kv_norm": r["kv_norm"], "w_uk": r["w_uk"], "w_uv": r["w_uv"],
             "wo": node(r, "attn/wo")}
        out = {"ln1": r["ln1"], "attn": a, "ln2": r["ln2"]}
        if kind == "dense":
            out["mlp"] = {k: node(r, f"mlp/{k}") for k in ("gate", "up",
                                                           "down")}
        else:
            out["moe"] = {
                "router": r["router"],
                "shared": {k: node(r, f"moe/shared/{k}")
                           for k in ("gate", "up", "down")},
                "experts": {k: node(r, f"moe/experts/{k}")
                            for k in ("gate", "up", "down")}}
        return out

    tree = {"embed": raw["embed"], "final_norm": raw["final_norm"],
            "lm_head": node(raw, "lm_head"),
            "layers": group(raw["layers"], "moe")}
    if "dense_layers" in raw:
        tree["dense_layers"] = group(raw["dense_layers"], "dense")
    return tree


# ---------------------------------------------------------- reference --
def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """DeepSeek-V2's YaRN rotary frequencies of the rope dims (its
    `DeepseekV2YarnRotaryEmbedding`), float64."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    s = cfg["rope_scaling"]

    def corr(rot):
        return (dim * math.log(s["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    lo = max(math.floor(corr(s["beta_fast"])), 0)
    hi = min(math.ceil(corr(s["beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    mask = 1.0 - np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
    return extra / s["factor"] * (1 - mask) + extra * mask


def softmax_scale(cfg: dict) -> float:
    _, dn, dr, _, _ = _dims(cfg)
    s = cfg["rope_scaling"]
    m = 0.1 * s["mscale_all_dim"] * math.log(s["factor"]) + 1.0
    return (dn + dr) ** -0.5 * m * m


def _rope(x, pos, inv):
    """x (S, ..., 2m): rotates the interleaved pairs (2i, 2i+1) by
    pos * inv[i], DeepSeek's de-interleave-then-rotate-half up to the
    order of dims within the dot product."""
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), -1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attention(cfg, w, lin, rnd, x):
    """Non-absorbed latent attention over one sequence x (S, d)."""
    S = x.shape[0]
    h, dn, dr, c, dv = _dims(cfg)
    pos = jnp.arange(S)
    inv = jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
    q = lin(x, "attn/wq").reshape(S, h, dn + dr)
    kv = lin(x, "attn/wkv_a")
    lat = rnd(reference.norm(kv[:, :c], w["kv_norm"],
                             {**cfg, "d_model": c}))
    k_pe = rnd(_rope(kv[:, c:], pos, inv))                   # (S, dr)
    q_pe = rnd(_rope(q[..., dn:], pos, inv))                 # (S, H, dr)
    k_nope = rnd(jnp.einsum("sc,hnc->shn", lat,
                            w["w_uk"].astype(jnp.float32),
                            precision=reference.HI))
    v = rnd(jnp.einsum("sc,hcv->shv", lat, w["w_uv"].astype(jnp.float32),
                       precision=reference.HI))
    scale = softmax_scale(cfg)
    nq = -(-S // Q_BLOCK)

    def block(i):
        qp = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        qi = jnp.minimum(qp, S - 1)
        s = (jnp.einsum("qhn,khn->hqk", q[qi, :, :dn], k_nope,
                        precision=reference.HI)
             + jnp.einsum("qhr,kr->hqk", q_pe[qi], k_pe,
                          precision=reference.HI)) * scale
        s = jnp.where(qp[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        p = rnd(jax.nn.softmax(s, axis=-1))
        return rnd(jnp.einsum("hqk,khv->qhv", p, v, precision=reference.HI))

    o = jax.lax.map(block, jnp.arange(nq)).reshape(nq * Q_BLOCK, h * dv)[:S]
    return lin(o, "attn/wo")


def _expert_linear(x, arrays, shp, sizes, act_wl):
    """`methods/itera.py`'s linear on rows sorted by expert, each row
    through its own expert's factors (a grouped integer product);
    `sizes` (E,) rows per expert."""
    v1, s1, v2, s2 = arrays
    e = sizes.shape[0]
    rows = jnp.repeat(jnp.arange(e), sizes, total_repeat_length=x.shape[0])
    qm = 2 ** (act_wl - 1) - 1
    xq, sx = reference.rowquant(x, qm)

    def gdot(a, codes):
        # integer codes in bf16 with f32 accumulation are exact at any
        # precision; pinned, since the grouped product is a Pallas kernel
        # on the TPU and refuses the "highest" the reference runs under
        return jax.lax.ragged_dot(a.astype(jnp.bfloat16),
                                  codes.astype(jnp.bfloat16), sizes,
                                  precision=jax.lax.Precision.DEFAULT,
                                  preferred_element_type=jnp.float32)

    t = gdot(xq, reference.codes(v1, shp["w1_packed"])) \
        * sx * s1.reshape(e, -1)[rows] * s2.reshape(e, -1)[rows]
    tq, st = reference.rowquant(t, qm)
    return gdot(tq, reference.codes(v2, shp["w2_packed"])) * st


def _moe(cfg, w, lin, rnd, x):
    """(expert output (S, d), top-k picks (S, k)) of one sequence."""
    m = cfg["moe"]
    S = x.shape[0]
    e, k = m["num_experts"], m["top_k"]
    logits = jnp.dot(x, w["router"], precision=reference.HI)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, pick = jax.lax.top_k(probs, k)
    if m["norm_topk_prob"]:
        gate = gate / gate.sum(-1, keepdims=True)
    gate = gate * m["routed_scaling_factor"]
    flat = pick.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((e,), jnp.int32).at[flat].add(1)
    tok = order // k
    xs = x[tok]                                  # the routed rows, gathered
    act = cfg["plan"]["act_wl"]
    ex = lambda a, s: rnd(_expert_linear(                   # noqa: E731
        a, w[s], site_shapes(cfg, s), sizes, act))
    hmid = rnd(jax.nn.silu(ex(xs, "moe/experts/gate"))
               * ex(xs, "moe/experts/up"))
    ys = ex(hmid, "moe/experts/down") * gate.reshape(-1)[order][:, None]
    y = jnp.zeros_like(x).at[tok].add(ys)
    sh = rnd(jax.nn.silu(lin(x, "moe/shared/gate"))
             * lin(x, "moe/shared/up"))
    return rnd(y + lin(sh, "moe/shared/down")), pick


def _block(cfg, w, kind, rnd, h):
    """One layer over one whole sequence h (S, d) f32 -> (h, picks)."""
    lin = lambda x, s: rnd(reference.linear(                   # noqa: E731
        x, w[s], site_shapes(cfg, s), cfg["plan"]["act_wl"]))
    x = rnd(reference.norm(h, w["ln1"], cfg))
    h = rnd(h + _attention(cfg, w, lin, rnd, x))
    x = rnd(reference.norm(h, w["ln2"], cfg))
    if kind == "dense":
        m = rnd(jax.nn.silu(lin(x, "mlp/gate")) * lin(x, "mlp/up"))
        return rnd(h + lin(m, "mlp/down")), None
    y, pick = _moe(cfg, w, lin, rnd, x)
    return rnd(h + y), pick


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(cfg_json, lower, kind, key, layer, hs):
    """One layer over every sequence, one at a time: hs (n, S, d) f32 ->
    (hs, picks (n, S, k) or None)."""
    cfg = json.loads(cfg_json)
    w = _layer_arrays(key, cfg, layer, kind)
    w["ln1"], w["ln2"] = synth.norm_params(cfg), synth.norm_params(cfg)
    w["kv_norm"] = _kv_norm(cfg)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda h: _block(cfg, w, kind,
                                            reference.rounder(lower), h), hs)


def _run(seed: int, cfg: dict, seqs, lower=None, picks=None) -> list:
    """`reference.hidden_states` with this family's layers; where
    `picks` is a list, each MoE layer's top-k picks (n, S, k) are
    appended to it."""
    fd = cfg["moe"]["first_dense"]

    def layer_fn(cj, lo, key, layer, hs):
        kind = "dense" if int(layer) < fd else "moe"
        hs, pick = _layer(cj, lo, kind, key, layer, hs)
        if picks is not None and pick is not None:
            picks.append(np.asarray(pick))
        return hs

    return reference.hidden_states(layer_fn, seed, cfg, seqs, lower)


def hidden_states(seed: int, cfg: dict, seqs, lower=None) -> list:
    return _run(seed, cfg, seqs, lower)


def logits(seed: int, cfg: dict, h):
    return reference.logits(seed, cfg, h, "lm_head",
                            site_shapes(cfg, "lm_head"))


# --------------------------------------------------------------- work --
def attention(cfg: dict, tok) -> dict:
    """Float operations and HBM bytes of `paged_attention`'s latent mode
    over the window: every head scores each visible position against
    its (C + dr)-wide entry and sums C-wide values (16 x (576 + 512)
    multiply-adds a position at DeepSeek-V2-Lite's widths); each entry
    read once per decode row, each prompt's entries at least once;
    queries read and outputs written once."""
    h, _, dr, c, _ = _dims(cfg)
    b = ITEMSIZE[cfg["dtype"]]
    visible = sum(p * (p + 1) // 2 for p in tok.prompt_lens) + sum(
        q + 1 for q in tok.decode_positions)
    read = sum(tok.prompt_lens) + sum(q + 1 for q in tok.decode_positions)
    L = cfg["num_layers"]
    ops = L * 2 * h * (c + dr + c) * visible
    byt = L * ((c + dr) * b * read + h * (c + dr + c) * b * tok.rows)
    return {"ops": ops, "bytes": byt}


ROUTE_WIDTH = 2048    # the routing pass pads sequences to a multiple
_ROUTED: dict = {}   # the last window's routing: (seed, cfg) -> (tok, hit)


def routing(seed: int, cfg: dict, tok) -> dict:
    """{(step, MoE layer): set of experts} the reference router sends at
    least one of the window's rows to, run over each in-window request's
    token ids up to the last position the window processed (the later
    ones change no pick), in groups padded to one multiple of
    ROUTE_WIDTH each, so that few programs compile and short requests
    are not padded to the longest. Every roofline and `step_mfu` of a
    run read the same window: it is routed once and kept."""
    key = (seed, json.dumps(cfg, sort_keys=True))
    kept = _ROUTED.get(key)
    if kept is not None and kept[0] is tok:
        return kept[1]
    hit: dict = {}
    need = [max(r.positions) + 1 for r in tok.requests]
    groups: dict = {}
    for i, n in enumerate(need):
        groups.setdefault(-(-n // ROUTE_WIDTH) * ROUTE_WIDTH, []).append(i)
    for w, idx in sorted(groups.items()):
        picks: list = []
        _run(seed, cfg, [np.pad(tok.requests[i].ids[:need[i]],
                                (0, w - need[i])) for i in idx],
             picks=picks)
        for layer, p in enumerate(picks):
            for j, i in enumerate(idx):
                r = tok.requests[i]
                rows = p[j][np.asarray(r.positions)]
                for step, experts in zip(r.steps, rows):
                    hit.setdefault((step, layer), set()).update(
                        int(e) for e in experts)
    _ROUTED.clear()
    _ROUTED[key] = (tok, hit)
    return hit


def work(cfg: dict, tok, steps: int, seed: int) -> dict:
    """`lowrank_qmm` for the attention projections of every layer, the
    dense MLP, the shared experts (each valid row) and the output head
    (each served token); `lowrank_gmm` for the routed experts: each
    step reads the factors of every expert the reference router sends
    one of that step's rows to, in every MoE layer, and each row runs
    top_k experts; `paged_attention` in its latent mode. A prompt's
    chunks all count in the step of its last chunk, so expert reads read
    low. The rows come from `tok`, which counts a prompt whole in the
    window where it was read back, chunks run before the window
    included: the terms that grow with rows (operations, activation
    bytes, attention positions) can read high by those rows; the
    weight reads do not."""
    fd, L = cfg["moe"]["first_dense"], cfg["num_layers"]
    n = {"all": L, "dense": fd, "moe": L - fd}
    out = linears(cfg, [
        (site_shapes(cfg, s), 1 if g == "head" else n[g],
         tok.emitted if g == "head" else tok.rows)
        for s, (_, _, g) in sites(cfg).items() if g != "expert"], steps)
    ex = [site_shapes(cfg, f"moe/experts/{k}") for k in ("gate", "up",
                                                         "down")]
    meth = methods.load(ex[0]["method"])
    reads = sum(len(v) for v in routing(seed, cfg, tok).values())
    pairs = (L - fd) * tok.rows * cfg["moe"]["top_k"]
    out_b = ITEMSIZE[cfg["dtype"]]
    out["lowrank_gmm"] = {
        "ops": sum(2 * pairs * meth.row_macs(s) for s in ex),
        "bytes": sum(reads * meth.weight_bytes(s)
                     + pairs * (s["k"] + 4 + s["n"] * out_b) for s in ex),
        "peak": "int8_ops"}
    out["paged_attention"] = {**attention(cfg, tok), "peak": "bf16_flops"}
    return out

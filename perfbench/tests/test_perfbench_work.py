"""The work-count functions against counts made by hand at smoke size."""
import pytest

import work
from families import dense

CFG = dict(num_layers=2, d_model=256, num_heads=4, num_kv_heads=2,
           head_dim=64, d_ff=512, vocab_size=512, mlp_act="swiglu",
           norm="rmsnorm", dtype="bfloat16",
           plan=dict(method="itera", weight_wl=4, act_wl=8,
                     rank_fraction=0.5, pack=True))
QUANT = {**CFG, "vocab_size": 500,
         "plan": dict(method="quant", weight_wl=4, act_wl=8, pack=True)}
# one request: prompt 3, two served tokens -> rows: 3 prompt + 1 decode
TOK = work.Tokens(prompt_lens=[3], decode_positions=[3], emitted=2)


def test_rows():
    assert TOK.rows == 4


def test_lowrank_by_hand():
    # ranks (0.5 of min(K, N), floored to 64 once min >= 256):
    # wq 256->256 r128; wk, wv 256->128 r64; wo 256->256 r128;
    # gate, up 256->512 r128; down 512->256 r128; lm_head 256->512 r128
    stacked = [(256, 128, 256), (256, 64, 128), (256, 64, 128),
               (256, 128, 256), (256, 128, 512), (256, 128, 512),
               (512, 128, 256)]
    ops = (2 * sum(2 * 4 * r * (k + n) for k, r, n in stacked)
           + 2 * 2 * 128 * (256 + 512))
    got = dense.work(CFG, TOK, steps=3, seed=0)["lowrank_qmm"]
    assert got["ops"] == ops and got["peak"] == "int8_ops"

    def wbytes(k, r, n):        # W1 packs along r, W2 along n, at 256
        w1 = k * r // 2 if r % 256 == 0 else k * r
        w2 = r * n // 2 if n % 256 == 0 else r * n
        return w1 + w2 + 8 * r

    per_step = 2 * sum(wbytes(*s) for s in stacked) + wbytes(256, 128, 512)
    acts = 2 * sum(4 * (k + 4 + 2 * n) for k, _, n in stacked) \
        + 2 * (256 + 4 + 2 * 512)
    assert got["bytes"] == 3 * per_step + acts


def test_attention_by_hand():
    # prompt positions 0, 1, 2 see 1, 2, 3 keys; the decode row at
    # position 3 sees 4: 10 (query, key) pairs, 2 flops each for QK and PV
    # per head dim, 4 heads, 2 layers
    got = dense.work(CFG, TOK, steps=3, seed=0)["paged_attention"]
    assert got["peak"] == "bf16_flops"
    assert got["ops"] == 2 * 4 * 4 * 64 * 10
    # K and V read: 3 prompt positions once + 4 for the decode row
    kv = 2 * 2 * 64 * 2 * (3 + 4)
    qo = 2 * 4 * 64 * 2 * 4
    assert got["bytes"] == 2 * (kv + qo)


def test_quant_matmul_by_hand():
    # every site one (K, N) matrix: wq, wo 256x256; wk, wv 256x128;
    # gate, up 256x512; down 512x256; lm_head 256x500
    stacked = [(256, 256), (256, 128), (256, 128), (256, 256), (256, 512),
               (256, 512), (512, 256)]
    got = dense.work(QUANT, TOK, steps=3, seed=0)
    assert list(got) == ["quant_matmul", "paged_attention"]
    q = got["quant_matmul"]
    assert q["peak"] == "int8_ops"
    assert q["ops"] == (2 * sum(2 * 4 * k * n for k, n in stacked)
                        + 2 * 2 * 256 * 500)

    def wbytes(k, n):           # codes pack along N at 256, f32 scale per N
        return (k * n // 2 if n % 256 == 0 else k * n) + 4 * n

    per_step = 2 * sum(wbytes(*s) for s in stacked) + wbytes(256, 500)
    acts = 2 * sum(4 * (k + 4 + 2 * n) for k, n in stacked) \
        + 2 * (256 + 4 + 2 * 500)
    assert q["bytes"] == 3 * per_step + acts
    # attention does not depend on the plan
    assert got["paged_attention"] == dense.work(
        CFG, TOK, steps=3, seed=0)["paged_attention"]


def test_least_time_takes_the_binding_bound():
    assert work.least_seconds(10.0, 1.0, 10.0, 10.0) == pytest.approx(1.0)
    assert work.least_seconds(1.0, 10.0, 10.0, 10.0) == pytest.approx(1.0)
    assert work.least_seconds(1.0, 1.0, 10.0, 100.0) == pytest.approx(0.1)

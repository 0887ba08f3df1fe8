"""Kernel microbenchmarks: Pallas kernels (compiled on a TPU; on the CPU
they run in interpret mode, and that wall time is NOT TPU latency — rows
then carry `interp` in their names and the JSON `backend` says "cpu")
plus the analytical
TPU latencies the DSE actually uses (modeled compute/memory terms), plus —
the number the packed-residency work is about — the modeled HBM bytes each
launch moves (`hbm_mb`): W4 packed streams half the weight bytes of the
W8/carrier path, so the bandwidth win is measured per case, not asserted.

Besides the csv rows on stdout, writes a machine-readable summary to
BENCH_kernels.json (path override: --out / $BENCH_KERNELS_OUT) that
`tools/perf_compare.py --kernels` diffs across runs.
"""
import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from common import csv_row, timed
from repro.configs.base import ModelConfig
from repro.core.itera import LowRankQ, svd_decompose
from repro.core.quant import pack_weights, quantize
from repro.hw import tpu_model as tm
from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.models import attention as mattn


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out",
                    default=os.environ.get("BENCH_KERNELS_OUT",
                                           "BENCH_kernels.json"))
    args = ap.parse_args(argv)

    rows = []

    def record(name, us_per_call, derived="", hbm_mb=None):
        csv_row(name, us_per_call,
                derived + (f";hbm_mb={hbm_mb:.3f}" if hbm_mb is not None
                           else ""))
        row = {"name": name, "us_per_call": round(us_per_call, 3),
               "derived": derived}
        if hbm_mb is not None:
            row["hbm_mb"] = round(hbm_mb, 3)
        rows.append(row)

    mode = "tpu" if ops.on_tpu() else "interp"
    interpret = not ops.on_tpu()
    key = jax.random.PRNGKey(0)
    cases = [
        ("paper512", 512, 512, 512, 128),
        ("ffn_like", 256, 1024, 4096, 256),
        ("decode_like", 8, 4096, 4096, 512),
    ]
    for name, m, k, n, r in cases:
        x = jax.random.normal(key, (m, k), jnp.float32)
        w = jax.random.normal(key, (k, n), jnp.float32) / np.sqrt(k)
        wq8 = quantize(w, 8, axis=0)
        wq4 = pack_weights(quantize(w, 4, axis=0))
        lr8 = svd_decompose(w, r, 8)
        lr4f = svd_decompose(w, r, 4)
        lr4 = LowRankQ(pack_weights(lr4f.w1), pack_weights(lr4f.w2))

        qmm_mb = {}
        for wl, wq in ((8, wq8), (4, wq4)):
            tag = f"W{wl}" + ("_packed" if wq.packed else "")
            dt, _ = timed(lambda: ops.qmm(x, wq, use_kernel=True,
                                          interpret=interpret), iters=1)
            qmm_mb[wl] = ops.qmm_hbm_bytes(m, wq) / 2**20
            record(f"kernel_qmm_{mode}_{tag}_{name}", dt * 1e6,
                   f"M={m};K={k};N={n}", hbm_mb=qmm_mb[wl])
        lrmm_mb = {}
        for wl, lr in ((8, lr8), (4, lr4)):
            # a factor whose pack axis would pad-inflate stays carrier
            # (core.quant.packable); the row is "packed" if any factor is
            tag = f"W{wl}" + ("_packed" if (lr.w1.packed or lr.w2.packed)
                              else "")
            dt, _ = timed(lambda: ops.lrmm(x, lr, use_kernel=True,
                                           interpret=interpret), iters=1)
            lrmm_mb[wl] = ops.lrmm_hbm_bytes(m, lr) / 2**20
            record(f"kernel_lrmm_{mode}_{tag}_{name}", dt * 1e6,
                   f"M={m};K={k};N={n};R={r}", hbm_mb=lrmm_mb[wl])
        # packing must never lose to its own carrier: the W4 launch (with
        # ops.packed_pad_ok demoting pad-inflating axes) streams at most
        # the W8 bytes. Tracked here so a choose_blocks / padding change
        # that reintroduces the old lrmm paper512 regression (packed
        # rp->256 padding costing more than the nibble halving saved)
        # fails the bench, not just a note in a JSON diff.
        assert qmm_mb[4] <= qmm_mb[8] + 1e-9, (name, qmm_mb)
        assert lrmm_mb[4] <= lrmm_mb[8] + 1e-9, (name, lrmm_mb)
        dt, _ = timed(lambda: ops.qmm(x, wq8, use_kernel=False), iters=3)
        record(f"kernel_qmm_ref_{name}", dt * 1e6, "jnp-reference")

        # modeled TPU latencies (what the roofline/DSE uses); weight_wl=4
        # is now a *deliverable* bandwidth model — packed W4 really moves
        # wl/8 bytes — not an aspiration
        for wl in (8, 4):
            bp = tm.best_point(m, k, n, None, weight_wl=wl)
            cp = tm.best_point(m, k, n, r, weight_wl=wl,
                               engines=("cascade",))
            record(f"kernel_qmm_tpu_model_W{wl}_{name}", bp.latency_s * 1e6,
                   f"bound={'compute' if bp.compute_s >= bp.memory_s else 'memory'}")
            record(f"kernel_lrmm_tpu_model_W{wl}_{name}", cp.latency_s * 1e6,
                   f"bound={'compute' if cp.compute_s >= cp.memory_s else 'memory'};"
                   f"speedup_vs_dense={bp.latency_s / cp.latency_s:.2f}x")

    # ---- paged serving attention: streamed kernel vs jnp gather oracle ----
    # Same mixed span batch (chunk + decode + idle rows, GQA) against the
    # same blocked KV pool; short vs long context shows the point of the
    # kernel — its bytes scale with ctx_lens while the gather path reads
    # the full MB*bs logical view either way.
    B, W, hk, g, dh, bs, mb = 4, 8, 4, 2, 64, 16, 16
    h = hk * g
    cfg_attn = ModelConfig(name="bench-attn", d_model=h * dh, num_heads=h,
                           num_kv_heads=hk, head_dim=dh, dtype="bfloat16")
    q_lens = [8, 1, 0, 8]                       # chunk, decode, idle, chunk
    ctx_cases = {"short": [40, 17, 0, 9], "long": [216, 230, 0, 188]}
    for cname, ctx in ctx_cases.items():
        ctx_a = jnp.asarray(ctx, jnp.int32)
        ql_a = jnp.asarray(q_lens, jnp.int32)
        bt = np.zeros((B, mb), np.int32)
        nxt = 1                                 # block 0 = reserved trash
        for r in range(B):
            need = -(-(ctx[r] + q_lens[r]) // bs)
            bt[r, :need] = np.arange(nxt, nxt + need)
            nxt += need
        bt_a = jnp.asarray(bt)
        nb_pool = B * mb + 1
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(ks[0], (B, W, h, dh), jnp.bfloat16)
        for kv_bits, tag in ((16, "bf16kv"), (8, "int8kv")):
            if kv_bits == 8:
                pool_l = {
                    "k": jax.random.randint(ks[1], (nb_pool, hk, bs, dh),
                                            -127, 128).astype(jnp.int8),
                    "v": jax.random.randint(ks[2], (nb_pool, hk, bs, dh),
                                            -127, 128).astype(jnp.int8),
                    "ks": jnp.ones((nb_pool, hk, bs, 1), jnp.float32) * 0.02,
                    "vs": jnp.ones((nb_pool, hk, bs, 1), jnp.float32) * 0.02,
                }
            else:
                pool_l = {
                    "k": jax.random.normal(ks[1], (nb_pool, hk, bs, dh),
                                           jnp.bfloat16),
                    "v": jax.random.normal(ks[2], (nb_pool, hk, bs, dh),
                                           jnp.bfloat16),
                }
            geom = f"B={B};W={W};Hk={hk};G={g};Dh={dh};bs={bs};MB={mb}"
            stream_mb = pa.stream_hbm_bytes(ctx, q_lens, bs, hk, dh,
                                            kv_bits=kv_bits,
                                            n_q_heads=h) / 2**20
            gather_mb = pa.gather_hbm_bytes(B, mb, bs, hk, dh,
                                            kv_bits=kv_bits, w=W,
                                            n_q_heads=h) / 2**20
            dt, _ = timed(lambda: pa.paged_attention(
                q, pool_l, bt_a, ctx_a, ql_a, interpret=interpret), iters=1)
            record(f"kernel_pattn_{mode}_{tag}_{cname}_ctx", dt * 1e6,
                   geom, hbm_mb=stream_mb)
            pos = ctx_a[:, None] + jnp.arange(W)[None, :]
            dt, _ = timed(lambda: mattn._span_attend_gather(
                q, pool_l, bt_a, pos, cfg_attn), iters=1)
            record(f"kernel_pattn_gather_{tag}_{cname}_ctx", dt * 1e6,
                   "jnp-gather-oracle", hbm_mb=gather_mb)
            # the acceptance bar: streamed bytes scale with ctx and stay
            # strictly below the gather whenever ctx < pool capacity
            assert stream_mb < gather_mb, (cname, tag, stream_mb, gather_mb)
            sp = tm.paged_attention_point(
                ctx, q_lens, num_kv_heads=hk, head_dim=dh, num_heads=h,
                block_size=bs, max_blocks=mb, kv_bits=kv_bits,
                streamed=True)
            gp = tm.paged_attention_point(
                ctx, q_lens, num_kv_heads=hk, head_dim=dh, num_heads=h,
                block_size=bs, max_blocks=mb, kv_bits=kv_bits,
                streamed=False)
            record(f"kernel_pattn_tpu_model_{tag}_{cname}_ctx",
                   sp.latency_s * 1e6,
                   f"bound={'compute' if sp.compute_s >= sp.memory_s else 'memory'};"
                   f"speedup_vs_gather={gp.latency_s / sp.latency_s:.2f}x")

    with open(args.out, "w") as f:
        json.dump({"schema": "kernels_bench/v2",
                   "backend": jax.default_backend(),
                   "jax_version": jax.__version__,
                   "rows": rows}, f, indent=2)
        f.write("\n")
    print(f"# wrote {len(rows)} rows to {args.out}", flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_checkout_cache

    use_checkout_cache()
    main()

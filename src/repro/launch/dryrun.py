import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# The two lines above MUST run before any jax import: jax locks the device
# count at first backend initialization. This module is the ONLY place the
# 512-way host-platform device pool is created; tests and benches see 1.
"""Multi-pod dry-run driver.

For every (arch x input-shape x mesh) cell:
    lowered  = jax.jit(step, in_shardings, out_shardings).lower(*abstract)
    compiled = lowered.compile()
print memory_analysis (fits-per-device proof) and cost_analysis, run the
HLO-text analyzer (trip-count-aware FLOPs / HBM bytes / collective bytes),
and cache everything to results/dryrun/<cell>.json — EXPERIMENTS.md tables
and the roofline are generated from that cache.

Usage:
  python -m repro.launch.dryrun --arch stablelm-12b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
  python -m repro.launch.dryrun --all --mesh single --compression itera
"""
import argparse
import json
import time
import traceback

import jax


VARIANTS = {
    # §Perf hillclimb variants: ModelConfig field overrides per cell
    "": {},
    "dots": {"remat_policy": "dots"},
    "kv8": {"kv_cache_bits": 8},
    "chunked512": {"attn_chunk": 512},
    "chunked2k": {"attn_chunk": 2048},
    "lchunk4k": {"loss_chunk": 4096},
    "ssmchunk32": {"ssm_chunk": 32},
    "ssmchunk64": {"ssm_chunk": 64},
}


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             compression: str = "none", out_dir: str = "results/dryrun",
             ssm_engine: str = "sequential", force: bool = False,
             variant: str = "") -> dict:
    from repro.configs import SHAPES, get_config, shape_applicable
    from repro.core.compress import CompressionConfig
    from repro.hw import hlo_analysis
    from repro.launch import steps
    from repro.launch.mesh import make_production_mesh
    from repro.models import set_linear_mode
    from repro.runtime import shardctx

    mesh_name = "multi" if multi_pod else "single"
    cell_id = f"{arch}__{shape_name}__{mesh_name}" + (
        f"__{compression}" if compression != "none" else "") + (
        f"__{variant}" if variant else "")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, cell_id + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            cached = json.load(f)
        if cached.get("status") in ("ok", "skipped"):  # errors retry
            return cached

    ok, why = shape_applicable(arch, shape_name)
    if not ok:
        rec = {"cell": cell_id, "status": "skipped", "reason": why}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        return rec

    comp_cfg = None
    if compression == "quant":
        comp_cfg = CompressionConfig(method="quant", weight_wl=4)
    elif compression == "itera":
        comp_cfg = CompressionConfig(method="itera", weight_wl=4,
                                     rank_fraction=0.35)

    t0 = time.time()
    set_linear_mode("ref")  # SPMD-friendly jnp math inside the big graphs
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"cell": cell_id, "arch": arch, "shape": shape_name,
           "mesh": list(mesh.devices.shape), "compression": compression,
           "status": "error"}
    try:
        with shardctx.use_mesh(mesh):
            cell = steps.build_cell(arch, shape_name, mesh,
                                    compression=comp_cfg,
                                    ssm_engine=ssm_engine,
                                    cfg_overrides=VARIANTS[variant])
            jitted = jax.jit(
                cell["fn"],
                in_shardings=cell["in_shardings"],
                out_shardings=cell["out_shardings"],
                donate_argnums=cell["donate_argnums"])
            lowered = jitted.lower(*cell["args"])
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo_text = compiled.as_text()
        hlo = hlo_analysis.analyze(hlo_text)
        try:  # cache the HLO so analyzer updates re-run without recompiling
            import zstandard
            with open(os.path.join(out_dir, cell_id + ".hlo.zst"),
                      "wb") as zf:
                zf.write(zstandard.ZstdCompressor(level=6).compress(
                    hlo_text.encode()))
        except Exception:  # noqa: BLE001 — cache is best-effort
            pass

        spec = SHAPES[shape_name]
        cfg = get_config(arch)
        n_chips = int(mesh.devices.size)
        rec.update(
            status="ok",
            n_chips=n_chips,
            seconds={"lower": round(t_lower, 1),
                     "compile": round(t_compile, 1)},
            memory_analysis={
                "argument_bytes_per_device": int(ma.argument_size_in_bytes),
                "output_bytes_per_device": int(ma.output_size_in_bytes),
                "temp_bytes_per_device": int(ma.temp_size_in_bytes),
                "alias_bytes_per_device": int(ma.alias_size_in_bytes),
                "peak_bytes_per_device": int(ma.argument_size_in_bytes
                                             + ma.output_size_in_bytes
                                             + ma.temp_size_in_bytes
                                             - ma.alias_size_in_bytes),
            },
            xla_cost_analysis={"flops": ca.get("flops", 0.0),
                               "bytes_accessed": ca.get("bytes accessed",
                                                        0.0)},
            hlo_analysis=hlo,
            workload={
                "kind": spec.kind, "seq_len": spec.seq_len,
                "global_batch": spec.global_batch,
                "params": cfg.param_count(),
                "active_params": cfg.active_param_count(),
            },
        )
        print(f"[dryrun] {cell_id}: OK "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s, "
              f"peak/device {rec['memory_analysis']['peak_bytes_per_device']/2**30:.2f} GiB, "
              f"flops/device {hlo['flops_per_device']:.3e}, "
              f"coll/device {hlo['collective_bytes_per_device']:.3e} B)")
    except Exception as e:  # noqa: BLE001 — record the failure, don't die
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[dryrun] {cell_id}: FAIL {rec['error']}")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def reanalyze(out_dir="results/dryrun"):
    """Re-run the HLO analyzer over cached .hlo.zst files (no recompiles)."""
    import glob

    import zstandard

    from repro.hw import hlo_analysis

    n = 0
    for jf in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        zf = jf[:-5] + ".hlo.zst"
        if not os.path.exists(zf):
            continue
        with open(jf) as f:
            rec = json.load(f)
        if rec.get("status") != "ok":
            continue
        text = zstandard.ZstdDecompressor().decompress(
            open(zf, "rb").read()).decode()
        rec["hlo_analysis"] = hlo_analysis.analyze(text)
        with open(jf, "w") as f:
            json.dump(rec, f, indent=1)
        n += 1
    print(f"[dryrun] reanalyzed {n} cells in {out_dir}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reanalyze", action="store_true",
                    help="refresh hlo_analysis from cached HLO, no compiles")
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--compression", default="none",
                    choices=["none", "quant", "itera"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--ssm-engine", default="sequential",
                    choices=["sequential", "chunked"])
    ap.add_argument("--variant", default="", choices=sorted(VARIANTS))
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    if args.reanalyze:
        reanalyze(args.out)
        return

    from repro.configs import cells

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    todo = []
    if args.all:
        for a, s, ok, _ in cells(include_skipped=True):
            for m in meshes:
                todo.append((a, s, m))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        for m in meshes:
            todo.append((args.arch, args.shape, m))

    n_ok = n_skip = n_fail = 0
    for a, s, m in todo:
        rec = run_cell(a, s, m, compression=args.compression,
                       out_dir=args.out, ssm_engine=args.ssm_engine,
                       force=args.force, variant=args.variant)
        st = rec.get("status")
        n_ok += st == "ok"
        n_skip += st == "skipped"
        n_fail += st == "error"
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_fail} failed "
          f"of {len(todo)}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

"""Benchmark driver: one module per paper table/figure + kernels +
roofline. Prints ``name,us_per_call,derived`` CSV rows (benchmarks with no
wall-time axis report 0.0 and carry their numbers in `derived`).

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run fig7 fig10 # subset
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

MODULES = [
    "fig1_quant",
    "fig4_sensitivity",
    "fig7_pareto",
    "fig8_nops",
    "fig9_generality",
    "fig10_engines",
    "fig11_codesign",
    "fig12_occupancy",
    "kernels_bench",
    "roofline",
]


def main() -> None:
    want = sys.argv[1:]
    mods = [m for m in MODULES if not want or any(w in m for w in want)]
    failures = []
    here = os.path.dirname(os.path.abspath(__file__))
    for name in mods:
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        # each module runs in its own process: XLA's JIT memory is not
        # reclaimable in-process and hundreds of compiles across benches
        # otherwise exhaust it
        import subprocess
        # the children share one compile cache: JAX_COMPILATION_CACHE_DIR
        # where it is set, else the checkout's .jax_cache/
        # (repro.launch.compile_cache; this parent stays off jax)
        r = subprocess.run(
            [sys.executable, os.path.join(here, name + ".py")],
            text=True, capture_output=True,
            env={"JAX_COMPILATION_CACHE_DIR": os.path.join(
                     here, os.pardir, ".jax_cache"),
                 **os.environ,
                 "PYTHONPATH": os.pathsep.join(
                     [os.path.join(here, "..", "src"), here,
                      os.environ.get("PYTHONPATH", "")])})
        sys.stdout.write(r.stdout)
        if r.returncode != 0:
            failures.append(name)
            print(f"# {name} FAILED (exit {r.returncode}):", flush=True)
            sys.stdout.write(r.stderr[-2000:])
        else:
            print(f"# {name} done in {time.time()-t0:.0f}s", flush=True)
    if failures:
        print(f"# FAILURES: {failures}", flush=True)
        raise SystemExit(1)
    print("# all benchmarks complete", flush=True)


if __name__ == "__main__":
    main()

"""Run one cell of the benchmark and print its result as the last line.

    python3 perfbench/run.py --workload phi3m-itera-w4.chat --seed 7 \
        --seconds 40 --trace 0

`--trace 0` prints the cell's end-to-end metrics, `--trace 1` its
per-layer metrics, read from a profiler trace of the whole window.
The last lines of standard error, and the `check` key that ends the
result line, give each number compared for `correct` beside its limit.
Exits non-zero, printing no result, where JAX finds no TPU, fewer chips
than the cell needs, or a device kind without published peaks.
"""
import time

T0 = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import bench

    try:
        result = bench.run(args.workload, args.seed, args.seconds,
                           bool(args.trace), T0)
    except bench.NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings that set a cell's limits: for each seed, the served path's
numbers and the control's, from one short window at the cell's own load,
each judged by the cell's limits as a run judges its own.

    python3 perfbench/controls.py --workload phi3m-itera-w4.chat \
        --seeds 11 12 13 --seconds 2

Readings per seed: `served`, the program as configured, and `lower`, the
control: the reference put in the program's place at the same positions
of the same prompts and served tokens, with every activation rounded to
the next float type down (float32 -> bfloat16, bfloat16 ->
float8_e4m3fn). Each carries `correct`, the verdict of `check.judge`
under `limits/<cell>.json`; a sound limit reads the served path correct
and the control not. One JSON line per seed; the benchmark's own runs
never run this.
"""
import time

T0 = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def served(cell, seed: int, seconds: float) -> list:
    """(prompt, served tokens) of a sample of one short window's
    finished requests, drawn from the seed."""
    import numpy as np

    import bench
    import check

    engine = bench.setup(cell, seed, T0)
    w = bench.WINDOWS[cell.mix["kind"]](engine, cell, seed, seconds,
                                        bench.Tracer(False))
    del engine
    gc.collect()
    finished = [(s.prompt, np.asarray(s.tokens, np.int32))
                for s in w.rec.reqs if s.done]
    return check.sample(finished, cell.mix["sample_requests"], seed)


def one_seed(name: str, seed: int, seconds: float) -> dict:
    import bench
    import check

    cell = bench.load_cell(name)
    bench.configure_jax(cell)
    items = served(cell, seed, seconds)
    out = check.readings(seed, cell.cfg, items, LOWER[cell.cfg["dtype"]])
    limits = check.load_limits(name)
    for reading in out.values():
        reading["correct"] = check.judge(limits, reading)[1]
    out["seed"] = seed
    out["longest"] = max(len(p) + len(o) for p, o in items)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    import bench

    bench.devices(1)
    for seed in args.seeds:
        print(json.dumps(one_seed(args.workload, seed, args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

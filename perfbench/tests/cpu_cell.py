"""Drive a whole benchmark run on the CPU at a tiny size, in a copy of the
benchmark directory: the harness's look for a chip is skipped, and
`fault` plants a defect in the served path underneath. A CPU trace holds
no TPU plane, so a traced run reduces it to one fixed step of one second
of device time: what such a run tests is the way from the window's rows
through the family's work to the per-layer metrics."""
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
SRC = os.path.join(os.path.dirname(PERFBENCH), "src")

TINY = {
    "name": "tiny", "source": "test", "reduced": [], "family": "dense",
    "model": {"num_layers": 2, "d_model": 256, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 64, "d_ff": 512,
              "vocab_size": 512, "mlp_act": "swiglu", "norm": "rmsnorm",
              "norm_eps": 1e-05, "pos_emb": "rope", "rope_theta": 10000.0,
              "dtype": "float32", "tie_embeddings": False},
    "plan": {"method": "itera", "weight_wl": 4, "act_wl": 8,
             "rank_fraction": 0.5, "pack": True},
    "serving": {"max_batch": 4, "block_size": 16, "chunk_tokens": 32,
                "matmul_precision": "highest", "num_blocks": 33},
    "max_positions": 512,
}
TINY_QUANT = {**TINY, "name": "tiny-quant",
              "plan": {"method": "quant", "weight_wl": 4, "act_wl": 8,
                       "pack": True}}
BURSTS = {"kind": "bursts", "burst_size": 4,
          "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.8,
                     "min": 4, "max": 48},
          "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                     "min": 2, "max": 12},
          "sample_requests": 3, "layout_seed": 1}
JOB = {"kind": "job", "requests": 400, "block": 8,
       "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                  "min": 4, "max": 40},
       "output": {"dist": "ratio", "low": 0.8, "high": 1.25, "min": 2,
                  "max": 16},
       "sample_requests": 3, "layout_seed": 2}

_SCRIPT = r"""
import json, sys
sys.path[:0] = [{bench!r}, {src!r}]
import jax
import bench

def cpu_devices(chips):
    peaks = bench.load_json(bench.HERE, "peaks.json")["devices"]
    return jax.devices()[:1], next(iter(peaks.values()))

bench.devices = cpu_devices
if {trace!r}:
    bench.devtrace.reduce = lambda pd, **_: bench.devtrace.Reduction(
        window_s=1.0, busy_s=1.0, op_s={{}}, step_count=1,
        step_device_s=1.0, gaps=[])
fault = {fault!r}
if fault:
    import jax.numpy as jnp
    from repro.models import transformer as tfm
    real = tfm.serve_step
    def broken(params, pool, *a, **k):
        toks, fin, recent, new_pool = real(params, pool, *a, **k)
        if fault == "token":
            toks = (toks + 1) % {vocab}
        elif fault == "stale_state":
            new_pool = pool
        return toks, fin, recent, new_pool
    tfm.serve_step = broken
res = bench.run({cell!r}, {seed}, {seconds}, {trace}, 0.0)
print(json.dumps(res))
"""


def cfg(config=TINY) -> dict:
    """What a family reads of a configuration, as `bench.Cell.cfg`."""
    return {**config["model"], "plan": config["plan"],
            "family": config["family"]}


def make_copy(root, traffic_name="tiny_bursts", mix=BURSTS, limit=0.5,
              config=TINY, metrics=("output_tok_s",)):
    """A checkout holding the benchmark plus one new traffic file, one new
    configuration and one new BENCHMARK.json cell, which joins the
    `workloads` of `metrics`; nothing existing is edited."""
    dst = os.path.join(root, "perfbench")
    shutil.copytree(PERFBENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    name = config["name"]
    cell = f"{name}.{traffic_name}"
    with open(os.path.join(dst, "traffic", f"{traffic_name}.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(dst, "configs", f"{name}.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(dst, "limits", f"{cell}.json"), "w") as f:
        json.dump({"max_gap": {"limit": limit}}, f)
    with open(os.path.join(os.path.dirname(PERFBENCH),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"perfbench/configs/{name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": name,
                               "traffic": traffic_name, "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and m["name"] in metrics:
            m["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst, cell


def run(root, cell, *, seed=3_000_000_019, seconds=2.0, trace=0,
        fault=None, timeout=600):
    code = _SCRIPT.format(bench=os.path.join(root, "perfbench"), src=SRC,
                          fault=fault, vocab=TINY["model"]["vocab_size"],
                          cell=cell, seed=seed, seconds=seconds,
                          trace=bool(trace))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env, cwd=root)
    if p.returncode != 0:
        raise AssertionError(p.stderr[-4000:])
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr

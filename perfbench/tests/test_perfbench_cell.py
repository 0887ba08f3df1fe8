"""A cell is data: a new traffic file, configuration and limits file plus
one BENCHMARK.json entry make a runnable cell, with no existing file
edited, under either plan method, and a model family or a plan method
arrives the same way, as a new file. And a run that finds no TPU, or a
checkout without the program, exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import cpu_cell

ROOT = os.path.dirname(cpu_cell.PERFBENCH)


@pytest.mark.parametrize("traffic_name,mix", [
    ("tiny_bursts", cpu_cell.BURSTS), ("tiny_job", cpu_cell.JOB)])
def test_new_cell_from_data_files(tmp_path, traffic_name, mix):
    _, cell = cpu_cell.make_copy(str(tmp_path), traffic_name, mix)
    res, err = cpu_cell.run(str(tmp_path), cell, seconds=1.5)
    assert res["correct"] is True, err[-2000:]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["compiles_in_window"] == 0
    assert set(res["metrics"]) == {"setup_s", "output_tok_s"}
    assert res["metrics"]["output_tok_s"]["value"] > 0
    assert list(res)[-1] == "check"
    assert res["check"]["max_gap"]["value"] <= 0.5
    assert err.strip().splitlines()[-1].startswith("check max_gap ")


@pytest.mark.parametrize("fault", [None, "token"])
def test_quant_plan_cell(tmp_path, fault):
    """A cell whose configuration quantizes every linear alone (plan
    method "quant", served through `quant_matmul`) reads `correct`; with
    a token altered where it is produced it does not."""
    _, cell = cpu_cell.make_copy(str(tmp_path), config=cpu_cell.TINY_QUANT)
    res, err = cpu_cell.run(str(tmp_path), cell, seconds=1.5, fault=fault)
    gap = res["check"]["max_gap"]
    if fault is None:
        assert res["correct"] is True, err[-2000:]
        assert res["failed"] == 0 and res["attempted"] > 0
        assert gap["value"] <= gap["limit"]
    else:
        assert res["correct"] is False
        assert gap["value"] > gap["limit"]


TINY_ALT = '''"""The dense family, served with an int8 KV cache, whose work first
makes the reads a routed family makes."""
import dataclasses
import sys

import numpy as np

from families import dense
from families.dense import hidden_states, logits, program_tree


def program_config(config):
    cfg = dataclasses.replace(dense.program_config(config), kv_cache_bits=8)
    print(f"tiny_alt: kv_cache_bits {cfg.kv_cache_bits}", file=sys.stderr)
    return cfg


def work(cfg, tok, steps, seed):
    """Dense work, after a stand-in for routing: the reference's last
    hidden state at every row the window processed, from the requests'
    token ids and the seed, picks one of 4 experts, and each step reads
    the experts its rows picked."""
    hs = hidden_states(seed, cfg, [r.ids for r in tok.requests])
    reads = {}
    for r, h in zip(tok.requests, hs):
        picked = np.asarray(h)[r.positions].argmax(-1) % 4
        for step, e in zip(r.steps, picked):
            reads.setdefault(step, set()).add(int(e))
    rows = sum(len(r.positions) for r in tok.requests)
    assert rows == tok.rows and sorted(reads) == list(range(len(reads)))
    print(f"tiny_alt: {rows} rows in {len(reads)} steps, "
          f"{sum(map(len, reads.values()))} expert reads", file=sys.stderr)
    return dense.work(cfg, tok, steps, seed)
'''


def _files(root):
    return {os.path.relpath(os.path.join(d, f), root)
            for d, _, fs in os.walk(root) for f in fs
            if "__pycache__" not in d}


def test_new_family_from_a_file(tmp_path):
    """A configuration that names a family found only as a new file,
    `families/tiny_alt.py`, runs end to end with no harness file edited,
    traced, so the family's `work` reads the window's rows.

    This is the room a family such as DeepSeek-V2-Lite needs, each part
    written in its own module: an expert site stack with a leading expert
    axis (its `program_tree`, from `synth.site_arrays` per expert), a
    second kind of layer (layer 0 dense, the rest routed experts; its
    `hidden_states` drives its own layers), latent-attention sites (its
    own site list and reference block), and expert work that depends on
    routing (its `work(cfg, tokens, steps, seed)` gets each in-window
    request's token ids, the rows processed and the step each ran in, and
    can run its own reference's router over them, as tiny_alt's stand-in
    does)."""
    dst, cell = cpu_cell.make_copy(
        str(tmp_path), config={**cpu_cell.TINY, "name": "tiny-alt",
                               "family": "tiny_alt"},
        metrics=("output_tok_s", "tokens_per_step", "step_mfu"))
    with open(os.path.join(dst, "families", "tiny_alt.py"), "w") as f:
        f.write(TINY_ALT)
    added = {"configs/tiny-alt.json", "families/tiny_alt.py",
             "limits/tiny-alt.tiny_bursts.json", "traffic/tiny_bursts.json"}
    theirs = {f for f in _files(cpu_cell.PERFBENCH)
              if not f.startswith("tests")}
    assert _files(dst) == theirs | added
    for f in theirs:
        with open(os.path.join(dst, f), "rb") as a, open(
                os.path.join(cpu_cell.PERFBENCH, f), "rb") as b:
            assert a.read() == b.read(), f
    res, err = cpu_cell.run(str(tmp_path), cell, seconds=1.5, trace=1)
    assert "tiny_alt: kv_cache_bits 8" in err
    assert res["correct"] is True, err[-2000:]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["compiles_in_window"] == 0
    line = next(s for s in err.splitlines() if " expert reads" in s)
    rows, steps = int(line.split()[1]), int(line.split()[4])
    assert rows > 0 and steps > 1
    # one traced step of one second on the CPU: tokens_per_step is the
    # window's rows, and step_mfu reads the family's work
    assert res["metrics"]["tokens_per_step"]["value"] == rows
    assert res["metrics"]["step_mfu"]["value"] > 0


TINY_Q = '''"""Plan method "tiny_q": "quant" under a name of its own."""
import sys

from methods import quant
from methods.quant import KERNEL, arrays, linear, node, weight_bytes


def shapes(plan, k, n):
    return {**quant.shapes(plan, k, n), "method": "tiny_q"}


def row_macs(shp):
    print("tiny_q: row_macs", file=sys.stderr)
    return quant.row_macs(shp)
'''


def test_new_method_from_a_file(tmp_path):
    """A plan that names a method found only as a new file,
    `methods/tiny_q.py`, is made, served, held to the reference and
    counted through that file, traced, with no harness file edited."""
    config = {**cpu_cell.TINY_QUANT, "name": "tiny-q",
              "plan": {**cpu_cell.TINY_QUANT["plan"], "method": "tiny_q"}}
    dst, cell = cpu_cell.make_copy(str(tmp_path), config=config,
                                   metrics=("output_tok_s", "step_mfu"))
    with open(os.path.join(dst, "methods", "tiny_q.py"), "w") as f:
        f.write(TINY_Q)
    assert _files(dst) == {f for f in _files(cpu_cell.PERFBENCH)
                           if not f.startswith("tests")} | {
        "configs/tiny-q.json", "methods/tiny_q.py",
        "limits/tiny-q.tiny_bursts.json", "traffic/tiny_bursts.json"}
    res, err = cpu_cell.run(str(tmp_path), cell, seconds=1.5, trace=1)
    assert res["correct"] is True, err[-2000:]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "tiny_q: row_macs" in err
    assert res["metrics"]["step_mfu"]["value"] > 0


def _run_py(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "opus-mt-itera-w4.offline", "--seed", "5", "--seconds", "1"],
        cwd=cwd, capture_output=True, text=True, timeout=300, env=env)


def test_no_tpu_exits_nonzero():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_checkout_without_program_fails(tmp_path):
    shutil.copytree(cpu_cell.PERFBENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert json.load(open(tmp_path / "BENCHMARK.json"))["paths"] == [
        "perfbench"]


def test_compile_counter_sees_a_compile():
    import jax
    import numpy as np

    import bench

    c = bench.CompileCounter()
    c.on = True
    jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
    c.on = False
    assert c.count >= 1
    n = c.count
    jax.jit(lambda x: x - 2)(np.arange(5.0))
    assert c.count == n

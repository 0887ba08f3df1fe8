"""A cell is data: a new traffic file, configuration and limits file plus
one BENCHMARK.json entry make a runnable cell, with no existing file
edited. And a run that finds no TPU, or a checkout without the program,
exits non-zero and prints no result."""
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

"""The reduction of the program's own spans (`spans.py`) on the small
recorded trace of `test_perfbench_trace`, with `repro.serve.*` spans
added: host time and counters per step by hand, gaps named by the
innermost span of either prefix, and nothing `devtrace` or the per-layer
metrics read moved by the program's spans."""
import json
import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

import bench
import devtrace
import spans as sp
import work
from test_perfbench_trace import ev, recorded

EXISTING = ["tokens_per_step", "step_device_ms", "step_mfu",
            "lowrank_qmm_roofline", "paged_attention_roofline",
            "device_idle_share", "weight_hbm_mb"]


def step(start_us, dur_us, valid, kv_valid, grid):
    e = ev("repro.serve.step", start_us, dur_us)
    e.stats = [("valid_tokens", valid), ("kv_blocks_valid", kv_valid),
               ("kv_grid_blocks", grid)]
    return e


def program_spans():
    """Two steps inside `perfbench.serve` [0, 700] us: the first
    consumes an older step (its readback, then the harness's on_token
    [250, 310]); the second drains, its readback over the device's idle
    gap [500, 600]."""
    return [ev("repro.serve.setup", 5, 55),
            step(60, 260, 10, 6, 16),
            ev("repro.serve.schedule", 62, 8),
            ev("repro.serve.build", 70, 10),
            ev("repro.serve.dispatch", 80, 15),
            ev("repro.serve.consume", 200, 115),
            ev("repro.serve.readback", 205, 40),
            step(400, 250, 4, 2, 16),
            ev("repro.serve.schedule", 402, 8),
            ev("repro.serve.build", 410, 10),
            ev("repro.serve.dispatch", 420, 10),
            ev("repro.serve.drain", 510, 130),
            ev("repro.serve.consume", 512, 126),
            ev("repro.serve.readback", 520, 60)]


def traced():
    """The program's spans, and the runtime's own line on the host: one
    wait for device memory inside the first step's dispatch, one
    outside every step."""
    pd = recorded()
    pd.planes[0].lines[0].events += program_spans()
    pd.planes[0].lines.append(NS(name="main/1305", events=[
        ev("AllocateBufferAwait", 82, 10),
        ev("AllocateBufferAwait", 700, 10)]))
    return pd


def test_step_host_time_and_counters_by_hand():
    st = sp.steps(traced())
    assert [s.counters for s in st] == [
        {"valid_tokens": 10, "kv_blocks_valid": 6, "kv_grid_blocks": 16},
        {"valid_tokens": 4, "kv_blocks_valid": 2, "kv_grid_blocks": 16}]
    assert [s.duration_s for s in st] == pytest.approx([260e-6, 250e-6])
    # less readback 40 and on_token 60; less readback 60
    assert [s.host_s for s in st] == pytest.approx([160e-6, 190e-6])
    assert [s.memory_wait_s for s in st] == pytest.approx([10e-6, 0.0])
    assert sp.host_step_ms(st) == pytest.approx(0.175)
    assert sp.attention_grid_fill(st) == pytest.approx(100.0 * 8 / 32)


def test_gaps_named_by_innermost_span_of_either_prefix():
    got = [(n, round(t * 1e6)) for n, t in sp.idle_gaps(traced())]
    assert got[:2] == [("perfbench.make_requests", 390),
                       ("perfbench.on_token", 200)]
    assert sorted(got[2:]) == [("repro.serve.readback", 100),
                               ("repro.serve.setup", 100)]


def test_program_without_spans_reads_nothing():
    pd = recorded()
    assert sp.steps(pd) == []
    assert sp.host_step_ms([]) is None
    assert sp.attention_grid_fill([]) is None
    assert sorted(sp.idle_gaps(pd)) == sorted(devtrace.reduce(pd).gaps)


def _metrics(red) -> dict:
    cell = bench.load_cell("opus-mt-itera-w4.offline")
    with open(os.path.join(bench.HERE, "peaks.json")) as f:
        peaks = next(iter(json.load(f)["devices"].values()))
    ctx = {"trace": red, "cfg": cell.cfg, "peaks": peaks, "seed": 5,
           "weight_hbm_bytes": 51_286_000,
           "trace_tokens": work.Tokens([48, 30], [48, 49, 30], 5)}
    return {m: bench.read_metric(m, ctx) for m in EXISTING}


def test_existing_reduction_and_metrics_unmoved_by_program_spans():
    plain, spanned = devtrace.reduce(recorded()), devtrace.reduce(traced())
    assert spanned == plain
    assert _metrics(spanned) == _metrics(plain)
    assert all(v is not None for v in _metrics(plain).values())
    # the same gaps, only named more finely
    assert (sorted(t for _, t in sp.idle_gaps(traced()))
            == sorted(t for _, t in plain.gaps))


def test_sweep_matches_a_scan_over_every_span():
    rng = np.random.default_rng(7)
    spans = []
    for i in range(300):
        s = float(rng.integers(0, 1000))
        spans.append(devtrace.Event(f"s{i}", s,
                                    s + float(rng.integers(0, 60))))
    times = [float(t) for t in rng.integers(-10, 1070, 500)]

    def scan(t):
        best = None
        for s in spans:
            if s.start <= t <= s.end and (best is None or s.end - s.start
                                          < best.end - best.start):
                best = s
        return best.name if best else "none"

    assert sp.attribute(times, spans, "none") == [scan(t) for t in times]


def test_steps_ignore_spans_of_other_host_lines():
    pd = traced()
    pd.planes[0].lines.append(NS(name="other thread", events=[
        ev("repro.serve.readback", 100, 200)]))
    assert [s.host_s for s in sp.steps(pd)] == pytest.approx(
        [160e-6, 190e-6])

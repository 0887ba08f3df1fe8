"""The trace reduction on a small recorded trace: busy time is the union
of device intervals, kernel time sums by name, the serving step is the
program whose runs hold paged_attention, and each idle gap goes to the
innermost benchmark span around it."""
from types import SimpleNamespace as NS

import pytest

import devtrace as tr


def ev(name, start_us, dur_us):
    return NS(name=name, start_ns=start_us * 1000.0,
              duration_ns=dur_us * 1000.0)


def recorded():
    ops = [ev("%fusion.3 = bf16[8]{0} fusion(%p)", 100, 40),
           ev("%lowrank_qmm.1 = bf16[8,8]{1,0} custom-call(%a)", 140, 40),
           ev("%paged_attention.7 = bf16[8]{0} custom-call(%b)", 180, 20),
           ev("%while.2 = (s32[]) while(%t)", 400, 100),
           ev("%lowrank_qmm.2 = bf16[8]{0} custom-call(%c)", 410, 30),
           ev("%copy.9 = bf16[8]{0} copy(%d)", 600, 10),
           ev("%paged_attention = bf16[8]{0} custom-call(%e)", 450, 30)]
    mods = [ev("jit__lambda_", 100, 100), ev("jit__lambda_", 400, 100),
            ev("jit_copy_block", 600, 10)]
    host = [ev("perfbench.serve", 0, 700), ev("perfbench.on_token", 250, 60),
            ev("perfbench.make_requests", 700, 300), ev("pjit", 10, 5)]
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=host)]),
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                       NS(name="XLA Modules", events=mods)]),
        NS(name="/device:TPU:0 SparseCore", lines=[]),
    ])


def test_busy_is_union_of_device_intervals():
    red = tr.reduce(recorded())
    # [100,200] + [400,500] + [600,610]
    assert red.busy_s == pytest.approx(210e-6)
    assert red.window_s == pytest.approx(1000e-6)


def test_kernel_time_by_name():
    red = tr.reduce(recorded())
    assert red.kernel_s("lowrank_qmm") == pytest.approx(70e-6)
    assert red.kernel_s("paged_attention") == pytest.approx(50e-6)
    assert red.op_s["fusion"] == pytest.approx(40e-6)


def test_loop_time_is_self_time():
    red = tr.reduce(recorded())
    # the while loop spans [400, 500] and holds 30 + 30 of nested ops
    assert red.op_s["while"] == pytest.approx(40e-6)
    assert sum(red.op_s.values()) == pytest.approx(red.busy_s)


def test_step_program_holds_paged_attention():
    red = tr.reduce(recorded())
    assert red.step_count == 2
    assert red.step_device_s == pytest.approx(200e-6)


def test_gaps_attributed_to_innermost_span():
    red = tr.reduce(recorded())
    got = {(n, round(t * 1e6)) for n, t in red.gaps}
    assert got == {("perfbench.serve", 100), ("perfbench.on_token", 200),
                   ("perfbench.serve", 100),
                   ("perfbench.make_requests", 390)}
    assert red.gaps[0] == ("perfbench.make_requests", pytest.approx(390e-6))
    b = red.breakdown()
    assert b["device_ops"][0][0] == "lowrank_qmm"
    assert b["device_ops"][0][1] == pytest.approx(70e-6)
    assert len(b["idle_gaps"]) == 4


def test_gap_outside_spans_takes_the_default():
    pd = recorded()
    pd.planes[0].lines[0].events = []
    red = tr.reduce(pd, default_span="perfbench.serve")
    assert {n for n, _ in red.gaps} == {"perfbench.serve"}


def test_trace_without_device_plane_is_refused():
    pd = recorded()
    pd.planes = pd.planes[:1]
    with pytest.raises(ValueError):
        tr.reduce(pd)

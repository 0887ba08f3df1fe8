"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read: device busy time (the union of the intervals in which an operation
ran), device time per kernel name, the serving-step program's executions
and device time, and the device's idle gaps, each attributed to the
benchmark's own host span (`perfbench.*` TraceAnnotations) it fell in.

A device plane is one named `/device:TPU:<n>`. Its "XLA Ops" line holds
one event per operation run, named by its HLO instruction
(`%lowrank_qmm.3 = s8[...] custom-call(...)`: a Pallas kernel carries its
`name`); a loop's event (`%while.12`) spans the events of its body, so
time per operation is self time. Its "XLA Modules" line holds one event
per program run. The serving step is the program whose runs contain
`paged_attention`, which only the serving step calls.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP_KERNEL = "paged_attention"
HOST_SPAN = "perfbench."


@dataclasses.dataclass
class Event:
    name: str
    start: float        # seconds, on the trace's own clock
    end: float


@dataclasses.dataclass
class Device:
    ops: list
    modules: list


def load(trace_dir: str):
    """ProfileData of the newest trace written under trace_dir."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(paths, key=os.path.getmtime))


def _events(line) -> list:
    return [Event(e.name, e.start_ns * 1e-9,
                  (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


def split(pd):
    """(devices, host spans, session (start, end)) of a ProfileData."""
    devices, spans = [], []
    lo, hi = float("inf"), float("-inf")
    for plane in pd.planes:
        is_dev = re.fullmatch(r"/device:TPU:\d+", plane.name) is not None
        lines = {line.name: _events(line) for line in plane.lines}
        for evs in lines.values():
            if evs:
                lo = min(lo, min(e.start for e in evs))
                hi = max(hi, max(e.end for e in evs))
        if is_dev:
            devices.append(Device(lines.get(OPS_LINE, []),
                                  lines.get(MODULES_LINE, [])))
        elif plane.name.startswith("/host:"):
            for evs in lines.values():
                spans += [e for e in evs if e.name.startswith(HOST_SPAN)]
    return devices, spans, (lo, hi)


def union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def base_name(name: str) -> str:
    """`%copy_fusion.12 = bf16[...] fusion(...)` -> `copy_fusion`."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def self_times(ops) -> list:
    """(event, self seconds): its duration less that of the events
    nested directly inside it on the same line."""
    out, stack = [], []
    for e in sorted(ops, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            stack.pop()
        rec = [e, e.end - e.start]
        if stack:
            stack[-1][1] -= e.end - e.start
        stack.append(rec)
        out.append(rec)
    return [(e, t) for e, t in out]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                 # mean over devices
    op_s: dict                    # base op name -> device self seconds
    step_count: int
    step_device_s: float
    gaps: list                    # [(attributed span, seconds)], longest first

    def kernel_s(self, name: str) -> float:
        return sum(t for n, t in self.op_s.items() if name in n)

    def breakdown(self) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, t] for n, t in top],
                "idle_gaps": [[n, t] for n, t in self.gaps[:10]]}


def _attribute(t: float, spans, default: str) -> str:
    """Innermost benchmark span covering time t."""
    best = None
    for s in spans:
        if s.start <= t <= s.end and (best is None
                                      or s.end - s.start
                                      < best.end - best.start):
            best = s
    return best.name if best else default


def _steps(dev: Device) -> list:
    marks = sorted(e.start for e in dev.ops if STEP_KERNEL in e.name)
    out = []
    for m in dev.modules:
        i = bisect.bisect_left(marks, m.start)
        if i < len(marks) and marks[i] <= m.end:
            out.append(m)
    return out


def reduce(pd, n_devices: int = 1,
           default_span: str = "outside benchmark spans") -> Reduction:
    """`default_span` names where the host was when a gap falls in no
    recorded span (a trace started inside a span does not record it)."""
    devices, spans, (lo, hi) = split(pd)
    devices = devices[:n_devices]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    busy, op_s, steps, step_s, gaps = 0.0, {}, 0, 0.0, []
    for dev in devices:
        u = union((e.start, e.end) for e in dev.ops)
        busy += sum(e - s for s, e in u)
        for e, t in self_times(dev.ops):
            k = base_name(e.name)
            op_s[k] = op_s.get(k, 0.0) + t
        st = _steps(dev)
        steps += len(st)
        step_s += sum(m.end - m.start for m in st)
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_attribute((s + e) / 2, spans, default_span),
                             e - s))
    gaps.sort(key=lambda g: -g[1])
    nd = len(devices)
    return Reduction(window_s=hi - lo, busy_s=busy / nd,
                     op_s={k: v / nd for k, v in op_s.items()},
                     step_count=steps // nd, step_device_s=step_s / nd,
                     gaps=gaps)

"""Reduction of the program's own host spans in a profiler trace.

`repro.runtime.tracing` writes the serve loop's phases as `repro.*`
TraceAnnotations on the profiler's clock, beside the benchmark's own
`perfbench.*` spans. This module reads, from the same trace `devtrace`
reduces:

* each traced `repro.serve.step`: its duration, its host time (the
  duration less the time its `repro.serve.readback` descendants and the
  harness's nested `perfbench.*` spans cover), the part of that host
  time in which the runtime held the step's dispatch waiting for device
  memory (PJRT's `AllocateBufferAwait`, on the runtime's own line of the
  host), and its counters (`valid_tokens`, `kv_blocks_valid`,
  `kv_grid_blocks`);
* the device's idle gaps, each named by the innermost span of either
  prefix around its midpoint, found by one sorted sweep.

A program without these spans (one that predates them) yields no steps,
and gaps named by the benchmark's spans alone.
"""
from __future__ import annotations

import bisect
import dataclasses
import heapq

import devtrace

PROGRAM_SPAN = "repro."
STEP_SPAN = "repro.serve.step"
READBACK_SPAN = "repro.serve.readback"
COUNTERS = ("valid_tokens", "kv_blocks_valid", "kv_grid_blocks")
MEMORY_WAIT = "AllocateBufferAwait"


@dataclasses.dataclass
class Span(devtrace.Event):
    stats: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Step:
    duration_s: float
    host_s: float
    memory_wait_s: float    # of host_s: dispatch held for device memory
    counters: dict          # those of COUNTERS the span carries


def host_lines(pd) -> list:
    """Per host line, its spans of either prefix sorted by (start,
    -end); a step span carries its counters."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [Span(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9,
                          {k: v for k, v in e.stats if k in COUNTERS}
                          if e.name == STEP_SPAN else {})
                     for e in line.events
                     if e.name.startswith((PROGRAM_SPAN,
                                           devtrace.HOST_SPAN))]
            out.append(sorted(spans, key=lambda s: (s.start, -s.end)))
    return out


def _waits(step: Span, spans: list, starts: list) -> float:
    """Seconds of `step` covered by its readbacks and harness spans."""
    lo = bisect.bisect_left(starts, step.start)
    hi = bisect.bisect_right(starts, step.end)
    inner = [(s.start, s.end) for s in spans[lo:hi]
             if s is not step and s.end <= step.end
             and (s.name == READBACK_SPAN
                  or s.name.startswith(devtrace.HOST_SPAN))]
    return sum(e - s for s, e in devtrace.union(inner))


def _memory_waits(pd) -> list:
    """(start, end) of the runtime's waits for device memory, merged."""
    return devtrace.union(
        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
        for plane in pd.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name == MEMORY_WAIT)


def steps(pd) -> list:
    """Every traced serving step, in trace order per host line."""
    waits = _memory_waits(pd)
    out = []
    for spans in host_lines(pd):
        starts = [s.start for s in spans]
        for s in spans:
            if s.name == STEP_SPAN:
                d = s.end - s.start
                held = sum(max(0.0, min(b, s.end) - max(a, s.start))
                           for a, b in waits)
                out.append(Step(d, d - _waits(s, spans, starts), held,
                                s.stats))
    return out


def attribute(times, spans, default: str) -> list:
    """Name of the innermost (shortest) span covering each time, the
    first listed among equals; `default` where none does. One sweep
    over spans by start and times in order, a heap of the spans begun
    keyed by length, the ended ones dropped as they surface."""
    order = sorted(range(len(spans)), key=lambda i: spans[i].start)
    names = [default] * len(times)
    heap, j = [], 0
    for k in sorted(range(len(times)), key=lambda k: times[k]):
        t = times[k]
        while j < len(order) and spans[order[j]].start <= t:
            i = order[j]
            heapq.heappush(heap, (spans[i].end - spans[i].start, i))
            j += 1
        while heap and spans[heap[0][1]].end < t:
            heapq.heappop(heap)
        if heap:
            names[k] = spans[heap[0][1]].name
    return names


def idle_gaps(pd, n_devices: int = 1,
              default_span: str = "outside benchmark spans") -> list:
    """[(innermost span, seconds)] of each device idle gap, longest
    first: `devtrace.reduce`'s gaps, named by either prefix."""
    devices, _, (lo, hi) = devtrace.split(pd)
    spans = [s for line in host_lines(pd) for s in line]
    bounds = []
    for dev in devices[:n_devices]:
        u = devtrace.union((e.start, e.end) for e in dev.ops)
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        bounds += [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    names = attribute([(s + e) / 2 for s, e in bounds], spans, default_span)
    return sorted(zip(names, (e - s for s, e in bounds)), key=lambda g: -g[1])


def host_step_ms(st: list) -> float | None:
    """Mean host time per traced serving step, in ms."""
    return 1000.0 * sum(s.host_s for s in st) / len(st) if st else None


def attention_grid_fill(st: list) -> float | None:
    """Share of the attention kernel's grid steps over valid KV blocks,
    summed over the traced steps, in %."""
    grid = sum(s.counters.get("kv_grid_blocks", 0) for s in st)
    if not grid:
        return None
    return 100.0 * sum(s.counters.get("kv_blocks_valid", 0)
                       for s in st) / grid

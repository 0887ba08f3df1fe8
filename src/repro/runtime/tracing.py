"""Host spans of the serving loop, on the JAX profiler's clock.

Every span the program writes is a `jax.profiler.TraceAnnotation` named
under `PREFIX`, so a profiler trace taken around `InferenceEngine.serve`
(`jax.profiler.trace(dir)`) holds the program's own phases beside the
device's operations. With no profiler session a span costs the span
object and nothing else; anything that must be computed for a span
(the serving step's counters) is computed only while `active()`.

Spans (see docs/serving.md, "Tracing"):

  * `repro.serve.setup`    — request resolution, block pool, scheduler,
                             the KV pool's allocation;
  * `repro.serve.step`     — one iteration of a serve loop, carrying the
                             counters `valid_tokens`, `kv_blocks_valid`
                             and `kv_grid_blocks`; parent of
    `repro.serve.schedule` — retiring stopped rows, scheduling, table
                             updates, copy-on-write block copies,
    `repro.serve.build`    — the packed per-step buffer,
    `repro.serve.dispatch` — the jitted step's call,
    `repro.serve.consume`  — crediting an older step's tokens, around
    `repro.serve.readback` — the blocking device-to-host reads;
  * `repro.serve.drain`    — the consumes after the last dispatch;
  * `repro.gc`             — a full (generation 2) garbage collection.
"""
from __future__ import annotations

import gc

import jax

PREFIX = "repro."


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A profiler span named `PREFIX + name`."""
    return jax.profiler.TraceAnnotation(PREFIX + name)


def active() -> bool:
    """Whether a profiler session is recording spans."""
    return jax.profiler.TraceAnnotation.is_enabled()


class _Collections:
    """`gc.callbacks` entry that spans each full collection while a
    profiler session records, so a stall in a trace can be told apart
    from the collector."""

    def __init__(self):
        self._open = None

    def __call__(self, phase: str, info: dict) -> None:
        if info["generation"] < 2:
            return
        if phase == "start":
            if active():
                self._open = span("gc")
                self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


_COLLECTIONS = _Collections()


def trace_collections() -> None:
    """Span full garbage collections from now on (idempotent: one
    callback per process, as the collector is one per process)."""
    if _COLLECTIONS not in gc.callbacks:
        gc.callbacks.append(_COLLECTIONS)

"""The serve loops' own spans and counters on the JAX profiler's clock
(runtime/tracing.py): one `repro.serve.step` per dispatched step, its
counters equal to what the step's buffer implies, readbacks nested where
the loop reads back, outputs untouched by a trace, and no counter work
without a profiler session."""
import gc
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import DraftSpec, InferenceEngine, Request
from repro.api import engine as engine_mod
from repro.configs import get_config
from repro.core.compress import CompressionConfig
from repro.kernels import paged_attention as pa
from repro.runtime import kvblocks, tracing
from repro.runtime import sampling as smp

PLAN = CompressionConfig(method="itera", weight_wl=8, rank_fraction=0.75)
LOOPS = {"plain": False, "speculative": True}


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("opus-mt", smoke=True)
    return InferenceEngine.build(cfg, PLAN, max_batch=3, block_size=4,
                                 chunk_tokens=8,
                                 speculate=DraftSpec(k=3, rank_fraction=0.7))


def _requests(engine):
    rng = np.random.default_rng(0)
    return [Request(tokens=rng.integers(0, engine.cfg.vocab_size, size=n),
                    max_tokens=g)
            for n, g in zip([5, 11, 3, 9, 14], [6, 3, 8, 5, 2])]


def _host_spans(trace_dir):
    """[(line key, name, start ns, end ns, stats)] of the program's spans."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(tracing.PREFIX):
                    out.append(((plane.name, li), e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[0], s[2], -s[3]))


def _inside(inner, outer) -> bool:
    return (inner[0] == outer[0] and outer[2] <= inner[2]
            and inner[3] <= outer[3])


def _record_buffers(monkeypatch, engine, speculative):
    """Wrap the jitted step so each dispatched buffer is kept."""
    bufs = []

    def keeping(fn, arg):
        def call(*a):
            bufs.append(np.array(a[arg]))
            return fn(*a)
        return call

    if speculative:
        ctl = engine.speculation
        step_fn = ctl.step_fn
        monkeypatch.setattr(ctl, "step_fn", lambda k, sample=False:
                            keeping(step_fn(k, sample), 4))
    else:
        unified = engine._unified_fn
        monkeypatch.setattr(engine, "_unified_fn",
                            lambda sample, stop, budget=None:
                            keeping(unified(sample, stop, budget), 3))
    return bufs


@pytest.mark.parametrize("loop", list(LOOPS))
def test_serve_spans_and_counters(engine, loop, tmp_path, monkeypatch):
    speculative = LOOPS[loop]
    reqs = _requests(engine)
    plain = engine.serve(reqs, speculate=speculative)
    bufs = _record_buffers(monkeypatch, engine, speculative)
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = engine.serve(reqs, speculate=speculative)
    finally:
        jax.profiler.stop_trace()
    for a, b in zip(plain.outputs, res.outputs):
        np.testing.assert_array_equal(a, b)

    spans = _host_spans(str(tmp_path))
    names = [s[1] for s in spans]
    steps = [s for s in spans if s[1] == "repro.serve.step"]
    assert len(steps) == res.steps == len(bufs)
    assert names.count("repro.serve.setup") == 1
    assert names.count("repro.serve.dispatch") == res.steps
    assert names.count("repro.serve.build") == res.steps

    # every position computed: prompt chunks, then one input per decode
    # row (plus its draft slots, less what verification rejected)
    decode_inputs = sum(o.size - 1 for o in res.outputs)
    if speculative:
        decode_inputs += res.drafted - res.accepted
    assert (sum(s[4]["valid_tokens"] for s in steps)
            == res.prefill_tokens + decode_inputs)

    ctx_col = -(smp.SAMP_COLS + (4 if speculative else 3))
    mb = max(kvblocks.blocks_needed(r.tokens.size, r.max_tokens, 4)
             for r in reqs)
    counts = jax.jit(kvblocks.valid_block_counts, static_argnums=(2, 3))
    for s, buf in zip(steps, bufs):
        nb = counts(jnp.asarray(buf[:, ctx_col]),
                    jnp.asarray(buf[:, ctx_col + 1]), 4, mb)
        assert s[4]["kv_blocks_valid"] == int(nb.sum())
        p = pa.chunk_blocks(4, mb)
        assert s[4]["kv_grid_blocks"] == int(
            sum(-(-int(n) // p) * p for n in np.asarray(nb)))
        assert s[4]["kv_blocks_valid"] <= s[4]["kv_grid_blocks"]

    readbacks = [s for s in spans if s[1] == "repro.serve.readback"]
    assert readbacks
    for rb in readbacks:
        assert any(_inside(rb, st) for st in steps)
        if not speculative:
            assert any(_inside(rb, c) for c in spans
                       if c[1] in ("repro.serve.consume",
                                   "repro.serve.drain"))
    if speculative:
        assert len(readbacks) == res.steps
    else:
        assert names.count("repro.serve.consume") == res.steps


@pytest.mark.parametrize("loop", list(LOOPS))
def test_counters_not_computed_without_a_session(engine, loop,
                                                 monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("step counters computed with no session")

    monkeypatch.setattr(engine_mod, "_step_counters", refuse)
    assert not tracing.active()
    res = engine.serve(_requests(engine), speculate=LOOPS[loop])
    assert res.steps > 0


def test_full_collections_are_spanned(engine, tmp_path):
    assert tracing._COLLECTIONS in gc.callbacks
    tracing.trace_collections()
    assert gc.callbacks.count(tracing._COLLECTIONS) == 1
    gc.collect()                    # no session: nothing opened
    assert tracing._COLLECTIONS._open is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        gc.collect(0)
        gc.collect(1)
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    names = [s[1] for s in _host_spans(str(tmp_path))]
    assert names.count("repro.gc") >= 1
    assert tracing._COLLECTIONS._open is None

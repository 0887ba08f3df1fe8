"""The harness: one run of one cell of `BENCHMARK.json`.

Everything that belongs to one configuration, traffic mix or metric sits
in a file of its own that this module finds by name:

* `configs/<name>.json` — the configuration as it is run ("family",
  "model", "plan", "serving", "max_positions"), beside its published
  keys;
* `families/<name>.py` — a model family: the program's config, the
  served tree, the plain reference and the work of each kernel (see
  `families/__init__.py`);
* `methods/<name>.py` — a plan method, named by the plan's "method": a
  site's shapes, arrays, program node, reference linear, kernel and work
  (see `methods/__init__.py`);
* `traffic/<name>.json` — a mix of request sizes for `traffic.py`;
* `metrics/<name>.py` — one metric, `read(ctx)` returning a number or
  None (nothing to read in this run: the metric is left out);
* `limits/<cell>.json` — the limit of each number `check.py` compares.

A run makes the weights from the seed on the device, builds the engine,
serves warm-up calls that compile every span width the cell can use,
then measures a window of `--seconds` through `InferenceEngine.serve`,
with the device's profiler on over the whole window when `--trace 1`. After the
window it frees the program's state and holds a sample of the served
requests to the reference.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

import check
import devtrace
import families
import traffic
import work

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".perfbench_trace")


class NoChip(RuntimeError):
    """The run found no accelerator it can measure."""


class WindowClosed(Exception):
    """Raised from the token callback to end a job at the window's close."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    config: dict           # the configuration file's contents
    mix: dict              # the traffic file's contents
    chips: int
    end_to_end: list       # metric entries this cell reports
    per_layer: list

    @property
    def cfg(self) -> dict:
        """What the family's tree, reference and work read: model sizes,
        plan and family."""
        return {**self.config["model"], "plan": self.config["plan"],
                "family": self.config["family"]}


def _reports(metric: dict, cell: str, e2e_names=None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def load_cell(name: str) -> Cell:
    bench = load_json(ROOT, "BENCHMARK.json")
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(wl)}")
    w = wl[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(ROOT, conf["file"])
    mix = traffic.load(w["traffic"])
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, config, mix, int(w["chips"]), e2e, per)


def read_metric(name: str, ctx: dict):
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name}", os.path.join(HERE, "metrics",
                                                 f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


# ------------------------------------------------------------ device --
def devices(chips: int):
    """The chips this run measures on, and their published peaks."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    peaks = load_json(HERE, "peaks.json")["devices"]
    kind = devs[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in peaks.json")
    return devs[:chips], peaks[kind]


def configure_jax(cell: Cell) -> None:
    """The checkout's compile cache, and the matmul precision the
    configuration states for its float dots ("highest" runs float32 dots
    in float32; the TPU's default rounds their operands to bfloat16)."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_default_matmul_precision",
                      cell.config["serving"]["matmul_precision"])


# ------------------------------------------------------------ engine --
def build_engine(cell: Cell, seed: int):
    from repro.api import InferenceEngine

    family = families.load(cell.config["family"])
    tree = family.program_tree(seed, cell.cfg)
    s = cell.config["serving"]
    return InferenceEngine.build(
        family.program_config(cell.config), None,
        params=tree, max_batch=s["max_batch"], block_size=s["block_size"],
        chunk_tokens=s["chunk_tokens"])


def call_rows(cell: Cell) -> int:
    """Rows of the serving batch: a call's requests, up to max_batch."""
    return min(cell.config["serving"]["max_batch"], traffic.rows(cell.mix))


def _serve(engine, cell: Cell, reqs, on_token=None):
    from repro.api import Request

    s = cell.config["serving"]
    return engine.serve(
        [Request(tokens=r.prompt, max_tokens=r.max_tokens) for r in reqs],
        max_batch=call_rows(cell), num_blocks=s["num_blocks"],
        on_token=on_token)


def warmup_calls(cell: Cell, rng) -> list:
    """Serve calls that between them run every span width the cell can
    use, at the cell's own rows, block-table width and pool.

    Each step of the serving loop is one program per (rows, span width W,
    block-table width); W is the power of two at or above the step's
    widest span, up to the per-step budget. Call T holds `rows` requests
    whose first steps span exactly T tokens (all of them T-token prompts
    where rows * T fits the budget; else one long prompt whose second
    chunk is T wide while the others decode), then, after them, the
    mix's longest request as one long prompt, which fixes the
    block-table width and takes full-budget chunks."""
    s = cell.config["serving"]
    rows, budget = call_rows(cell), s["chunk_tokens"]
    vocab = cell.config["model"]["vocab_size"]
    p_max, o_max = traffic.longest(cell.mix, cap(cell))
    first = -(-budget // rows)
    tok = lambda n: traffic.tokens(rng, n, vocab)            # noqa: E731
    calls, t = [], 1
    while t < 2 * budget:
        if rows * t <= budget:
            reqs = [traffic.Req(tok(t), 2) for _ in range(rows)]
        else:
            reqs = [traffic.Req(tok(first + min(t, budget - rows + 1)), 1)]
            reqs += [traffic.Req(tok(1), 2) for _ in range(rows - 1)]
        reqs.append(traffic.Req(tok(p_max + o_max - 1), 1))
        calls.append(reqs)
        t *= 2
    return calls


def cap(cell: Cell) -> int | None:
    """The configuration's cap on prompt plus output positions."""
    return cell.config.get("max_positions")


# ------------------------------------------------------------ window --
@dataclasses.dataclass
class Served:
    prompt: np.ndarray
    max_tokens: int
    t_call: float
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.max_tokens


class Recorder:
    """Every served token by request, timed when the engine confirms it."""

    def __init__(self):
        self.reqs: list[Served] = []
        self.on_first_final = None
        self.deadline = None

    def add(self, reqs, t_call: float) -> int:
        base = len(self.reqs)
        self.reqs += [Served(r.prompt, r.max_tokens, t_call) for r in reqs]
        return base

    def callback(self, base: int):
        import jax

        def on_token(ev):
            with jax.profiler.TraceAnnotation("perfbench.on_token"):
                s = self.reqs[base + ev.rid]
                s.times.append(ev.time)
                s.tokens.append(ev.token)
                if ev.final and self.on_first_final is not None:
                    hook, self.on_first_final = self.on_first_final, None
                    hook(ev.time)
                if self.deadline is not None and ev.time >= self.deadline:
                    raise WindowClosed
        return on_token


@dataclasses.dataclass
class Window:
    start: float
    end: float
    rec: Recorder
    trace_span: tuple | None = None    # host (start, stop) of the trace
    compiles: int = 0                  # programs compiled or loaded inside


class Tracer:
    """The device profiler from `start()` to `stop()`, when on."""

    def __init__(self, on: bool):
        self.on = on
        self.span = None

    def start(self) -> None:
        import jax

        if not self.on:
            return
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self.span = (time.time(), None)

    def stop(self) -> None:
        import jax

        if self.span and self.span[1] is None:
            t = time.time()
            jax.profiler.stop_trace()
            self.span = (self.span[0], t)


class CompileCounter:
    """Programs compiled, or loaded from the persistent cache, while on."""

    def __init__(self):
        import jax

        self.on, self.count = False, 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_) -> None:
        if self.on and name == "/jax/compilation_cache/cache_hits":
            self.count += 1

    def _duration(self, name: str, _secs: float, **_) -> None:
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def window_bursts(engine, cell: Cell, seed: int, seconds: float,
                  tracer: Tracer) -> Window:
    import jax

    vocab = cell.config["model"]["vocab_size"]
    rec = Recorder()
    gen = traffic.bursts(cell.mix, seed, vocab, cap(cell))
    tracer.start()
    start = time.time()
    while time.time() < start + seconds:
        with jax.profiler.TraceAnnotation("perfbench.make_requests"):
            burst = next(gen)
        t_call = time.time()
        base = rec.add(burst, t_call)
        with jax.profiler.TraceAnnotation("perfbench.serve"):
            _serve(engine, cell, burst, rec.callback(base))
    end = time.time()
    tracer.stop()
    return Window(start, end, rec, tracer.span)


def window_job(engine, cell: Cell, seed: int, seconds: float,
               tracer: Tracer) -> Window:
    """One serve call of the whole job. The window opens at the first
    completed request, once admission trickles, and the token callback
    ends the call `seconds` later."""
    import jax

    vocab = cell.config["model"]["vocab_size"]
    rec = Recorder()
    with jax.profiler.TraceAnnotation("perfbench.make_requests"):
        reqs = traffic.job(cell.mix, seed, vocab, cap(cell))
    opened = []

    def on_open(t):
        opened.append(t)
        rec.deadline = t + seconds
        tracer.start()

    rec.on_first_final = on_open
    base = rec.add(reqs, time.time())
    finished_early = False
    try:
        with jax.profiler.TraceAnnotation("perfbench.serve"):
            _serve(engine, cell, reqs, rec.callback(base))
        finished_early = True
    except WindowClosed:
        pass
    tracer.stop()
    if not opened:
        raise RuntimeError("the job finished no request")
    end = (max(max(s.times) for s in rec.reqs if s.times) if finished_early
           else opened[0] + seconds)
    return Window(opened[0], end, rec, tracer.span)


WINDOWS = {"bursts": window_bursts, "job": window_job}


# ---------------------------------------------------------- accounting --
def in_window(w: Window, lo: float, hi: float) -> work.Tokens:
    """Valid rows processed between host times lo and hi: the prompt of
    every request whose first token came in the interval, and each served
    token after the first (it was fed back as a decode input); by request
    too, with its token ids and the step of each row, a step known by the
    time its tokens were read back."""
    prompts, decode, emitted, held = [], [], 0, []
    for s in w.rec.reqs:
        p = len(s.prompt)
        pos, when = [], []
        for j, t in enumerate(s.times):
            if lo <= t < hi:
                emitted += 1
                if j == 0:
                    prompts.append(p)
                    pos += range(p)
                    when += [t] * p
                else:
                    decode.append(p + j - 1)
                    pos.append(p + j - 1)
                    when.append(t)
        if pos:
            held.append((s, pos, when))
    step = {t: i for i, t in enumerate(sorted(
        {t for _, _, when in held for t in when}))}
    reqs = [work.Rows(np.concatenate([s.prompt, s.tokens]).astype(np.int32),
                      pos, [step[t] for t in when])
            for s, pos, when in held]
    return work.Tokens(prompts, decode, emitted, reqs)


# ---------------------------------------------------------------- run --
def log(msg: str) -> None:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


def setup(cell: Cell, seed: int, t0: float):
    """Weights, engine and every program the window runs, warmed."""
    engine = build_engine(cell, seed)
    log(f"weights and engine at {time.time() - t0:.1f}s")
    rng = np.random.default_rng([seed, 4])
    for reqs in warmup_calls(cell, rng):
        _serve(engine, cell, reqs)
    log(f"warm-up done at {time.time() - t0:.1f}s")
    return engine


def run(name: str, seed: int, seconds: float, trace: bool,
        t0: float) -> dict:
    cell = load_cell(name)
    devs, peaks = devices(cell.chips)
    configure_jax(cell)
    counter = CompileCounter()
    engine = setup(cell, seed, t0)
    weight_bytes = engine.weight_hbm_bytes()
    setup_s = time.time() - t0

    counter.on = True
    tracer = Tracer(trace)
    w = WINDOWS[cell.mix["kind"]](engine, cell, seed, seconds, tracer)
    counter.on = False
    w.compiles = counter.count
    mem = devs[0].memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    del engine
    gc.collect()
    log(f"window {w.end - w.start:.1f}s, {w.compiles} programs compiled "
        f"or loaded inside it")

    finished = [(s.prompt, np.asarray(s.tokens, np.int32))
                for s in w.rec.reqs if s.done and s.times[-1] < w.end]
    vocab = cell.config["model"]["vocab_size"]
    attempted = [s for s in w.rec.reqs
                 if any(w.start <= t < w.end for t in s.times)
                 or (cell.mix["kind"] == "bursts" and s.t_call < w.end)]
    failed = sum(1 for s in attempted if s.done and not check.well_formed(
        s.tokens, s.max_tokens, vocab))
    if cell.mix["kind"] == "bursts":
        failed += sum(1 for s in attempted if not s.done)
    items = check.sample(finished, cell.mix["sample_requests"], seed)
    got = check.readings(seed, cell.cfg, items)["served"]
    log(f"reference over {got['tokens']} served tokens done at "
        f"{time.time() - t0:.1f}s")
    compared, within = check.judge(check.load_limits(cell.name), got)
    correct = failed == 0 and bool(items) and within

    ctx = {"cell": cell, "window": w, "setup_s": setup_s, "seed": seed,
           "weight_hbm_bytes": weight_bytes, "peaks": peaks, "cfg": cell.cfg}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": len(attempted),
              "failed": failed, "compiles_in_window": w.compiles}
    if trace:
        red = devtrace.reduce(
            devtrace.load(TRACE_DIR), n_devices=len(devs),
            default_span=("perfbench.serve" if cell.mix["kind"] == "job"
                          else "outside benchmark spans"))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx["trace"] = red
        ctx["trace_tokens"] = in_window(w, *w.trace_span)
        metrics = cell.per_layer
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = red.breakdown()
    else:
        metrics = cell.end_to_end
    values = {}
    for m in metrics:
        v = read_metric(m["name"], ctx)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    result.update(metrics=values, device=device, check=compared)
    for k, c in compared.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return result

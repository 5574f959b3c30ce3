"""The benchmark's inner entry: one run of one cell, as a result dict.

``run_cell`` builds the program's serving engine (the pooled emulated-memory
KV store behind ``repro.serve.engine.ServeEngine``, driven by its
``Scheduler``), warms it up, drives it with the cell's traffic for a window
of ``seconds``, checks what it served against the plain reference
(``bench.reference``), and reduces its spans, counters and (with ``trace``)
the profiler's trace to the cell's metrics.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name in
``BENCHMARK.json``: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``, ``bench/metrics/<metric>.py``; and what
belongs to one architecture is ``bench/archs/<model_type>.py``, found by
the configuration file's ``model_type``.

Host times are the benchmark's own spans around ``ServeEngine.admit`` and
``ServeEngine.step``; both end in a host read of sampled tokens, so they
are synchronized with the device.  A token is visible when the call that
sampled it returns.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import reference, traffic, work
from bench import weights as W

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
#: sampled requests for the check: at least and at most this many, the
#: longest first
CHECK_MIN_REQUESTS, CHECK_MAX_REQUESTS = 3, 16
#: a closed-loop warm-up ends after this many ticks in a row that traced no
#: program: the program retraces its step for each mix of committed and
#: fresh input arrays it meets, and each mix has to be met before the window
QUIET_TICKS = 16


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list


def load(workload: str, spec_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json``, with its configuration,
    mix and metrics read from their own files."""
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return Cell(name=workload, chips=w["chips"],
                config=read_config(w["config"]),
                mix=read_mix(w["traffic"]),
                end_to_end=[m for m in spec["end_to_end"] if mine(m)],
                per_layer=[m for m in spec["per_layer"] if mine(m)])


def read_config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def read_mix(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def chip_cell(workload: str, log=sys.stderr) -> Cell | None:
    """The cell ``workload``, with JAX's compilation cache on, if JAX finds
    a TPU with as many chips as the cell asks for; else None, said on
    ``log``."""
    from repro.launch.compile_cache import enable_compile_cache
    cell = load(workload)
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: no device: {e}", file=log)
        return None
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=log)
        return None
    return cell


# -- the program ------------------------------------------------------------

def build_engine(c: dict, w: dict):
    """(engine, scheduler) as ``repro.launch.serve.build`` and ``run``
    make them, with the benchmark's weights: the entry point's parsed
    defaults, the KV mesh context for ``kv_shards``, the engine config.
    The model's config and parameter tree come from the configuration's
    architecture module (``bench/archs/<model_type>.py``)."""
    import jax

    from repro.launch import serve
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import Model
    from repro.parallel import mesh_ctx
    from repro.serve.engine import EngineConfig, ServeEngine
    from repro.serve.scheduler import Scheduler, SchedulerConfig
    sv = c["serving"]
    args = serve.parse_args([
        "--arch", c["arch"], "--kv-layout", sv["kv_layout"],
        "--page-slots", str(sv["page_slots"]),
        "--paged-kernel", sv["paged_kernel"], "--slots", str(sv["slots"]),
        "--max-len", str(sv["max_len"]), "--kv-shards", str(sv["kv_shards"]),
        "--retain-frames", str(sv["retain_frames"]),
        "--max-fused-steps", str(sv["max_fused_steps"])])
    arch = work.arch(c)
    cfg = arch.model_config(c)
    if args.kv_shards > 1:
        mesh_ctx.set_context(make_host_mesh(args.kv_shards),
                             kv_axes=("data",))
    else:
        mesh_ctx.clear_context()
    model = Model(cfg)
    params = arch.program_params(w, cfg)
    want = jax.tree.map(lambda s: (s.shape, s.dtype), model.shapes())
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise ValueError(f"weights do not fit the program's tree: {got} "
                         f"against {want}")
    engine = ServeEngine(model, params, EngineConfig(
        slots=args.slots, max_len=args.max_len,
        preempt_mode=args.preempt_mode, retain_frames=args.retain_frames,
        host_frames=args.host_frames, spill_frames=args.spill_frames,
        spill_path=args.spill_path, max_fused_steps=args.max_fused_steps,
        prefix_index=args.prefix_index))
    sched = Scheduler(engine, SchedulerConfig(window=args.sched_window,
                                              aging_steps=args.aging_steps))
    return engine, sched


# -- spans and token times ---------------------------------------------------

@dataclasses.dataclass
class Span:
    """One host span: ``name``, start and end (``time.perf_counter``),
    the decode dispatch iterations it ran with the context length of each
    sequence each one decoded (``ctxs``), for admissions the prompt and
    shared-prefix tokens, and the device frames of the pool allocated
    (to live sequences and to retained prefixes) and the slots in use at
    its end."""
    name: str
    t0: float
    t1: float
    ctxs: list = dataclasses.field(default_factory=list)
    prompt: int = 0
    shared: int = 0
    traced: bool = False
    frames: int = 0
    slots: int = 0


def visible(req) -> int:
    """Tokens of ``req`` the host has seen: its output, plus the sampled
    next token while it is still running."""
    return len(req.output) + (0 if req.done or not hasattr(req, "_next")
                              else 1)


class Recorder:
    """Wraps ``engine.admit`` and ``engine.step`` on the instance with
    spans, and records when each request's tokens became visible."""

    def __init__(self, engine, annotate: bool):
        self.engine = engine
        self.annotate = annotate
        self.traced = False
        self.spans: list[Span] = []
        self.times: dict[int, list] = {}     # id(req) -> [(t, n tokens)]
        self.done_at: dict[int, float] = {}
        self._admit, self._step = engine.admit, engine.step
        engine.admit, engine.step = self.admit, self.step

    def close(self) -> None:
        """Unwrap the engine (the wrappers hold it)."""
        del self.engine.admit, self.engine.step
        self.engine = None

    @contextlib.contextmanager
    def span(self, name: str):
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation("bench." + name):
                yield
        else:
            yield

    def _occupancy(self) -> dict:
        e = self.engine
        blocks = getattr(e, "blocks", None)
        return {"frames": blocks.allocator.used_count() if blocks else 0,
                "slots": sum(r is not None for r in e.slot_req)}

    def _saw(self, req, before: int, t: float) -> None:
        n = visible(req) - before
        if n > 0:
            self.times.setdefault(id(req), []).append((t, n))
        if req.done and id(req) not in self.done_at:
            self.done_at[id(req)] = t

    def admit(self, req, slot):
        e = self.engine
        d0 = e.counters["decode_steps"]
        s0 = e.counters["shared_prompt_tokens"]
        before = visible(req)
        t0 = time.perf_counter()
        with self.span("admit"):
            self._admit(req, slot)
        t1 = time.perf_counter()
        n = e.counters["decode_steps"] - d0
        total = len(req.prompt) + len(req.output)
        self.spans.append(Span(
            "admit", t0, t1, ctxs=[[total - n + 1 + i] for i in range(n)],
            prompt=len(req.prompt),
            shared=e.counters["shared_prompt_tokens"] - s0,
            traced=self.traced, **self._occupancy()))
        self._saw(req, before, t1)

    def step(self, max_steps=None):
        e = self.engine
        active = [r for r in e.slot_req if r is not None]
        base = [len(r.prompt) + len(r.output) for r in active]
        before = [visible(r) for r in active]
        t0 = time.perf_counter()
        with self.span("step"):
            n = self._step(max_steps)
        t1 = time.perf_counter()
        self.spans.append(Span("step", t0, t1,
                               ctxs=[[b + k + 1 for b in base]
                                     for k in range(n)],
                               traced=self.traced, **self._occupancy()))
        for r, b in zip(active, before):
            self._saw(r, b, t1)
        return n


# -- driving the traffic -----------------------------------------------------

class Driver:
    """Submits requests as the mix says and ticks the scheduler."""

    def __init__(self, engine, sched, rec: Recorder):
        from repro.serve.engine import Request
        self.Request = Request
        self.engine, self.sched, self.rec = engine, sched, rec
        self.due: dict[int, float] = {}       # id(req) -> due time
        self.sent: dict[int, float] = {}      # id(req) -> submit time
        self.reqs: list = []
        self.failed: list = []
        self.n_done = 0
        self.uid = 0
        self.items: list = []              # open loop: the schedule,
        self.next = 0                      # the next item to send,
        self.t_open = 0.0                  # and the time due_s counts from
        self.at_open = self.at_close = None   # open loop: backlog()
        self.queues: list = []
        self.client: dict[int, int] = {}   # id(req) -> closed-loop client

    def send(self, item, due: float):
        req = self.Request(uid=self.uid, prompt=item.prompt,
                           max_new_tokens=item.max_new)
        self.uid += 1
        self.due[id(req)] = due
        self.sent[id(req)] = time.perf_counter()
        self.reqs.append(req)
        self.sched.submit([req])
        return req

    def backlog(self, now: float) -> dict:
        """Open loop: requests due by ``now`` and not yet admitted (queued,
        or never sent because a tick ran past their due time), and
        requests in slots."""
        late = sum(1 for it in self.items[self.next:]
                   if self.t_open + it.due_s <= now)
        return {"waiting": len(self.sched.queue) + late,
                "in_slots": sum(r is not None
                                for r in self.engine.slot_req)}

    def unsent(self, t1: float) -> list:
        """Open loop: due times by ``t1`` of the requests never sent."""
        return [self.t_open + it.due_s for it in self.items[self.next:]
                if self.t_open + it.due_s <= t1]

    def start_open(self, items: list, t_open: float) -> None:
        """Open loop: ``items`` fall due ``due_s`` seconds after
        ``t_open``."""
        self.items, self.next, self.t_open = list(items), 0, t_open

    def open_loop(self, t_stop: float) -> None:
        """Send each request when it falls due and tick, until
        ``t_stop``; sleep while the engine is idle."""
        items = self.items
        while True:
            now = time.perf_counter()
            if now >= t_stop:
                return
            while (self.next < len(items)
                   and self.t_open + items[self.next].due_s <= now):
                it = items[self.next]
                self.send(it, self.t_open + it.due_s)
                self.next += 1
            if self.idle():
                nxt = (self.t_open + items[self.next].due_s
                       if self.next < len(items) else t_stop)
                with self.rec.span("wait"):
                    time.sleep(max(0.0, min(nxt, t_stop) - now))
                continue
            self.tick()

    def idle(self) -> bool:
        return not self.sched.queue and all(
            r is None for r in self.engine.slot_req)

    def tick(self) -> list:
        """One scheduler tick; returns the requests it completed.  A head
        request the scheduler can never admit (it returned no progress
        with an empty engine) is refused and counted failed."""
        with self.rec.span("tick"):
            progressed = self.sched.tick()
        if not progressed and self.sched.queue and all(
                r is None for r in self.engine.slot_req):
            self.failed.append(self.sched.queue.popleft())
        done = self.sched.completed[self.n_done:]
        self.n_done = len(self.sched.completed)
        return done

    def drain(self) -> None:
        while not self.idle():
            self.tick()

    def start_clients(self, queues: list) -> None:
        """Closed loop: each client sends its first request."""
        self.queues = [list(q) for q in queues]
        for c in range(len(self.queues)):
            self.send_next(c)

    def send_next(self, c: int) -> None:
        if not self.queues[c]:
            raise RuntimeError(f"client {c} ran out of requests: raise the "
                               f"mix's requests_per_client")
        req = self.send(self.queues[c].pop(0), time.perf_counter())
        self.client[id(req)] = c

    def closed_loop(self, t_end: float) -> None:
        """Tick until ``t_end``; the client of each completed request
        sends its next one at once."""
        while time.perf_counter() < t_end:
            self.closed_tick()

    def closed_tick(self) -> None:
        for req in self.tick():
            self.send_next(self.client.pop(id(req)))


class Tracer:
    """Traces the whole window (started before its first tick, stopped
    after its last) and reduces what the profiler wrote."""

    def __init__(self, rec: Recorder):
        self.rec = rec
        self.dir = None
        self.on = False

    def start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(self.dir)
        self.on = self.rec.traced = True

    def stop(self) -> None:
        import jax
        if self.on:
            jax.profiler.stop_trace()
            self.on = self.rec.traced = False

    def summary(self):
        from bench import trace as T
        if self.dir is None:
            return None
        try:
            devices, spans = T.read_xplane(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        if not devices or not spans:
            return None
        lo = min(s for s, _, _ in spans)
        hi = max(e for _, e, _ in spans)
        return T.reduce(devices, spans, (lo, hi))


def run_window(drv: Driver, loop: str, seconds: float, tracer=None):
    """Drive the window (under ``tracer``, if given); returns (t0, t1)."""
    if loop == "open":
        drv.at_open = drv.backlog(time.perf_counter())
    if tracer:
        tracer.start()
    t0 = time.perf_counter()
    t_end = t0 + seconds
    if loop == "open":
        drv.open_loop(t_end)
    else:
        drv.closed_loop(t_end)
    t1 = time.perf_counter()
    if tracer:
        tracer.stop()
    if loop == "open":
        drv.at_close = drv.backlog(t1)
    return t0, t1


# -- the run -----------------------------------------------------------------

def percentile(xs, q: float) -> float | None:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) \
        else None


#: JAX's events for a program traced (a jit cache miss in this process)
#: and for a program compiled (a miss in the persistent cache too)
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def _compile_counter():
    """A list that grows by the name of every program JAX traces or
    compiles while the context is open."""
    import jax
    events: list = []

    def listener(event: str, duration: float, fun_name: str = "?",
                 **_) -> None:
        if event in (TRACE_EVENT, COMPILE_EVENT):
            events.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield events
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def load_metric(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader gets."""
    config: dict
    spans: list             # Span, inside the window
    window_s: float
    trace: object           # bench.trace.Summary, or None
    peak: dict | None       # bench/peaks.json entry of this device
    n_chips: int
    memory_peak_bytes: int

    @property
    def traced_spans(self) -> list:
        return [s for s in self.spans if s.traced]


def _check_sample(drv: Driver, rec: Recorder, t0: float, t1: float,
                  seed: int, want_tokens: int) -> list:
    """Requests finished in the window, drawn from the seed: the longest,
    then others until ``want_tokens`` served tokens and at least
    ``CHECK_MIN_REQUESTS`` requests."""
    done = [r for r in drv.reqs
            if t0 <= rec.done_at.get(id(r), -1.0) <= t1]
    if not done:
        return []
    done.sort(key=lambda r: -(len(r.prompt) + len(r.output)))
    rest = done[1:]
    rng = np.random.default_rng([seed, 1])
    pick = [done[0]] + [rest[i] for i in rng.permutation(len(rest))]
    out, n = [], 0
    for r in pick[:CHECK_MAX_REQUESTS]:
        out.append(r)
        n += len(r.output)
        if n >= want_tokens and len(out) >= CHECK_MIN_REQUESTS:
            break
    return out


def _crossing_warmup(c: dict, mix: dict, seed: int) -> list:
    """Closed loop: ``max_fused_steps`` requests served together before the
    clients start.  Their prompts differ by one token each and their outputs
    end just past the first page boundary, one step apart, so a page
    boundary falls at every step of a fused run and the requests complete on
    consecutive steps with none admitted after them.  A fused run then
    follows a table push (fresh tables, committed lengths): an input mix
    the program compiles for, which the window's long outputs meet and the
    clients' first minute of short contexts does not."""
    sv = c["serving"]
    n = min(sv["max_fused_steps"], sv["slots"])
    p0 = mix["prompt"]["min"]
    rng = np.random.default_rng([seed, 4])
    return [traffic.Item(0.0, rng.integers(0, c["vocab_size"], p0 + j)
                         .astype(np.int32), sv["page_slots"] - p0 + n + j)
            for j in range(n)]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             process_start: float, peak_table: dict | None = None,
             log=sys.stderr, control: bool = False,
             detail: bool = False) -> dict:
    """One run of ``cell``; returns the result line as a dict.
    ``peak_table`` replaces ``bench/peaks.json``.  With ``control`` the
    control takes the program's place in the check (``correct`` then
    reads the control against the limit); with ``detail`` the result also
    holds, before ``checks``, a ``detail`` entry: the program's gap, the
    backlog at the window's open and close, and the counters."""
    def say(msg):
        print(f"bench: {msg}", file=log, flush=True)

    with _compile_counter() as compiles:
        return _run(cell, seed, seconds, trace, process_start, peak_table,
                    say, control, detail, compiles)


def _run(cell, seed, seconds, trace, process_start, peak_table, say,
         control, detail, compiles) -> dict:
    import jax
    c, mix = cell.config, cell.mix
    devs = jax.devices()
    dev = devs[0]
    marks = [(None, process_start, 0)]

    def mark(phase: str) -> None:
        marks.append((phase, time.perf_counter(), len(compiles)))

    mark("start-up")
    w = W.make(c, seed, c["torch_dtype"])
    engine, sched = build_engine(c, w)
    del w
    mark("weights and engine")
    rec = Recorder(engine, annotate=trace)
    drv = Driver(engine, sched, rec)
    sv = c["serving"]
    warm_new = 2 * sv["max_fused_steps"] + 1
    sch = traffic.schedule(mix, seed, seconds, c["vocab_size"], warm_new)

    # set-up: warm every shape the window uses.  Two requests that share a
    # part of a page go first: prompts that share a first token share a
    # partial page, and the copy-on-write that follows is a path the
    # window's traffic can take.
    rng = np.random.default_rng([seed, 3])
    base = rng.integers(0, c["vocab_size"], mix["prompt"]["min"] - 1)
    for last in rng.choice(c["vocab_size"], 2, replace=False):
        drv.send(traffic.Item(0.0, np.append(base, last).astype(np.int32),
                              warm_new), time.perf_counter())
    drv.drain()
    mark("first requests")
    if sch.loop == "open":
        for item in sch.warm:
            drv.send(item, time.perf_counter())
        drv.drain()
        mark("shared prompts")
        drv.reqs.clear()
        # the lead: the requests due before the window, served as they
        # fall due, so that the window opens on a loaded engine
        t_open = time.perf_counter() + mix.get("lead_s", 0)
        drv.start_open(sch.items, t_open)
        drv.open_loop(t_open)
    else:
        for item in _crossing_warmup(c, mix, seed):
            drv.send(item, time.perf_counter())
        drv.drain()
        mark("page crossings")
        drv.start_clients(sch.items)
        drv.closed_loop(time.perf_counter() + mix["warmup_s"])
        mark("clients' warm-up")
        quiet = 0
        while quiet < QUIET_TICKS:
            n = len(compiles)
            drv.closed_tick()
            quiet = quiet + 1 if len(compiles) == n else 0
    mark("lead" if sch.loop == "open" else "quiet ticks")
    n_compiles = len(compiles)
    counters0 = dict(engine.counters)

    tracer = Tracer(rec) if trace else None
    t0, t1 = run_window(drv, sch.loop, seconds, tracer)
    setup_s = t0 - process_start
    window_s = t1 - t0
    say(f"window {window_s:.3f} s after {setup_s:.3f} s of set-up; "
        f"compiles in set-up {n_compiles}, in the window "
        f"{len(compiles) - n_compiles}")
    say("set-up by phase (seconds, programs traced): " + ", ".join(
        f"{name} {t - t_prev:.3f} {n - n_prev}"
        for (_, t_prev, n_prev), (name, t, n) in zip(marks, marks[1:])))
    traced = collections.Counter(compiles[n_compiles:])
    if traced:
        say(f"programs traced in the window: {traced.most_common(10)}")

    # end-to-end numbers.  The window's requests are those due from its
    # nominal opening (open loop: the end of the lead; the window itself
    # opens at the first tick boundary after it) to its close.
    start = drv.t_open if sch.loop == "open" else t0
    in_window = [r for r in drv.reqs if start <= drv.due[id(r)] <= t1]
    ttft, itl, tokens = [], [], 0
    for r in in_window:
        ts = rec.times.get(id(r))
        ttft.append((ts[0][0] if ts and ts[0][0] <= t1 else t1)
                    - drv.due[id(r)])
    # requests that fell due while the window's last tick ran were never
    # sent: they count, with the time they waited until the close
    unsent = drv.unsent(t1) if sch.loop == "open" else []
    ttft += [t1 - due for due in unsent]
    for r in drv.reqs:
        prev = None
        for t, n in rec.times.get(id(r), []):
            if t0 <= t <= t1:
                tokens += n
                if prev is not None:
                    itl += [t - prev] + [0.0] * (n - 1)
                else:
                    itl += [0.0] * (n - 1)
            prev = t
    late = [drv.sent[id(r)] - drv.due[id(r)] for r in in_window
            if id(r) in drv.sent]
    if sch.loop == "open":
        late_total = max(late) if late else 0.0
        say(f"generator lateness: max {late_total:.4f} s over "
            f"{len(late)} sends; {len(unsent)} due in the window's last "
            f"tick and never sent")
    e2e = {"tokens_per_s": tokens / window_s,
           "ttft_p90_s": percentile(ttft, 90),
           "itl_p95_s": percentile(itl, 95),
           "setup_s": setup_s}
    done = [r for r in drv.reqs if t0 <= rec.done_at.get(id(r), -1.0) <= t1]
    occupied = [s for s in rec.spans if t0 <= s.t1 <= t1]
    frames_peak = max((s.frames for s in occupied), default=0)
    slots_peak = max((s.slots for s in occupied), default=0)
    say(f"{len(ttft)} requests due in the window, {len(done)} finished in "
        f"it, {tokens} tokens, {len(itl)} inter-token gaps; at most "
        f"{slots_peak} slots in use and {frames_peak} frames allocated; "
        f"{e2e}")
    half = [[], []]
    for r, t in zip(in_window, ttft):
        half[drv.due[id(r)] >= start + window_s / 2].append(t)
    mean_halves = [float(np.mean(h)) if h else None for h in half]
    drv_at = (drv.at_open, drv.at_close)
    if sch.loop == "open":
        say(f"backlog (due and not admitted / in slots) at the open "
            f"{drv.at_open}, at the close {drv.at_close}; mean TTFT of "
            f"requests due in the first and second half of the window: "
            f"{mean_halves[0]} s, {mean_halves[1]} s")

    # memory, then free the program's state before the reference
    mem = memory_peak_bytes()
    sample = _check_sample(drv, rec, t0, t1, seed, mix["check_tokens"])
    served = [(np.array(r.prompt), list(r.output)) for r in sample]
    failed = sum(1 for r in drv.failed if start <= drv.due[id(r)] <= t1)
    short = sum(1 for r in drv.reqs if id(r) in rec.done_at
                and len(r.output) != r.max_new_tokens)
    spans = [s for s in rec.spans if s.t0 >= t0 and s.t1 <= t1]
    counters = {k: engine.counters[k] - counters0.get(k, 0)
                for k in engine.counters}
    engine.shutdown(abort=True)
    rec.close()
    from repro.parallel import mesh_ctx
    mesh_ctx.clear_context()
    del engine, sched, drv, rec
    gc.collect()

    limit = c["check"]["max_logit_gap"]
    t_ref = time.perf_counter()
    gap, n_tok = (reference.max_logit_gap(c, seed, served) if served
                  else (None, 0))
    say(f"reference over {len(served)} requests, {n_tok} served tokens, "
        f"in {time.perf_counter() - t_ref:.3f} s")
    checked = gap
    if control:
        # the control in the program's place: the float8 reference's first
        # pick at each position of the same prompts and served tokens
        checked, _ = (reference.max_logit_gap(c, seed, served, control=True)
                      if served else (None, 0))
        say(f"control: the float8 reference's picks read {checked}; the "
            f"program's own tokens {gap}")
    correct = (checked is not None and limit is not None and checked <= limit
               and short == 0)
    checks = {"max_logit_gap": {"value": checked, "limit": limit},
              "short_outputs": {"value": short, "limit": 0}}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": bool(correct), "attempted": len(ttft),
              "failed": failed}
    result["metrics"] = {}
    if trace:
        summary = tracer.summary() if tracer else None
        peak = (work.peaks(dev.device_kind, peak_table)
                if dev.platform != "cpu" or peak_table else None)
        view = RunView(c, spans, window_s, summary, peak, len(devs), mem)
        for m in cell.per_layer:
            v = load_metric(m["name"]).read(view)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        device["busy_s"] = summary.busy_s if summary else 0.0
        device["window_s"] = summary.window_s if summary else 0.0
        if summary:
            result["breakdown"] = summary.breakdown()
    else:
        for m in cell.end_to_end:
            v = e2e.get(m["name"])
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
    result["device"] = device
    if detail:
        result["detail"] = {
            "program_gap": gap, "checked_tokens": n_tok,
            "checked_requests": len(served), "finished": len(done),
            "slots_peak": slots_peak, "frames_peak": frames_peak,
            "at_open": drv_at[0], "at_close": drv_at[1],
            "mean_ttft_halves": mean_halves, "e2e": e2e,
            "counters": counters}
    result["checks"] = checks
    say(f"counters over the window: {counters}")
    for name, ch in checks.items():
        say(f"check {name}: {ch['value']} (limit {ch['limit']})")
    return result

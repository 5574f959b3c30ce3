"""Host spans of the serving engine (``Telemetry.span``) and the wall-clock
fields of the request traces: off by default and then free of records and
profiler annotations; on, a well-nested tree whose counts agree with the
engine's counters; and the seconds beside the decode steps."""
import jax
import numpy as np
import pytest

from conftest import tiny_dense_cfg
from repro.models import Model
from repro.serve.telemetry import Telemetry, step_summary

#: the spans of the engine and scheduler (serve/engine.py docstring)
NAMES = {"schedule", "admit", "prefix_match", "grow", "sync_tables",
         "inputs", "dispatch", "device_wait", "step", "stage_run", "replay",
         "page_io"}


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``; records the names
    entered and left."""

    def __init__(self):
        self.entered, self.left = [], []

    def __call__(self, name):
        notes = self

        class _Note:
            def __enter__(self):
                notes.entered.append(name)

            def __exit__(self, *exc):
                notes.left.append(name)
        return _Note()


@pytest.fixture
def notes(monkeypatch):
    n = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", n)
    return n


def _serve(rng, spans: bool, max_fused_steps: int = 8, pool_pages=24,
           slots=2, n=4, **ecfg_kw):
    """Four requests with a shared system prompt through the scheduler;
    returns (requests, engine after shutdown, counters before serving)."""
    from repro.serve import EngineConfig, Request, Scheduler, ServeEngine
    cfg = tiny_dense_cfg(vocab_size=64, kv_layout="pooled", kv_page_slots=4,
                         kv_pool_pages=pool_pages)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, EngineConfig(
        slots=slots, max_len=32, max_fused_steps=max_fused_steps,
        **ecfg_kw))
    system = rng.integers(0, 64, 6).astype(np.int32)
    reqs = [Request(uid=10 + i, prompt=np.concatenate(
        [system, rng.integers(0, 64, 1 + i).astype(np.int32)]),
        max_new_tokens=6) for i in range(n)]
    if spans:
        engine.metrics.start_spans()
    counters0 = dict(engine.counters)
    sched = Scheduler(engine)
    sched.submit(reqs)
    sched.run()
    engine.metrics.stop_spans()
    engine.shutdown()
    return reqs, engine, counters0


def _inside(spans, child) -> bool:
    p = spans[child.parent]
    return p.t0_ns <= child.t0_ns and child.t1_ns <= p.t1_ns


def _descendants(spans, i):
    """Indices of the spans under span ``i``."""
    out, mine = [], {i}
    for j in range(i + 1, len(spans)):
        if spans[j].parent in mine:
            mine.add(j)
            out.append(j)
    return out


# -- the recorder -------------------------------------------------------------
def test_span_off_is_one_shared_noop(notes):
    tel = Telemetry()
    a, b = tel.span("admit", uid=3, prompt=5), tel.span("step")
    assert a is b
    with a as sp:
        sp["prefilled"] = 7
    assert tel.spans == [] and notes.entered == []


def test_span_on_nests_and_closes(notes):
    tel = Telemetry()
    tel.start_spans()
    with tel.span("admit", uid=4, prompt=3) as sp:
        with tel.span("grow"):
            pass
        with pytest.raises(KeyError):
            with tel.span("dispatch", steps=1):
                raise KeyError("an exception still closes the span")
        sp["prefilled"] = 2
    with tel.span("step"):
        pass
    tel.stop_spans()
    with tel.span("step"):
        pass
    assert [s.name for s in tel.spans] == ["admit", "grow", "dispatch",
                                          "step"]
    assert [s.parent for s in tel.spans] == [None, 0, 0, None]
    assert tel.spans[0].uid == 4
    assert tel.spans[0].counts == {"prompt": 3, "prefilled": 2}
    assert tel.spans[2].counts == {"steps": 1}
    assert all(s.t1_ns is not None and s.t1_ns >= s.t0_ns
               for s in tel.spans)
    assert notes.entered == ["serve.admit", "serve.grow", "serve.dispatch",
                             "serve.step"]
    assert sorted(notes.left) == sorted(notes.entered)
    assert tel._top is None


# -- spans in the engine ------------------------------------------------------
def test_engine_spans_off_record_nothing(rng, notes):
    reqs, engine, _ = _serve(rng, spans=False)
    assert all(r.done for r in reqs)
    assert engine.metrics.spans == []
    assert notes.entered == []


def test_engine_spans_form_a_tree(rng, notes):
    reqs, engine, _ = _serve(rng, spans=True)
    spans = engine.metrics.spans
    assert {s.name for s in spans} >= NAMES - {"page_io"}
    assert {s.name for s in spans} <= NAMES
    for i, s in enumerate(spans):
        assert s.t1_ns is not None and s.t0_ns <= s.t1_ns, s
        if s.parent is not None:
            assert s.parent < i and _inside(spans, s), s
    assert [n[len("serve."):] for n in notes.entered] == \
        [s.name for s in spans]
    uids = {r.uid for r in reqs}
    admits = [s for s in spans if s.name == "admit"]
    assert {s.uid for s in admits} == uids
    assert all(s.uid is None for s in spans if s.name != "admit")
    # every admission runs inside a scheduling pass; engine work inside an
    # admission or a step
    assert all(spans[s.parent].name == "schedule" for s in admits)
    for s in spans:
        if s.name not in ("schedule", "admit", "step"):
            assert s.parent is not None, s


def test_admit_span_counts_its_prefill(rng):
    reqs, engine, c0 = _serve(rng, spans=True)
    spans = engine.metrics.spans
    admits = [i for i, s in enumerate(spans) if s.name == "admit"]
    for i in admits:
        under = [spans[j].name for j in _descendants(spans, i)]
        assert spans[i].counts["prefilled"] == under.count("dispatch") > 0
    prefilled = sum(spans[i].counts["prefilled"] for i in admits)
    assert prefilled == engine.counters["prefill_tokens"] \
        - c0["prefill_tokens"]
    assert sum(spans[i].counts["shared"] for i in admits) == \
        engine.counters["shared_prompt_tokens"] > 0
    steps = [s for s in spans if s.name == "step"]
    assert sum(s.counts["iterations"] for s in steps) + prefilled == \
        engine.counters["decode_steps"]
    assert sum(1 for s in spans if s.name == "dispatch") == \
        engine.counters["dispatches"]
    assert sum(s.counts["bytes"] for s in spans
               if s.name == "sync_tables") == \
        engine.counters["table_push_bytes"] > 0


def test_page_io_spans_under_swap(rng):
    """A pool too small for every sequence swaps pages out and back in:
    those moves are ``page_io`` spans, inside a ``grow`` (swap-out on
    preemption) or a ``prefix_match`` (swap-in on resume)."""
    reqs, engine, _ = _serve(rng, spans=True, pool_pages=8, slots=3, n=3,
                             preempt_mode="swap")
    spans = engine.metrics.spans
    assert engine.counters["swapped"] > 0
    moves = [s for s in spans if s.name == "page_io"]
    assert moves and all(s.counts["pages"] > 0 for s in moves)
    parents = {spans[s.parent].name for s in moves}
    assert parents <= {"grow", "prefix_match"} and "prefix_match" in parents
    grows = [s for s in spans if s.name == "grow"]
    assert sum(s.counts.get("preemptions", 0) for s in grows) == \
        engine.counters["preempted"]


def test_spans_change_no_token(rng):
    """Switching spans on changes what is recorded, never what is served."""
    off, e_off, _ = _serve(np.random.default_rng(1), spans=False)
    on, e_on, _ = _serve(np.random.default_rng(1), spans=True)
    assert [r.output for r in off] == [r.output for r in on]
    assert step_summary(e_off.telemetry()) == step_summary(e_on.telemetry())


# -- the wall clock -----------------------------------------------------------
def test_fused_and_stepwise_see_the_same_tokens(rng):
    fused, _, _ = _serve(np.random.default_rng(2), spans=False)
    step, _, _ = _serve(np.random.default_rng(2), spans=False,
                        max_fused_steps=1)
    for f, s in zip(fused, step):
        assert f.output == s.output
        assert len(f._trace.token_ns) == len(s._trace.token_ns) \
            == len(f._trace.token_steps) == len(f.output)


def test_seconds_agree_with_steps(rng):
    reqs, engine, _ = _serve(rng, spans=False)
    trs = [r._trace for r in reqs]
    for t in trs:
        assert t.ttft_s >= 0 and t.queue_wait_s >= 0
        assert t.arrival_ns <= t.admit_ns <= t.token_ns[0] \
            <= t.completion_ns
        assert t.token_ns == sorted(t.token_ns)
        assert all(g >= 0 for g in t.itl_gaps_s())
    for a in trs:
        for b in trs:
            if a.ttft < b.ttft:
                assert a.ttft_s < b.ttft_s
            if a.queue_wait < b.queue_wait:
                assert a.queue_wait_s < b.queue_wait_s
    summary = engine.telemetry()
    for steps, secs in (("ttft_steps", "ttft_s"), ("itl_steps", "itl_s"),
                        ("queue_wait_steps", "queue_wait_s")):
        assert summary[secs]["n"] == summary[steps]["n"] > 0
        assert 0 <= summary[secs]["p50"] <= summary[secs]["max"]
    assert summary["ttft_s"]["max"] == pytest.approx(
        max(t.ttft_s for t in trs), abs=1e-6)

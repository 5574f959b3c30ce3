"""The harness end to end on the CPU at smoke size, through its inner entry
(``harness.run_cell``), not the command: the last line's keys, the metrics
of each mode, and no program traced inside the window."""
import time

from bench import harness
from bench import trace as T
from bench.tests.smoke import smoke_cell

SEED = 2 ** 31 + 4321
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}
#: peaks for the CPU, so that the traced run reduces the trace it is given
FAKE_PEAKS = {"kinds": {"cpu": {"bf16_flops_per_s": 1e12,
                                "hbm_bytes_per_s": 1e11}}}
#: the two kernels as the TPU trace labels them (``trace.op_label``)
GATHER = T.op_label("%closed_call.31 = (f32[4,2,2,32]{3,2,1,0}, "
                    "f32[4,2,2,1]{3,2,1,0}, f32[4,2,2,1]{3,2,1,0}) "
                    "custom-call(s32[32]{0} %a)")
WRITE = T.op_label("%closed_call.30 = (f32[32,2,16,32]{3,2,1,0}, "
                   "f32[32,2,16,32]{3,2,1,0}) custom-call(s32[32]{0} %a)")


def _run(cell, trace, **kw):
    return harness.run_cell(cell, SEED, 3.0, trace, time.perf_counter(),
                            **kw)


def test_untraced_run_reports_the_end_to_end_metrics():
    cell = smoke_cell("chat")
    r = _run(cell, False)
    assert set(r) == KEYS and list(r)[-1] == "checks"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end:
        got = r["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    assert "busy_s" not in r["device"]
    assert r["checks"]["max_logit_gap"]["value"] <= \
        r["checks"]["max_logit_gap"]["limit"]


def test_traced_run_reports_the_per_layer_metrics(monkeypatch):
    """The CPU trace has no device plane: one is added from the host spans
    it holds, with a kernel and a program event inside each span."""
    real = T.read_xplane

    def with_device(log_dir):
        devices, spans = real(log_dir)
        assert devices == [] and spans
        ops = [(s, s + (e - s) // 2, GATHER) for s, e, n in spans
               if n == "step"]
        ops += [(s, s + (e - s) // 4, WRITE) for s, e, n in spans
                if n in ("step", "admit")]
        mods = [(s, e, "jit_fused_decode_run") for s, e, n in spans
                if n == "step"]
        return [T.Device("/device:TPU:0", ops, mods)], spans

    monkeypatch.setattr(T, "read_xplane", with_device)
    cell = smoke_cell("chat")
    r = _run(cell, True, peak_table=FAKE_PEAKS)
    assert set(r) == KEYS | {"breakdown"} and list(r)[-1] == "checks"
    assert r["correct"] is True
    want = {m["name"] for m in cell.per_layer}
    assert set(r["metrics"]) == want - {"peak_hbm_gb"}   # CPU: no stats
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {n for n, _ in r["breakdown"]["device_ops"]} == {GATHER, WRITE}
    assert 0 < r["metrics"]["shared_prompt_frac"]["value"] < 1


def test_no_program_traced_inside_the_window(monkeypatch):
    counts = {}
    real = harness.run_window

    def counted(drv, *a, **k):
        import jax
        events = []

        def listener(event, duration, **_):
            if event in (harness.TRACE_EVENT, harness.COMPILE_EVENT):
                events.append(event)
        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            return real(drv, *a, **k)
        finally:
            counts["window"] = len(events)
            jax.monitoring.unregister_event_duration_listener(listener)

    monkeypatch.setattr(harness, "run_window", counted)
    _run(smoke_cell("chat"), False)
    assert counts["window"] == 0


def test_closed_loop_warmup_crosses_pages_at_every_phase():
    """The closed loop's set-up serves one fused run's worth of requests
    that need their second page at every step of a run, and complete on
    consecutive steps past the page boundary, within ``max_len``."""
    cell = smoke_cell("pool4")
    sv = cell.config["serving"]
    n, page = sv["max_fused_steps"], sv["page_slots"]
    items = harness._crossing_warmup(cell.config, cell.mix, SEED)
    assert len(items) == n <= sv["slots"]
    assert sorted((page - len(it.prompt)) % n for it in items) == \
        list(range(n))
    first = items[0].max_new
    assert [it.max_new for it in items] == list(range(first, first + n))
    assert all(page < len(it.prompt) + it.max_new <= sv["max_len"]
               for it in items)

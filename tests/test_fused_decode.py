"""Fused multi-step decode (the jitted ``lax.while_loop`` dispatch path):
token and telemetry identity against step-at-a-time dispatch -- including
under swap- and spill-preemption pressure -- the
``BlockManager.stage_fused_run`` staging protocol the fusion gate is built
on (pre-staged boundary prefetches let runs CROSS page boundaries), early
exit at EOS, and the regression pin that the fused engine reproduces the
committed SLO baseline byte-for-byte."""
import json
import os

import jax
import numpy as np
import pytest

from conftest import tiny_dense_cfg
from repro.models import Model
from repro.serve.telemetry import step_summary


def _cfg(pool_pages=None, layout="pooled", page_slots=4):
    return tiny_dense_cfg(vocab_size=64, kv_layout=layout,
                          kv_page_slots=page_slots,
                          kv_pool_pages=pool_pages
                          if layout == "pooled" else None)


def _serve(prompts, layout="pooled", pool_pages=24, page_slots=4,
           max_new=6, slots=4, max_len=32, share=False, **ecfg_kw):
    from repro.serve import EngineConfig, Request, ServeEngine, Scheduler
    cfg = _cfg(pool_pages, layout, page_slots)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params,
                         EngineConfig(slots=slots, max_len=max_len,
                                      **ecfg_kw))
    if layout == "pooled":
        engine.blocks.share_prefixes = share
    sched = Scheduler(engine)
    sched.submit([Request(uid=i, prompt=p, max_new_tokens=max_new)
                  for i, p in enumerate(prompts)])
    done = sched.run()
    stats = engine.shutdown()            # leak detector: raises on leak
    return {r.uid: tuple(r.output) for r in done}, stats


# -- identity: fused vs step-at-a-time ---------------------------------------
def test_fused_matches_stepwise_pooled(rng):
    """Fusion must change WHO drives the decode loop, never what it
    computes: identical tokens, identical decode-step telemetry, and
    strictly fewer Python dispatches when runs actually fuse."""
    prompts = [rng.integers(0, 64, int(rng.integers(2, 7))).astype(np.int32)
               for _ in range(6)]
    kw = dict(pool_pages=16, page_slots=8, max_new=10, slots=4)
    fused, st_f = _serve(prompts, max_fused_steps=8, **kw)
    step, st_s = _serve(prompts, max_fused_steps=1, **kw)
    assert fused == step
    assert step_summary(st_f["telemetry"]) == step_summary(st_s["telemetry"])
    assert st_f["decode_steps"] == st_s["decode_steps"]
    assert st_f["dispatches"] < st_s["dispatches"]


def test_fused_matches_stepwise_reserved(rng):
    """The reserved (paged) policy has no growth, sharing, or prefetch, so
    the horizon is only budget-bounded and fusion is maximal."""
    prompts = [rng.integers(0, 64, int(rng.integers(2, 7))).astype(np.int32)
               for _ in range(6)]
    kw = dict(layout="paged", page_slots=8, max_new=12, slots=4)
    fused, st_f = _serve(prompts, max_fused_steps=8, **kw)
    step, st_s = _serve(prompts, max_fused_steps=1, **kw)
    assert fused == step
    assert step_summary(st_f["telemetry"]) == step_summary(st_s["telemetry"])
    assert st_f["dispatches"] < st_s["dispatches"]


def test_fused_identity_under_swap_preemption(rng):
    """A pool tight enough to force preempt+swap+restore mid-workload:
    preemption is a control-plane event, so it can only land between fused
    runs -- tokens and telemetry stay identical to stepwise dispatch."""
    prompts = [rng.integers(0, 64, int(rng.integers(3, 8))).astype(np.int32)
               for _ in range(8)]
    kw = dict(pool_pages=10, page_slots=4, max_new=8, slots=8,
              preempt_mode="swap")
    fused, st_f = _serve(prompts, max_fused_steps=8, **kw)
    step, st_s = _serve(prompts, max_fused_steps=1, **kw)
    assert fused == step
    assert step_summary(st_f["telemetry"]) == step_summary(st_s["telemetry"])
    assert st_f["swapped"] > 0                    # pressure actually hit
    assert st_f["swapped"] == st_s["swapped"]
    assert st_f["leaked_frames"] == st_s["leaked_frames"] == 0


def test_fused_identity_under_spill_pressure(rng):
    """Same with the host store sized to force HOST -> SPILL demotion and
    two-hop resumes: the deepest preemption path in the tier stack must
    not observe any difference from fused dispatch."""
    prompts = [rng.integers(0, 64, int(rng.integers(3, 8))).astype(np.int32)
               for _ in range(8)]
    kw = dict(pool_pages=10, page_slots=4, max_new=8, slots=8,
              preempt_mode="swap", host_frames=2, spill_frames=32)
    fused, st_f = _serve(prompts, max_fused_steps=8, **kw)
    step, st_s = _serve(prompts, max_fused_steps=1, **kw)
    assert fused == step
    assert step_summary(st_f["telemetry"]) == step_summary(st_s["telemetry"])
    assert st_f["spill_out_pages"] > 0 and st_f["spill_in_pages"] > 0
    assert st_f["leaked_frames"] == st_f["leaked_spill_frames"] == 0


# -- the noop_run horizon query ----------------------------------------------
def test_noop_run_semantics():
    """Step-by-step contract of the pure horizon query (a staged plan that
    is immediately cancelled): grantable boundary prefetches no longer end
    a run -- they would be staged -- so the horizon counts straight through
    page boundaries and stops only at events staging cannot absorb: the
    end of the block table, and (tested separately) copy-on-write and a
    declined prefetch."""
    from repro.emem_vm import BlockManager
    bm = BlockManager(n_frames=8, n_seqs=2, max_lpages=4, page_slots=4)
    bm.begin_seq(0, np.arange(3, dtype=np.int32))
    for pos in range(3):                          # prefill maps page 0
        bm.ensure_writable(0, pos)
    free0 = bm.allocator.free_count()
    c0 = dict(bm.counters)
    # boundaries at nl=4, 8, 12 would all be staged: limit comes back
    assert bm.noop_run(0, 3, 8) == 8
    # ... and the query left no trace: allocator and counters untouched
    assert bm.allocator.free_count() == free0
    assert bm.counters == c0
    # steps 0..12 write pos 3..15; pos 16 would need page 4 -> off-table
    assert bm.noop_run(0, 3, 64) == 13
    bm.ensure_writable(0, 3)
    assert bm.prefetch(0, 4)                      # page 1 now pending
    # a pending prefetch hit is deferred accounting, not a break
    assert bm.noop_run(0, 4, 8) == 8
    bm.ensure_writable(0, 4)                      # hit recorded, page live
    assert bm.noop_run(0, 5, 8) == 8
    assert bm.noop_run(0, 5, 1) == 1              # limit caps the answer
    assert bm.noop_run(0, 5, 0) == 0


def test_noop_run_stops_at_declined_prefetch():
    """The headroom gate is the one boundary event staging must NOT absorb:
    when the stepwise loop would have declined the speculative allocation
    (free frames <= live sequences), the next boundary write is mandatory
    growth -- possibly a preemption -- and the run must end exactly where
    stepwise dispatch would have faulted."""
    from repro.emem_vm import BlockManager
    bm = BlockManager(n_frames=3, n_seqs=2, max_lpages=4, page_slots=4)
    bm.begin_seq(0, np.arange(3, dtype=np.int32))
    bm.begin_seq(1, np.arange(3, dtype=np.int32))
    for pos in range(3):
        bm.ensure_writable(0, pos)
        bm.ensure_writable(1, pos)
    # 2 live seqs, 1 free frame: the nl=4 prefetch is declined for both
    # slots, so the run covers the boundary-deciding step and stops --
    # step 1 would write pos 4 into an unmapped page (mandatory growth)
    free0 = bm.allocator.free_count()
    plan = bm.stage_fused_run([0, 1], [3, 3], 8)
    assert plan.n == 1 and plan.allocs == []
    bm.cancel_fused_run(plan)
    assert bm.allocator.free_count() == free0


def test_noop_run_breaks_on_shared_page():
    """A divergent write onto a shared page is a copy-on-write event: the
    horizon must stop at the first position past the shared prefix."""
    from repro.emem_vm import BlockManager
    bm = BlockManager(n_frames=8, n_seqs=2, max_lpages=4, page_slots=4,
                      share_prefixes=True)
    donor = np.arange(8, dtype=np.int32)
    bm.begin_seq(0, donor)
    for pos in range(8):
        bm.ensure_writable(0, pos)
    follower = np.concatenate([donor[:6], np.array([63], np.int32)])
    shared = bm.begin_seq(1, follower)
    assert shared >= 4                            # at least page 0 shared
    if int(bm.shared_len[1]) > 4:                 # page 1 shared mid-page:
        # the first write past the prefix (pos 6+) hits the shared page
        assert bm.noop_run(1, int(bm.shared_len[1]), 8) == 0


def test_noop_run_reserved_is_unbounded():
    """Reserved tables are statically mapped, never shared, never
    prefetched: every step is a no-op and the limit comes straight back."""
    from repro.emem_vm import BlockManager
    bm = BlockManager(n_frames=8, n_seqs=2, max_lpages=4, page_slots=4,
                      policy="reserved")
    bm.begin_seq(0, np.arange(3, dtype=np.int32))
    assert bm.noop_run(0, 3, 8) == 8
    assert bm.noop_run(0, 15, 64) == 64


# -- boundary crossing --------------------------------------------------------
def test_fused_runs_cross_page_boundaries(rng):
    """The point of staged prefetch: a fused run no longer ends at a page
    boundary.  With ample pool headroom every boundary allocation is
    staged, the (lpage, frame) mappings ride into the while_loop, and the
    whole generation executes as ONE dispatch that writes across several
    page boundaries (the paper's §2.1 'translation rides the access' --
    there is no host round-trip left at a page crossing)."""
    from repro.serve import EngineConfig, Request, ServeEngine
    cfg = _cfg(pool_pages=8, page_slots=8)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params,
                         EngineConfig(slots=1, max_len=32,
                                      max_fused_steps=64))
    req = Request(uid=0, prompt=rng.integers(0, 64, 5).astype(np.int32),
                  max_new_tokens=24)
    engine.admit(req, 0)
    runs = []
    while engine.slot_req[0] is not None:
        n_before = int(np.asarray(engine.lengths)[0])
        n = engine.step()
        runs.append((n_before, n))
    stats = engine.shutdown()
    ps, lpages = 8, 4
    crossed = 0
    for start, n in runs:
        if n > 1:
            for pos in range(start, start + n):
                if (pos + 1) % ps == 0 and (pos + 1) // ps < lpages:
                    crossed += 1                  # boundary INSIDE a run
    assert crossed >= 1, runs
    assert any(n > 1 for _, n in runs), runs      # fusion did engage
    assert sum(n for _, n in runs) == len(req.output)
    # the staged allocations are accounted exactly like stepwise prefetch
    assert stats["prefetch_allocs"] >= crossed
    assert stats["prefetch_hits"] >= crossed


def test_fused_boundary_stats_match_stepwise(rng):
    """Satellite regression for staged-prefetch accounting: a fused engine
    and an explicit max_fused_steps=1 engine must report IDENTICAL pool
    and serving counters -- prefetch_allocs/prefetch_hits attribution from
    the while_loop carry replay included -- with dispatches and the
    scheduler's scoring traffic (score_cache_hits: fewer ticks, fewer
    window re-scorings) the only numbers fusion is allowed to move."""
    prompts = [rng.integers(0, 64, int(rng.integers(3, 8))).astype(np.int32)
               for _ in range(6)]
    kw = dict(pool_pages=24, page_slots=4, max_new=10, slots=4)
    fused, st_f = _serve(prompts, max_fused_steps=8, **kw)
    step, st_s = _serve(prompts, max_fused_steps=1, **kw)
    assert fused == step
    assert st_f["prefetch_allocs"] > 0            # boundaries were staged
    assert st_f["prefetch_hits"] > 0
    keys = (set(st_f) | set(st_s)) - {"dispatches", "telemetry",
                                      "score_cache_hits",
                                      "table_push_bytes"}
    diff = {k: (st_f.get(k), st_s.get(k)) for k in keys
            if st_f.get(k) != st_s.get(k)}
    assert not diff, diff
    assert step_summary(st_f["telemetry"]) == step_summary(st_s["telemetry"])
    assert st_f["dispatches"] < st_s["dispatches"]


def test_fused_eos_early_exit(rng):
    """EOS is detected inside the while_loop from the fed-back token: the
    fused run stops early and completion matches stepwise exactly."""
    prompt = rng.integers(0, 64, 4).astype(np.int32)
    kw = dict(max_new=12, page_slots=8, pool_pages=8, slots=1)
    base, _ = _serve([prompt], max_fused_steps=1, **kw)
    eos = int(base[0][2])
    cut = base[0].index(eos) + 1                  # first occurrence wins
    fused, st_f = _serve([prompt], max_fused_steps=16, eos_id=eos, **kw)
    step, st_s = _serve([prompt], max_fused_steps=1, eos_id=eos, **kw)
    assert fused == step
    assert fused[0] == base[0][:cut]
    assert step_summary(st_f["telemetry"]) == step_summary(st_s["telemetry"])
    # prefill decodes each prompt token, then `cut` generation steps
    assert st_f["decode_steps"] == st_s["decode_steps"] == len(prompt) + cut


# -- the committed SLO baseline ----------------------------------------------
vm_bench = pytest.importorskip("benchmarks.vm_bench")


def test_fused_engine_reproduces_committed_slo_telemetry():
    """The slo section of BENCH_vm.json predates fused decode (it was
    measured with step-at-a-time dispatch).  Both the fused default and an
    explicit max_fused_steps=1 engine must reproduce its headline numbers
    byte-for-byte -- fusion that moves a telemetry number is a bug, and
    this is the pin that catches it PR over PR."""
    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_vm.json")
    with open(path) as f:
        committed = json.load(f).get("slo")
    if not committed:
        pytest.skip("no committed slo baseline yet")
    pool = committed["pool_pages"]
    slots = committed["slots"]
    retain = committed["retain_frames"]
    out_1, tel_1 = vm_bench._run_slo("pooled", "swap", pool, slots, retain,
                                     max_fused=1)
    out_f, tel_f = vm_bench._run_slo("pooled", "swap", pool, slots, retain)
    assert out_f == out_1
    assert tel_f == tel_1
    for key, got in (("p99_ttft_steps", tel_f["ttft_steps"]["p99"]),
                     ("mean_itl_steps", tel_f["itl_steps"]["mean"]),
                     ("p50_ttft_steps", tel_f["ttft_steps"]["p50"]),
                     ("p95_queue_wait_steps",
                      tel_f["queue_wait_steps"]["p95"]),
                     ("decode_steps", tel_f["steps"]),
                     ("preemptions", tel_f["preemptions"]),
                     ("completed", tel_f["completed"])):
        assert got == committed[key], (key, got, committed[key])

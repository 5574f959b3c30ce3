"""Model-layer unit tests: families, MoE semantics, serving engine."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # container without hypothesis
    from _hypothesis_compat import given, settings, strategies as st

from conftest import tiny_dense_cfg
from repro.models import Model, ModelConfig
from repro.models import moe as MOE
from repro.models.layers import (build_axes, build_params, chunked_attention,
                                 chunked_attention_unrolled, rms_norm, rope)
from repro.kernels.flash_attention import ref as fa_ref


def test_rms_norm_unit_scale(rng):
    x = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32)) * 10
    y = rms_norm(x, jnp.ones((64,)), 1e-6)
    np.testing.assert_allclose(np.mean(np.asarray(y) ** 2, -1), 1.0, rtol=1e-3)


def test_rope_preserves_norm_and_relativity(rng):
    x = jnp.asarray(rng.normal(size=(1, 8, 32)).astype(np.float32))
    pos = jnp.arange(8)[None]
    y = rope(x, pos, 1e4)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    # relative property: <rope(q,i), rope(k,j)> depends only on i-j
    q = jnp.asarray(rng.normal(size=(1, 1, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 1, 32)).astype(np.float32))
    def dot(i, j):
        qi = rope(q, jnp.asarray([[i]]), 1e4)
        kj = rope(k, jnp.asarray([[j]]), 1e4)
        return float(jnp.sum(qi * kj))
    assert dot(3, 1) == pytest.approx(dot(7, 5), rel=1e-4)


@pytest.mark.parametrize("causal,window", [(True, None), (True, 8),
                                           (False, None)])
def test_chunked_attention_matches_ref(rng, causal, window):
    B, Hq, Hkv, S, D = 2, 4, 2, 32, 16
    q = jnp.asarray(rng.normal(size=(B, Hq, S, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    ref = fa_ref.mha(q, k, v, causal=causal, window=window)
    for fn in (chunked_attention, chunked_attention_unrolled):
        out = fn(q, k, v, causal=causal, window=window, chunk_q=8, chunk_k=8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5, err_msg=str(fn))


# -- MoE -------------------------------------------------------------------------
def _moe_cfg(**kw):
    base = dict(name="m", family="moe", n_layers=2, d_model=32, n_heads=4,
                n_kv_heads=2, head_dim=8, d_ff=64, vocab_size=64,
                n_experts=4, n_experts_active=2, moe_capacity_factor=8.0,
                param_dtype="float32", compute_dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def test_moe_matches_dense_oracle(rng):
    cfg = _moe_cfg()
    p = build_params(MOE.moe_defs(cfg), jax.random.key(0), jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 8, 32)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(MOE.moe_block(cfg, p, x)),
        np.asarray(MOE.moe_block_dense_oracle(cfg, p, x)),
        rtol=1e-4, atol=1e-5)


def test_moe_shared_experts(rng):
    cfg = _moe_cfg(n_shared_experts=2, d_expert=16)
    p = build_params(MOE.moe_defs(cfg), jax.random.key(1), jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, 4, 32)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(MOE.moe_block(cfg, p, x)),
        np.asarray(MOE.moe_block_dense_oracle(cfg, p, x)),
        rtol=1e-4, atol=1e-5)


def test_moe_capacity_drops_tokens(rng):
    """With capacity factor << 1 most tokens are dropped -> output shrinks
    toward the shared/zero path but stays finite."""
    cfg = _moe_cfg(moe_capacity_factor=0.1)
    p = build_params(MOE.moe_defs(cfg), jax.random.key(2), jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 16, 32)).astype(np.float32))
    y_small = MOE.moe_block(cfg, p, x)
    y_big = MOE.moe_block(dataclasses.replace(cfg, moe_capacity_factor=8.0),
                          p, x)
    assert bool(jnp.all(jnp.isfinite(y_small)))
    assert float(jnp.abs(y_small).sum()) < float(jnp.abs(y_big).sum())


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_moe_gate_weights_normalized(seed):
    cfg = _moe_cfg()
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(1, 4, 32)).astype(np.float32))
    p = build_params(MOE.moe_defs(cfg), jax.random.key(seed % 1000),
                     jnp.float32)
    logits = (x.reshape(-1, 32) @ p["router"]).astype(jnp.float32)
    w, _ = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.n_experts_active)
    w = w / w.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)


# -- axes/defs consistency ----------------------------------------------------------
def test_param_axes_match_shapes():
    for family_cfg in (tiny_dense_cfg(), _moe_cfg()):
        model = Model(family_cfg)
        shapes = model.shapes()
        axes = model.axes()
        flat_s = jax.tree.leaves(shapes)
        flat_a = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
        assert len(flat_s) == len(flat_a)
        for s, a in zip(flat_s, flat_a):
            assert len(s.shape) == len(a), (s.shape, a)


# -- serving engine -------------------------------------------------------------------
def test_serve_engine_continuous_batching(rng):
    from repro.serve import EngineConfig, Request, ServeEngine, Scheduler
    cfg = tiny_dense_cfg(vocab_size=64)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params,
                         EngineConfig(slots=2, max_len=48))
    sched = Scheduler(engine)
    reqs = [Request(uid=i, prompt=rng.integers(0, 64, 5).astype(np.int32),
                    max_new_tokens=4) for i in range(5)]
    sched.submit(reqs)
    done = sched.run()
    assert len(done) == 5
    for r in done:
        assert len(r.output) == 4
        assert all(0 <= t < 64 for t in r.output)


def test_serve_engine_matches_manual_decode(rng):
    """Engine per-step logits == manual prefill+decode (teacher-forced on a
    fixed continuation -- greedy token ids are fragile to float ties)."""
    from repro.serve import EngineConfig, Request, ServeEngine
    cfg = tiny_dense_cfg(vocab_size=64)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    prompt = rng.integers(0, 64, 6).astype(np.int32)

    # manual reference: logits after consuming the prompt
    ref_logits, _ = model.prefill(
        params, {"tokens": jnp.asarray(prompt)[None]}, max_len=32)

    engine = ServeEngine(model, params, EngineConfig(slots=1, max_len=32))
    req = Request(uid=0, prompt=prompt, max_new_tokens=3)
    engine.admit(req, 0)
    # after admit, the engine's last logits determined req._next
    _, eng_logits, _ = engine._decode(
        engine.params,
        jnp.asarray([[prompt[-1]]], jnp.int32).repeat(1, 0),
        engine.cache, engine.lengths)  # re-decode of last token is a no-op
    np.testing.assert_allclose(np.asarray(ref_logits[0, :64]),
                               np.asarray(eng_logits[0, :64]),
                               rtol=1e-4, atol=1e-4)
    # the engine completes the request
    while engine.slot_req[0] is not None:
        engine.step()
    assert req.done and len(req.output) == 3


def test_serve_engine_empty_prompt(rng):
    """Regression: admit() used to crash (unbound ``logits``) on an empty
    prompt; now an implicit BOS produces the first logits."""
    from repro.serve import EngineConfig, Request, ServeEngine
    cfg = tiny_dense_cfg(vocab_size=64)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, EngineConfig(slots=2, max_len=16))
    req = Request(uid=0, prompt=np.zeros(0, np.int32), max_new_tokens=3)
    engine.admit(req, 0)
    while engine.slot_req[0] is not None:
        engine.step()
    assert req.done and len(req.output) == 3
    assert all(0 <= t < 64 for t in req.output)


def _pooled_cfg(pool_pages=None, layout="pooled"):
    return tiny_dense_cfg(vocab_size=64, kv_layout=layout, kv_page_slots=4,
                          kv_pool_pages=pool_pages)


def _hybrid_cfg(**kw):
    """Two-layer period: one attention layer, then one mamba layer."""
    return ModelConfig(
        name="t-hyb", family="hybrid", n_layers=4, d_model=64, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=64, attn_period=2,
        attn_offset=0, ssm_state=8, ssm_head_dim=16, ssm_groups=1,
        ssm_conv=4, ssm_expand=2, ssd_chunk=8, param_dtype="float32",
        compute_dtype="float32", attn_chunk_q=16, attn_chunk_k=16,
        kv_page_slots=4, **kw)


@pytest.mark.parametrize("variant", ["scan", "unroll_layers", "hybrid"])
def test_serve_pooled_matches_fixed_paged(rng, variant):
    """kv_layout="pooled" is token-identical to the fixed paged layout and
    to the batch layout, whether the layer scan carries the page pool
    (scan), the layers are unrolled, or a period mixes a carried attention
    pool with scanned SSM state (hybrid)."""
    from repro.serve import EngineConfig, Request, ServeEngine, Scheduler
    prompts = [rng.integers(0, 64, int(rng.integers(2, 7))).astype(np.int32)
               for _ in range(5)]
    outs = {}
    for layout in ("batch", "paged", "pooled"):
        kw = dict(kv_layout=layout,
                  kv_pool_pages=16 if layout == "pooled" else None)
        if variant == "hybrid":
            cfg = _hybrid_cfg(**kw)
        else:
            cfg = dataclasses.replace(
                _pooled_cfg(), unroll_layers=variant == "unroll_layers",
                **kw)
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        engine = ServeEngine(model, params, EngineConfig(slots=2, max_len=32))
        sched = Scheduler(engine)
        sched.submit([Request(uid=i, prompt=p, max_new_tokens=4)
                      for i, p in enumerate(prompts)])
        done = sched.run()
        outs[layout] = {r.uid: tuple(r.output) for r in done}
        if layout == "pooled":
            assert engine.pool_stats()["used"] == 0   # all frames released
    assert outs["batch"] == outs["paged"] == outs["pooled"]


def test_serve_pooled_oversubscribes_fixed_reservation(rng):
    """With the KV byte budget that caps the fixed layout at 2 slots, the
    pooled engine admits strictly more concurrent short requests."""
    from repro.serve import EngineConfig, Request, ServeEngine, Scheduler
    fixed_slots, max_len = 2, 32
    cfg = _pooled_cfg(pool_pages=fixed_slots * (max_len // 4))
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, EngineConfig(slots=6, max_len=max_len))
    sched = Scheduler(engine)
    reqs = [Request(uid=i, prompt=rng.integers(0, 64, 3).astype(np.int32),
                    max_new_tokens=4) for i in range(6)]
    sched.submit(reqs)
    sched._admit_waiting()
    concurrent = sum(r is not None for r in engine.slot_req)
    assert concurrent > fixed_slots, concurrent
    done = sched.run()
    assert len(done) == 6 and all(len(r.output) == 4 for r in done)
    assert engine.pool_stats()["used"] == 0


def test_serve_pooled_rejects_oversized_request(rng):
    """A request needing more frames than the pool holds can never be
    admitted; the scheduler surfaces that instead of spinning."""
    from repro.serve import EngineConfig, Request, ServeEngine, Scheduler
    cfg = _pooled_cfg(pool_pages=2)      # 8 token positions total
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, EngineConfig(slots=2, max_len=32))
    sched = Scheduler(engine)
    big = Request(uid=0, prompt=rng.integers(0, 64, 12).astype(np.int32),
                  max_new_tokens=8)
    sched.submit([big])
    with pytest.raises(RuntimeError, match="never be admitted"):
        sched.run()


@pytest.mark.parametrize("layout", ["batch", "paged", "pooled"])
def test_admit_does_not_corrupt_inflight_slots(rng, layout):
    """Admitting B mid-flight must not change A's output: decode runs the
    full batch, so prefill writes must be masked to the admitted slot."""
    from repro.serve import EngineConfig, Request, ServeEngine
    cfg = tiny_dense_cfg(vocab_size=64) if layout == "batch" else \
        _pooled_cfg(pool_pages=16 if layout == "pooled" else None,
                    layout=layout)
    pa = rng.integers(0, 64, 5).astype(np.int32)
    pb = rng.integers(0, 64, 6).astype(np.int32)

    def run(admit_b):
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        engine = ServeEngine(model, params, EngineConfig(slots=2, max_len=32))
        ra = Request(uid=0, prompt=pa, max_new_tokens=6)
        engine.admit(ra, 0)
        engine.step()
        engine.step()
        if admit_b:
            engine.admit(Request(uid=1, prompt=pb, max_new_tokens=2), 1)
        while engine.slot_req[0] is not None:
            engine.step()
        return ra.output

    assert run(admit_b=False) == run(admit_b=True), layout


def test_oversized_prompt_rejected(rng):
    """A prompt with no room to generate under max_len is rejected up front
    (previously: pooled crashed mid-prefill leaking the slot + frames)."""
    from repro.serve import EngineConfig, Request, ServeEngine, Scheduler
    for cfg in (tiny_dense_cfg(vocab_size=64), _pooled_cfg(pool_pages=64)):
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        engine = ServeEngine(model, params, EngineConfig(slots=2, max_len=16))
        big = Request(uid=0, prompt=rng.integers(0, 64, 20).astype(np.int32),
                      max_new_tokens=4)
        assert not engine.can_admit(big)
        with pytest.raises(RuntimeError, match="inadmissible"):
            engine.admit(big, 0)
        assert engine.slot_req[0] is None          # no state leaked
        if engine.blocks is not None and engine.blocks.policy == "on_demand":
            assert engine.blocks.free_count() == engine.n_frames
        sched = Scheduler(engine)
        sched.submit([big])
        with pytest.raises(RuntimeError, match="never be admitted"):
            sched.run()


def _serve_pooled(rng, prompts, max_new=4, slots=4, max_len=32,
                  pool_pages=24, share=True, **ecfg_kw):
    from repro.serve import EngineConfig, Request, ServeEngine, Scheduler
    cfg = _pooled_cfg(pool_pages=pool_pages)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params,
                         EngineConfig(slots=slots, max_len=max_len,
                                      **ecfg_kw))
    engine.blocks.share_prefixes = share
    sched = Scheduler(engine)
    sched.submit([Request(uid=i, prompt=p, max_new_tokens=max_new)
                  for i, p in enumerate(prompts)])
    done = sched.run()
    stats = engine.shutdown()            # leak detector: raises on leak
    return {r.uid: tuple(r.output) for r in done}, stats


def test_serve_prefix_sharing_token_identity(rng):
    """Requests with a common system prompt share its KV pages (one physical
    copy, refcounted) and still decode token-identically to the unshared
    run; divergence is handled by copy-on-write."""
    system = rng.integers(0, 64, 10).astype(np.int32)
    prompts = [np.concatenate([system,
                               rng.integers(0, 64, 3).astype(np.int32)])
               for _ in range(5)]
    shared, st_s = _serve_pooled(rng, prompts, share=True)
    unshared, st_u = _serve_pooled(rng, prompts, share=False)
    assert shared == unshared
    # slots=4: three requests run concurrently with the donor and share
    # (the fifth admits after everything completed -- nothing live to match)
    assert st_s["shared_prompt_tokens"] >= 3 * len(system)
    assert st_s["cow_copies"] > 0                 # tails diverge mid-page
    assert st_u["shared_prompt_tokens"] == 0
    assert st_s["allocs"] < st_u["allocs"]        # fewer frames touched
    assert st_s["leaked_frames"] == st_u["leaked_frames"] == 0


def test_serve_swap_preemption_token_identity_and_cost(rng):
    """Tentpole acceptance: a run whose sequences are preempted, swapped to
    host, and restored produces byte-identical outputs to both the
    unpreempted run and the PR 2 recompute path -- and resume-by-swap-in
    costs fewer decode steps than resume-by-re-prefill."""
    prompts = [rng.integers(0, 64, int(rng.integers(3, 8))).astype(np.int32)
               for _ in range(5)]
    kw = dict(max_new=6, slots=5, share=False)
    swap, st_swap = _serve_pooled(rng, prompts, pool_pages=10,
                                  preempt_mode="swap", **kw)
    rec, st_rec = _serve_pooled(rng, prompts, pool_pages=10,
                                preempt_mode="recompute", **kw)
    roomy, st_roomy = _serve_pooled(rng, prompts, pool_pages=64, **kw)
    assert swap == roomy and rec == roomy
    assert st_swap["swapped"] > 0 and st_swap["swap_resumed"] > 0
    assert st_swap["swap_in_pages"] > 0
    assert st_rec["swapped"] == 0 and st_rec["preempted"] > 0
    assert st_roomy["preempted"] == 0
    # the FLOPs-for-PCIe-bytes trade: swap resumes skip the re-prefill
    assert st_swap["decode_steps"] < st_rec["decode_steps"], \
        (st_swap["decode_steps"], st_rec["decode_steps"])
    assert st_swap["leaked_frames"] == st_rec["leaked_frames"] == 0


def test_serve_swap_identity_across_both_policies(rng):
    """Acceptance: the preempt+swap+restore pooled run matches the reserved
    (paged) policy run token for token -- the static layout never preempts,
    so it doubles as the unpreempted reference for the other policy."""
    from repro.serve import EngineConfig, Request, ServeEngine, Scheduler
    prompts = [rng.integers(0, 64, int(rng.integers(3, 8))).astype(np.int32)
               for _ in range(5)]
    outs, stats = {}, {}
    for layout, pool in (("paged", None), ("pooled", 10)):
        cfg = _pooled_cfg(pool_pages=pool, layout=layout)
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        with ServeEngine(model, params,
                         EngineConfig(slots=5, max_len=32)) as engine:
            if engine.blocks.policy == "on_demand":
                engine.blocks.share_prefixes = False
            sched = Scheduler(engine)
            sched.submit([Request(uid=i, prompt=p, max_new_tokens=6)
                          for i, p in enumerate(prompts)])
            done = sched.run()
            outs[layout] = {r.uid: tuple(r.output) for r in done}
        stats[layout] = engine.shutdown()          # idempotent: recorded stats
    assert outs["paged"] == outs["pooled"]
    assert stats["pooled"]["swapped"] > 0          # the tight pool did swap
    assert stats["paged"]["leaked_frames"] == 0
    assert stats["pooled"]["leaked_frames"] == 0


def test_serve_spill_tier_token_identity_and_cost(rng):
    """Tentpole acceptance: with the host store sized to force demotion,
    preempted pages overflow into the spill tier (HOST -> SPILL) and
    resumes promote two-hop (SPILL -> HOST -> DEVICE) -- token-identically
    to recompute and to the roomy run, and still strictly cheaper in
    decode steps than the recompute cliff."""
    prompts = [rng.integers(0, 64, int(rng.integers(3, 8))).astype(np.int32)
               for _ in range(8)]
    kw = dict(max_new=6, slots=8, share=False, pool_pages=10)
    spilled, st_sp = _serve_pooled(rng, prompts, preempt_mode="swap",
                                   host_frames=2, spill_frames=32, **kw)
    rec, st_rec = _serve_pooled(rng, prompts, preempt_mode="recompute", **kw)
    roomy, _ = _serve_pooled(rng, prompts, max_new=6, slots=8, share=False,
                             pool_pages=64)
    assert spilled == rec == roomy
    assert st_sp["host_demotions"] > 0 and st_sp["spill_out_pages"] > 0
    assert st_sp["spill_in_pages"] > 0            # two-hop promotions ran
    assert st_sp["decode_steps"] < st_rec["decode_steps"]
    assert st_sp["leaked_frames"] == 0
    assert st_sp["leaked_host_frames"] == st_sp["leaked_spill_frames"] == 0


def test_serve_host_full_recompute_fallback(rng):
    """Satellite acceptance: preempt_mode="swap" with a host store too
    small for any record and the spill tier DISABLED must take the
    recompute fallback -- no swaps, token identity preserved (the demotion
    path must not regress the PR 3 behavior when spill is off)."""
    prompts = [rng.integers(0, 64, int(rng.integers(3, 8))).astype(np.int32)
               for _ in range(6)]
    kw = dict(max_new=6, slots=6, share=False, pool_pages=10)
    fb, st_fb = _serve_pooled(rng, prompts, preempt_mode="swap",
                              host_frames=1, spill_frames=0, **kw)
    roomy, _ = _serve_pooled(rng, prompts, max_new=6, slots=6, share=False,
                             pool_pages=64)
    assert fb == roomy
    assert st_fb["swapped"] == 0 and st_fb["preempted"] > 0
    assert st_fb["spill_out_pages"] == 0
    assert st_fb["leaked_frames"] == 0


def test_serve_swap_restores_recurrent_state(rng):
    """Swap-preemption on a hybrid (attention+SSM) model: the evicted
    slot's conv/ssd state rides the swap record and is restored on resume,
    and a reused slot starts from zeroed recurrent state -- both runs must
    match the unconstrained pool token for token."""
    from repro.serve import EngineConfig, Request, ServeEngine, Scheduler

    def hybrid_cfg(pool):
        return ModelConfig(
            name="t-hyb", family="hybrid", n_layers=2, d_model=64,
            n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=64,
            attn_period=2, attn_offset=0, ssm_state=8, ssm_head_dim=16,
            ssm_groups=1, ssm_conv=4, ssm_expand=2, ssd_chunk=8,
            param_dtype="float32", compute_dtype="float32",
            attn_chunk_q=16, attn_chunk_k=16, kv_layout="pooled",
            kv_page_slots=4, kv_pool_pages=pool)

    prompts = [rng.integers(0, 64, int(rng.integers(3, 7))).astype(np.int32)
               for _ in range(4)]

    def run(pool):
        model = Model(hybrid_cfg(pool))
        params = model.init(jax.random.key(0))
        with ServeEngine(model, params,
                         EngineConfig(slots=4, max_len=32)) as engine:
            sched = Scheduler(engine)
            sched.submit([Request(uid=i, prompt=p, max_new_tokens=5)
                          for i, p in enumerate(prompts)])
            done = sched.run()
        return ({r.uid: tuple(r.output) for r in done}, engine.shutdown())

    tight, st_tight = run(pool=6)
    roomy, st_roomy = run(pool=32)
    assert tight == roomy
    assert st_tight["swapped"] > 0 and st_tight["swap_resumed"] > 0
    assert st_roomy["swapped"] == 0
    assert st_tight["leaked_frames"] == 0
    # retention needs prefix sharing, which recurrent state forbids: asking
    # for it on a hybrid model is a loud error, not a silent no-op
    model = Model(hybrid_cfg(32))
    with pytest.raises(ValueError, match="attention-only"):
        ServeEngine(model, model.init(jax.random.key(0)),
                    EngineConfig(slots=2, max_len=32, retain_frames=4))


def test_serve_retention_survives_idle_gap(rng):
    """A completed system prompt's pages stay in the retention pool across
    an idle gap (nothing live, queue empty) and the next request with the
    same prefix shares them instead of re-prefilling."""
    from repro.serve import EngineConfig, Request, ServeEngine, Scheduler
    cfg = _pooled_cfg(pool_pages=16)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    system = rng.integers(0, 64, 8).astype(np.int32)
    with ServeEngine(model, params,
                     EngineConfig(slots=2, max_len=32,
                                  retain_frames=8)) as engine:
        sched = Scheduler(engine)
        sched.submit([Request(uid=0, prompt=system, max_new_tokens=3)])
        sched.run()
        assert all(r is None for r in engine.slot_req)   # fully idle
        assert engine.blocks.stats()["retained_entries"] == 1
        late = Request(uid=1, prompt=np.concatenate(
            [system, rng.integers(0, 64, 2).astype(np.int32)]),
            max_new_tokens=3)
        sched.submit([late])
        sched.run()
        assert engine.blocks.counters["retained_hits"] >= 1
        assert engine.blocks.counters["retained_tokens"] >= len(system) - 1
    # context-manager exit ran the leak detector; drained pool counts as 0
    assert engine.shutdown()["leaked_frames"] == 0


def test_serve_prefetch_allocates_before_boundary(rng):
    """Satellite: pooled decode allocates the next page one token before
    the boundary; the boundary write then hits the prefetched frame."""
    from repro.serve import EngineConfig, Request, ServeEngine
    cfg = _pooled_cfg(pool_pages=16)     # page_slots=4
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    with ServeEngine(model, params, EngineConfig(slots=1, max_len=32)) \
            as engine:
        req = Request(uid=0, prompt=rng.integers(0, 64, 3).astype(np.int32),
                      max_new_tokens=8)   # crosses positions 4 and 8
        engine.admit(req, 0)
        while engine.slot_req[0] is not None:
            engine.step()
    stats = engine.shutdown()
    assert stats["prefetch_allocs"] >= 2
    assert stats["prefetch_hits"] >= 2
    assert stats["leaked_frames"] == 0


def test_engine_context_manager_aborts_on_exception(rng):
    """Satellite: the leak detector cannot be skipped by an exception --
    __exit__ aborts active requests, releases their frames, and lets the
    original exception propagate."""
    from repro.serve import EngineConfig, Request, ServeEngine
    cfg = _pooled_cfg(pool_pages=16)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    with pytest.raises(ValueError, match="boom"):
        with ServeEngine(model, params,
                         EngineConfig(slots=2, max_len=32)) as engine:
            engine.admit(Request(uid=0,
                                 prompt=rng.integers(0, 64, 5)
                                 .astype(np.int32),
                                 max_new_tokens=4), 0)
            raise ValueError("boom")
    stats = engine.shutdown()            # idempotent: the recorded stats
    assert stats["aborted"] == 1
    assert stats["leaked_frames"] == 0
    assert engine.blocks.used_count() == 0


def test_engine_shutdown_idempotent(rng):
    from repro.serve import EngineConfig, Request, ServeEngine
    cfg = _pooled_cfg(pool_pages=16)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, EngineConfig(slots=1, max_len=32))
    req = Request(uid=0, prompt=rng.integers(0, 64, 4).astype(np.int32),
                  max_new_tokens=2)
    engine.admit(req, 0)
    while engine.slot_req[0] is not None:
        engine.step()
    first = engine.shutdown()
    again = engine.shutdown()
    assert again is first    # second call: recorded stats, no re-run
    # satellite regression: the telemetry summary used to be re-computed
    # per call AFTER caching, so the second dict lacked / differed in the
    # telemetry section.  It must be snapshotted once, into the cached dict.
    assert "telemetry" in first and again["telemetry"] == first["telemetry"]
    assert first["telemetry"]["completed"] == 1
    assert first["telemetry"]["ttft_steps"]["n"] == 1


def test_engine_shutdown_idempotent_after_abort(rng):
    """The abort path (context-manager exit while a request is live) must
    also snapshot telemetry once: repeated shutdowns return the identical
    dict, with the aborted request counted in it."""
    from repro.serve import EngineConfig, Request, ServeEngine
    cfg = _pooled_cfg(pool_pages=16)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, EngineConfig(slots=1, max_len=32))
    req = Request(uid=0, prompt=rng.integers(0, 64, 4).astype(np.int32),
                  max_new_tokens=4)
    engine.admit(req, 0)
    engine.step()                        # live mid-generation, then abort
    first = engine.shutdown(abort=True)
    assert engine.shutdown() is first
    assert first["telemetry"]["aborted"] == 1
    assert first["telemetry"]["completed"] == 0


def test_serve_preemption_token_identity(rng):
    """Optimistic admission + preemption: a pool too small for everyone's
    worst case still completes every request, token-identically to an
    unconstrained pool (preempted requests re-prefill their generated
    tokens as a prompt extension)."""
    prompts = [rng.integers(0, 64, int(rng.integers(3, 8))).astype(np.int32)
               for _ in range(5)]
    tight, st_tight = _serve_pooled(rng, prompts, max_new=6, slots=5,
                                    pool_pages=10, share=False)
    roomy, st_roomy = _serve_pooled(rng, prompts, max_new=6, slots=5,
                                    pool_pages=64, share=False)
    assert tight == roomy
    assert st_tight["preempted"] > 0 and st_roomy["preempted"] == 0
    assert st_tight["completed"] == len(prompts)


def test_preempt_after_final_token_completes(rng):
    """Regression: a sequence preempted right after its final token was
    appended (but before the decode ran) must complete, not requeue --
    re-admission would decode past its budget and change the output."""
    from repro.serve import EngineConfig, Request, ServeEngine
    cfg = _pooled_cfg(pool_pages=16)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    # stepwise: the test forges a preemption between two exact single
    # steps, so a fused run must not complete the request first
    engine = ServeEngine(model, params,
                         EngineConfig(slots=2, max_len=32,
                                      max_fused_steps=1))
    req = Request(uid=0, prompt=rng.integers(0, 64, 5).astype(np.int32),
                  max_new_tokens=3)
    engine.admit(req, 0)
    engine.step()
    engine.step()
    assert len(req.output) == 2 and not req.done
    # reproduce the step()-loop state at the moment of pool exhaustion:
    # the last budgeted token is appended, the decode has not yet run
    req.output.append(req._next)
    lengths = np.array(engine.lengths)
    lengths[0] += 1
    engine._preempt(0, lengths)
    assert req.done and len(req.output) == 3
    assert engine.drain_preempted() == []        # nothing requeued
    assert engine.shutdown()["completed"] == 1


def test_serve_admits_beyond_worst_case_reservation(rng):
    """PR 1's headroom rule blocked admission unless the request's WORST
    case fit; optimistic admission packs the pool by prompt need only."""
    from repro.serve import EngineConfig, Request, ServeEngine
    cfg = _pooled_cfg(pool_pages=4)      # 16 positions
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, EngineConfig(slots=4, max_len=16))
    reqs = [Request(uid=i, prompt=rng.integers(0, 64, 4).astype(np.int32),
                    max_new_tokens=8) for i in range(3)]
    # worst case is 3 pages each (9 > 4 frames): PR 1 admitted only one
    for slot, r in enumerate(reqs):
        assert engine.can_admit(r)       # prompt needs just 1 page each
        engine.admit(r, slot)
    assert sum(r is not None for r in engine.slot_req) == 3


def test_serve_shutdown_leak_detector(rng):
    from repro.serve import EngineConfig, Request, ServeEngine
    cfg = _pooled_cfg(pool_pages=16)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, EngineConfig(slots=2, max_len=32))
    req = Request(uid=0, prompt=rng.integers(0, 64, 5).astype(np.int32),
                  max_new_tokens=3)
    engine.admit(req, 0)
    with pytest.raises(RuntimeError, match="active"):
        engine.shutdown()                # still running: refuse
    while engine.slot_req[0] is not None:
        engine.step()
    stats = engine.shutdown()
    assert stats["leaked_frames"] == 0 and stats["completed"] == 1
    # a leak is detected: simulate a lost reference
    engine2 = ServeEngine(model, params, EngineConfig(slots=2, max_len=32))
    engine2.blocks.allocator.alloc()
    with pytest.raises(RuntimeError, match="leak"):
        engine2.shutdown()


def test_engine_has_no_layout_branching():
    """The tentpole's acceptance criterion: both kv_layout values route
    through the BlockManager -- no `if self.pooled:` forks left."""
    import inspect
    from repro.serve import engine as engine_mod
    src = inspect.getsource(engine_mod)
    assert "self.pooled" not in src


def test_scheduler_completes_duplicate_uids(rng):
    from repro.serve import EngineConfig, Request, ServeEngine, Scheduler
    cfg = tiny_dense_cfg(vocab_size=64)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, EngineConfig(slots=2, max_len=32))
    sched = Scheduler(engine)
    reqs = [Request(uid=7, prompt=rng.integers(0, 64, 4).astype(np.int32),
                    max_new_tokens=3) for _ in range(2)]
    sched.submit(reqs)
    done = sched.run()
    assert len(done) == 2 and all(len(r.output) == 3 for r in done)


def test_moe_sorted_dispatch_equals_scatter(rng):
    """The gather-only (sort) dispatch is bit-equivalent to the scatter
    baseline, including the capacity-drop rule (§Perf cell B lever)."""
    import dataclasses
    for cf in (8.0, 0.5):
        cfg = dataclasses.replace(_moe_cfg(n_shared_experts=1, d_expert=16),
                                  moe_capacity_factor=cf)
        p = build_params(MOE.moe_defs(cfg), jax.random.key(3), jnp.float32)
        x = jnp.asarray(rng.normal(size=(2, 16, 32)).astype(np.float32))
        np.testing.assert_array_equal(
            np.asarray(MOE.moe_block_sorted(cfg, p, x)),
            np.asarray(MOE.moe_block_scatter(cfg, p, x)))

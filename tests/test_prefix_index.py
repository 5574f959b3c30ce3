"""Radix-tree prefix index vs the retired linear scan.

The tree (:class:`repro.emem_vm.PrefixTree`) must be *semantically
invisible*: every ``(match_len, donor)`` answer, every admission cost,
every retention-pool reclaim decision and every allocator state must be
byte-for-byte what the linear matcher produced.  The linear path stays
behind ``prefix_index="linear"`` for one PR exactly so these tests can
use it as the oracle: the property test drives both BlockManagers through
the same random op stream and compares everything observable after every
op.  On top sit the serving-layer pieces this PR added around the index:
the scheduler's epoch-keyed admission-score cache and the per-request
``prefix_match_depth_pages`` telemetry.
"""
import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # container without hypothesis
    from _hypothesis_compat import given, settings, strategies as st

from conftest import tiny_dense_cfg
from repro.emem_vm import BlockManager, FrameAllocator, PrefixTree
from repro.emem_vm.allocator import OutOfFrames
from repro.models import Model
from repro.serve.telemetry import step_summary


def _toks(*xs):
    return np.asarray(xs, np.int32)


# -- PrefixTree structure ------------------------------------------------------
def test_tree_split_and_merge():
    """Diverging prompts split an edge into an interior node; removing a
    terminal merges the pass-through chain back (the tree stays a
    *compressed* trie, node count included)."""
    t = PrefixTree(page_slots=2)
    t.insert_pool(1, _toks(1, 2, 3, 4), [(0, 10), (1, 11)])
    assert t.n_nodes == 2                        # root + one leaf
    t.insert_pool(2, _toks(1, 2, 7, 8), [(0, 10), (1, 12)])
    assert t.n_nodes == 4                        # split at [1,2]
    assert t.lookup(_toks(1, 2, 3, 4)) == (4, ("pool", 1))
    assert t.lookup(_toks(1, 2, 7, 9)) == (3, ("pool", 2))
    # equal match at the shared interior: earliest insertion wins the tie
    assert t.lookup(_toks(1, 2, 9, 9)) == (2, ("pool", 1))
    pages = t.remove_pool(1)
    assert pages == [(0, 10), (1, 11)]
    assert t.n_nodes == 2                        # chain merged back
    assert t.lookup(_toks(1, 2, 3, 4)) == (2, ("pool", 2))
    t.remove_pool(2)
    assert t.n_nodes == 1 and t.pool_count == 0
    assert t.lookup(_toks(1, 2, 3, 4)) == (0, None)


def test_tree_pool_outranks_live_and_strictly_longer_wins():
    """The linear scan's donor contract: the pool wins at equal match
    length; a live prompt only wins with a strictly longer match."""
    t = PrefixTree(page_slots=2)
    t.insert_pool(7, _toks(5, 6, 7), [(0, 0), (1, 1)])
    t.insert_live(0, _toks(5, 6, 7))
    assert t.lookup(_toks(5, 6, 7, 8)) == (3, ("pool", 7))
    t.insert_live(1, _toks(5, 6, 7, 8, 9))
    assert t.lookup(_toks(5, 6, 7, 8)) == (4, ("live", 1))
    t.remove_live(1)
    assert t.lookup(_toks(5, 6, 7, 8)) == (3, ("pool", 7))
    t.remove_live(0)
    t.remove_pool(7)
    assert t.lookup(_toks(5, 6, 7, 8)) == (0, None)


def test_tree_touch_restamps_tiebreak_and_lru():
    """``touch_pool`` is the OrderedDict ``move_to_end``: it reorders both
    the LRU reclaim order and the equal-match tie-break (iteration order
    IS the tie-break in the linear oracle)."""
    t = PrefixTree(page_slots=2)
    t.insert_pool(1, _toks(4, 4, 1), [(0, 0)])
    t.insert_pool(2, _toks(4, 4, 2), [(0, 1)])
    assert t.lru_keys() == [1, 2] and t.oldest_pool() == 1
    assert t.lookup(_toks(4, 4, 9)) == (2, ("pool", 1))
    t.touch_pool(1)                              # 1 becomes newest
    assert t.lru_keys() == [2, 1] and t.oldest_pool() == 2
    assert t.lookup(_toks(4, 4, 9)) == (2, ("pool", 2))


def test_tree_duplicate_pool_rejected_and_find_pool():
    t = PrefixTree(page_slots=2)
    t.insert_pool(3, _toks(9, 9), [(0, 5)])
    assert t.find_pool(_toks(9, 9)) == 3
    assert t.find_pool(_toks(9)) is None         # mid-edge: no terminal
    assert t.find_pool(_toks(9, 9, 9)) is None
    with pytest.raises(ValueError, match="dedupe"):
        t.insert_pool(4, _toks(9, 9), [(0, 6)])


def test_tree_frame_counts_and_reclaimable():
    """``reclaimable`` counts distinct frames whose every allocator
    reference is pool-held -- shared frames (within or across entries)
    only count once all holders are pool entries, pinned frames never."""
    a = FrameAllocator(8)
    f0, f1, f2 = a.alloc(), a.alloc(), a.alloc()
    a.ref(f1)                                    # f1 doubly referenced
    t = PrefixTree(page_slots=2)
    t.insert_pool(1, _toks(1, 2), [(0, f0), (1, f1)])
    t.insert_pool(2, _toks(1, 3), [(0, f2), (1, f1)])
    assert t.pool_frames_total == 4
    # f0, f2 free on drop; f1 has refcount 2 == its two pool holds
    assert t.reclaimable(a) == 3
    # excluding an entry an admission shares from removes its contribution
    assert t.reclaimable(a, exclude_key=1) == 1  # only f2 (f1 short 1 ref)
    a.pin(f0)
    assert t.reclaimable(a) == 2
    a.unpin(f0)
    t.remove_pool(2)
    assert t.pool_frames_total == 2 and t.reclaimable(a) == 1  # f0 only


# -- differential property test: tree vs linear oracle -------------------------
class _NullIO:
    """Page-IO stub: payload identity is all swap correctness needs."""

    def read(self, frames):
        return [("pg", int(f)) for f in frames]

    def write(self, assignments):
        pass


def _mk(prefix_index: str) -> BlockManager:
    bm = BlockManager(n_frames=14, n_seqs=3, max_lpages=6, page_slots=2,
                      policy="on_demand", share_prefixes=True,
                      retain_frames=8, n_spill_frames=4,
                      prefix_index=prefix_index)
    bm.page_io = _NullIO()
    return bm


#: nested-prefix prompt families: base[f][:L] gives heavy shared structure
_BASES = [np.arange(12, dtype=np.int32),
          np.concatenate([np.arange(6, dtype=np.int32),
                          np.arange(20, 26, dtype=np.int32)]),
          np.arange(100, 112, dtype=np.int32)]


def _observe(a: BlockManager, b: BlockManager, probes) -> None:
    """Everything observable must agree after every op."""
    for p in probes:
        assert a._match_prefix(p) == b._match_prefix(p), p
        assert a.admission_cost(p) == b.admission_cost(p), p
    sa, sb = a.stats(), b.stats()
    sa.pop("prefix_index"), sb.pop("prefix_index")
    assert sa == sb
    assert a.allocator._free == b.allocator._free     # exact LIFO state
    assert (a.block_table == b.block_table).all()


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 2 ** 16), min_size=8, max_size=48))
def test_tree_linear_differential(ops):
    """Random begin/grow/evict/restore/release/toggle streams drive a tree
    and a linear BlockManager in lockstep; every op must leave the two in
    the identical observable state (matches, costs, stats, allocator free
    list, block tables), fail identically (OutOfFrames parity), reclaim
    retained entries in the identical order under pressure, and shut down
    leak-free."""
    mgrs = (_mk("tree"), _mk("linear"))
    probes = [b[:k].copy() for b in _BASES for k in (3, 7, 12)]
    live: dict[int, np.ndarray] = {}     # seq -> prompt
    grown: dict[int, int] = {}           # seq -> positions written
    swapped: set[int] = set()            # tags (tag == seq here)

    def both(fn):
        outs, errs = [], []
        for m in mgrs:
            try:
                outs.append(fn(m))
                errs.append(None)
            except OutOfFrames as e:
                outs.append(None)
                errs.append(type(e))
        assert errs[0] == errs[1], errs  # OutOfFrames parity
        assert outs[0] == outs[1], outs
        return outs[0], errs[0]

    for x in ops:
        op, seq = (x >> 2) % 6, x % 3
        val = x >> 5
        if op == 0 and seq not in live and seq not in swapped:
            prompt = _BASES[val % 3][:2 + val % 11].copy()

            def begin(m, s=seq, p=prompt):
                n = m.begin_seq(s, p)
                for pos in range(min(n, len(p) - 1), len(p)):
                    m.ensure_writable(s, pos)
                return n
            _, err = both(begin)
            if err is None:
                live[seq] = prompt
                grown[seq] = len(prompt)
            else:                        # mid-prefill failure: same partial
                both(lambda m, s=seq: m.release_seq(s))
        elif op == 1 and seq in live:
            pos = grown[seq]
            if pos < 12:
                _, err = both(lambda m, s=seq, p=pos: m.ensure_writable(s, p))
                if err is None:
                    grown[seq] = pos + 1
        elif op == 2 and seq in live:
            both(lambda m, s=seq, c=val % 2: m.release_seq(s, completed=c))
            del live[seq], grown[seq]
        elif op == 3 and seq in live:
            swapped_pages, _ = both(lambda m, s=seq: m.evict_seq(s, s))
            if swapped_pages is not None:
                del live[seq]
                swapped.add(seq)
        elif op == 4 and seq in swapped and seq not in live:
            prompt = _BASES[val % 3][:4].copy()
            _, err = both(
                lambda m, s=seq, p=prompt: m.restore_seq(s, s, tokens=p))
            if err is None:
                swapped.discard(seq)
                live[seq] = prompt
                grown[seq] = 12          # restored pages: no regrow info
        elif op == 5:
            share = bool(val % 2)
            for m in mgrs:
                m.share_prefixes = share
        _observe(*mgrs, probes)

    for s in list(live):
        both(lambda m, q=s: m.release_seq(q, completed=True))
    _observe(*mgrs, probes)
    assert mgrs[0].shutdown() == mgrs[1].shutdown() == 0


def test_reclaim_order_under_pressure_matches_oracle():
    """LRU reclaim = coldest-leaf pruning: when allocation pressure drains
    the retention pool, both indexes must drop the same entries in the
    same order (observed through which prefixes still match)."""
    mgrs = (_mk("tree"), _mk("linear"))
    prompts = [np.asarray([g * 10 + 1, g * 10 + 2, g * 10 + 3, g * 10 + 4],
                          np.int32) for g in range(4)]
    for a in mgrs:
        for p in prompts:                # retain 4 x 2 pages = 8 (budget)
            a.begin_seq(0, p)
            for pos in range(len(p)):
                a.ensure_writable(0, pos)
            a.release_seq(0, completed=True)
    sa, sb = mgrs[0].stats(), mgrs[1].stats()
    assert sa["retained_entries"] == sb["retained_entries"] == 4
    # two big live sequences (12 pages against 6 free frames) force
    # reclaim, oldest retained entries first
    bigs = {1: np.arange(200, 212, dtype=np.int32),
            2: np.arange(300, 312, dtype=np.int32)}
    for m in mgrs:
        for s, big in bigs.items():
            m.begin_seq(s, big)
            for pos in range(len(big)):
                m.ensure_writable(s, pos)
    for p in prompts:
        assert mgrs[0]._match_prefix(p) == mgrs[1]._match_prefix(p)
    sa, sb = mgrs[0].stats(), mgrs[1].stats()
    assert sa["retained_reclaimed"] == sb["retained_reclaimed"] > 0
    # the survivors are the NEWEST entries: the oldest prompt no longer hits
    assert mgrs[0]._match_prefix(prompts[0]) == (0, None)
    for m in mgrs:
        for s in bigs:
            m.release_seq(s)
        assert m.shutdown() == 0


# -- serving layer: engine identity, score cache, telemetry --------------------
def _engine(prefix_index="tree", pool_pages=20, slots=4, max_len=32,
            **ecfg_kw):
    from repro.serve import EngineConfig, ServeEngine
    cfg = tiny_dense_cfg(vocab_size=64, kv_layout="pooled", kv_page_slots=4,
                         kv_pool_pages=pool_pages)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    return ServeEngine(model, params,
                       EngineConfig(slots=slots, max_len=max_len,
                                    prefix_index=prefix_index, **ecfg_kw))


def _shared_prefix_run(prefix_index: str, retain_frames=6):
    from repro.serve import Request, Scheduler
    rng = np.random.default_rng(3)
    system = rng.integers(0, 64, 8).astype(np.int32)
    with _engine(prefix_index, retain_frames=retain_frames) as engine:
        sched = Scheduler(engine)
        sched.submit([Request(
            uid=i,
            prompt=np.concatenate(
                [system, rng.integers(0, 64, 3).astype(np.int32)]),
            max_new_tokens=5) for i in range(6)])
        done = sched.run()
        tel = engine.telemetry()
        pool = engine.pool_stats()
    stats = engine.shutdown()
    return {r.uid: tuple(r.output) for r in done}, tel, pool, stats


def test_engine_tree_linear_identity():
    """Same shared-prefix workload, both indexes: token-identical outputs,
    identical telemetry (every latency an exact decode-step count, so
    equality is exact, not approximate) and identical counters -- down to
    the score-cache hits, because the tree bumps the epoch exactly where
    the linear path did."""
    out_t, tel_t, pool_t, stats_t = _shared_prefix_run("tree")
    out_l, tel_l, pool_l, stats_l = _shared_prefix_run("linear")
    assert out_t == out_l
    assert step_summary(tel_t) == step_summary(tel_l)
    assert pool_t.pop("prefix_index") == "tree"
    assert pool_l.pop("prefix_index") == "linear"
    assert pool_t == pool_l
    assert step_summary(stats_t.pop("telemetry")) == \
        step_summary(stats_l.pop("telemetry"))
    assert stats_t == stats_l


def test_engine_rejects_unknown_prefix_index():
    from repro.serve import EngineConfig, ServeEngine
    cfg = tiny_dense_cfg(vocab_size=64, kv_layout="pooled",
                         kv_page_slots=4, kv_pool_pages=8)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    with pytest.raises(ValueError, match="prefix_index"):
        ServeEngine(model, params, EngineConfig(slots=1, max_len=16,
                                                prefix_index="btree"))
    with pytest.raises(ValueError, match="prefix_index"):
        BlockManager(n_frames=4, n_seqs=1, max_lpages=2, page_slots=2,
                     prefix_index="btree")


def test_reserved_policy_forces_linear_index():
    """The reserved policy never matches or retains: its BlockManager has
    no tree regardless of the requested index."""
    bm = BlockManager(n_frames=12, n_seqs=2, max_lpages=6, page_slots=2,
                      policy="reserved", prefix_index="tree")
    assert bm.prefix_index == "linear" and bm._tree is None
    assert bm.shutdown() == 0


class _NeverCache(dict):
    """A score cache that never hits: ``get`` misses, stores are dropped."""

    def get(self, key, default=None):
        return None

    def __setitem__(self, key, value):
        pass


def test_scheduler_score_cache_hits_and_identity():
    """The epoch-keyed score cache must fire when free slots stand against
    an exhausted frame pool: the waiting window is re-scored every tick,
    and the decode steps in between mostly change nothing an admission
    cost depends on (the epoch only moves at page boundaries).  And it
    must be *pure* speedup: disabling it changes no output token and no
    admission timing."""
    from repro.serve import Request, Scheduler

    def run(cache: bool):
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 64, 8).astype(np.int32)
                   for _ in range(8)]
        # pool 4 pages = exactly one live sequence's worst case: the other
        # three slots stand free while the queue waits, and the single live
        # sequence only mutates the tables at page boundaries -- most
        # stepwise ticks re-score the window at an unchanged epoch
        with _engine("tree", pool_pages=4, slots=4,
                     max_fused_steps=1) as engine:
            sched = Scheduler(engine)
            if not cache:
                sched._score_cache = _NeverCache()
            sched.submit([Request(uid=i, prompt=p, max_new_tokens=6)
                          for i, p in enumerate(prompts)])
            done = sched.run()
            tel = engine.telemetry()
            hits = engine.counters["score_cache_hits"]
        engine.shutdown()
        return {r.uid: tuple(r.output) for r in done}, tel, hits

    out_c, tel_c, hits_c = run(cache=True)
    out_n, tel_n, hits_n = run(cache=False)
    assert hits_c > 0 and hits_n == 0
    assert out_c == out_n
    assert step_summary(tel_c) == step_summary(tel_n)


def test_score_cache_invalidated_by_epoch():
    """Any BlockManager mutation (here: a release) advances the epoch and
    invalidates cached scores -- a stale hit would mis-price the freed
    frames."""
    bm = BlockManager(n_frames=8, n_seqs=2, max_lpages=4, page_slots=2,
                      policy="on_demand", share_prefixes=True,
                      prefix_index="tree")
    e0 = bm.epoch
    bm.begin_seq(0, _toks(1, 2, 3))
    assert bm.epoch > e0
    e1 = bm.epoch
    bm.ensure_writable(0, 0)
    assert bm.epoch > e1
    e2 = bm.epoch
    assert bm.admission_cost(_toks(1, 2)) is not None   # queries: no bump
    assert bm.epoch == e2
    bm.release_seq(0)
    assert bm.epoch > e2
    e3 = bm.epoch
    bm.share_prefixes = False
    assert bm.epoch > e3
    assert bm.shutdown() == 0


def test_match_depth_telemetry():
    """A request admitted onto retained prefix pages records how deep the
    index match ran, in whole KV pages, in its trace row and the summary
    distribution."""
    from repro.serve import Request, Scheduler
    rng = np.random.default_rng(9)
    system = rng.integers(0, 64, 8).astype(np.int32)   # 2 pages at slots=4
    with _engine("tree", retain_frames=6) as engine:
        sched = Scheduler(engine)
        sched.submit([Request(uid=0, prompt=system, max_new_tokens=3)])
        sched.run()
        assert engine.blocks.stats()["retained_entries"] == 1
        sched.submit([Request(
            uid=1,
            prompt=np.concatenate(
                [system, rng.integers(0, 64, 2).astype(np.int32)]),
            max_new_tokens=3)])
        sched.run()
        rows = {r["uid"]: r for r in engine.metrics.request_rows()}
        assert rows[0]["match_depth_pages"] == 0       # cold admission
        assert rows[1]["match_depth_pages"] == 2       # 8 tokens = 2 pages
        dist = engine.telemetry()["prefix_match_depth_pages"]
        assert dist["n"] == 2 and dist["max"] == 2.0
    assert engine.shutdown()["leaked_frames"] == 0


def test_all_tier_leak_free_under_tree_index():
    """Swap + spill churn with retention on the tree index: every frame on
    every tier back to zero at shutdown (the leak detector is the
    acceptance bar the refactor must not move)."""
    from repro.serve import Request, Scheduler
    rng = np.random.default_rng(13)
    with _engine("tree", pool_pages=10, slots=4, retain_frames=4,
                 host_frames=6, spill_frames=8) as engine:
        sched = Scheduler(engine)
        sched.submit([Request(uid=i,
                              prompt=rng.integers(0, 64, 6).astype(np.int32),
                              max_new_tokens=8) for i in range(8)])
        done = sched.run()
        assert len(done) == 8
        assert engine.blocks.prefix_index == "tree"
    stats = engine.shutdown()
    assert stats["leaked_frames"] == 0

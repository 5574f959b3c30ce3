"""Batched serving engine: continuous batching over fixed decode slots.

The engine owns a fixed-capacity decode batch (B slots).  Requests are
admitted by the scheduler into free slots, prefilled one at a time (their KV
written into the slot), then advanced together by the shared decode step --
the standard continuous-batching pattern (vLLM/Orca) on top of this repo's
model facade.

KV frame ownership and *residency* are unified behind one refcounted
:class:`repro.emem_vm.BlockManager`: every sequence goes through a
logical->frame block table that rides in the cache pytree (``cache["vm"]``)
into the paged-attention kernel.  The two paged ``kv_layout`` values are
just allocation policies:

  * ``"paged"``  -- *reserved*: every slot permanently owns its worst-case
    ``max_pages`` frames (the fixed slots x max_pages layout, now expressed
    as a static block table);
  * ``"pooled"`` -- *on-demand*: frames come from the shared pool as each
    sequence grows and return at completion.  On top of the indirection:

      - **prefix sharing / copy-on-write**: admission matches the prompt
        against the retention pool and live sequences' prompts;
        common-prefix pages are shared (refcount++, read-only via the
        ``frame_ro`` bit in ``cache["vm"]``) and prefill resumes after the
        shared tokens.  The first divergent write copies the page to a
        private frame (BlockManager ``CowCopy`` records, applied to the
        device pages before the step).
      - **swap-preemption**: ``can_admit`` reserves only what the
        admission immediately needs (not the worst case), so the pool packs
        optimistically.  When a growing sequence finds the pool exhausted,
        the youngest sequence is preempted -- its frames move to the HOST
        tier (``BlockManager.evict_seq``) and the request is requeued.
        Re-admission is a *swap-in* (``restore_seq``), not a re-prefill:
        the engine trades prefill FLOPs for PCIe bytes.  With
        ``spill_frames > 0`` the host tier is itself actively managed: on
        host-store pressure the BlockManager demotes host pages one tier
        further down into the file/bytes-backed spill store, and a restore
        promotes them back (``SPILL -> HOST -> DEVICE``).  Recompute is the
        *last* resort only: when swapping is off
        (``preempt_mode="recompute"``) or BOTH backing tiers are full, the
        PR 2 path still applies -- the request requeues with its generated
        tokens as a prompt extension and deterministic greedy decode makes
        the re-run token-identical.
      - **prefix retention**: with ``retain_frames > 0`` completed prompts'
        prefix pages stay alive in the BlockManager's bounded LRU pool, so
        a system prompt survives idle gaps between requests.
      - **next-page prefetch**: pooled decode knows the next page a
        sequence will need; the frame is allocated one token before the
        page-boundary write instead of on it (``BlockManager.prefetch``).

The engine itself carries no residency branching: it calls ``evict_seq`` /
``restore_seq`` / ``release_seq`` and mechanically applies the page moves
the BlockManager decides on, via the :class:`repro.emem_vm.PageIO`
callbacks bound at construction.

**Fused multi-step decode.**  The steady-state token loop does not cross
the host boundary once per step: before each ``step()`` the engine caps
the run at the first budget / ``max_len`` completion, *stages* it against
the BlockManager (:meth:`BlockManager.stage_fused_run` pre-allocates the
boundary prefetches the stepwise loop would have granted, so page
boundaries no longer end a run; only growth-after-declined-prefetch,
copy-on-write or end-of-table do), and executes the whole plan as one
jitted ``lax.while_loop``
(:func:`repro.serve.fused_decode.fused_decode_run`) with greedy argmax
sampling in-kernel -- the staged (lpage, frame) mappings ride in as
per-iteration columns the device applies to the carried vm tables, and
the plan is committed afterwards for the steps that actually ran.  One ``int32[cap, B]`` token buffer crosses the host
boundary per run, and the engine then replays the per-step bookkeeping
(token attribution, ``StepClock`` time, budgets, completion checks)
host-side from that buffer -- byte-for-byte what the stepwise path would
have recorded.  ``EngineConfig.max_fused_steps=1`` reproduces
step-at-a-time dispatch exactly.

**Host spans.**  Each boundary of the hot loop opens a span of the
engine's :class:`Telemetry` (``self.metrics.span``; a no-op until
``metrics.start_spans()``): ``admit`` and ``step`` around the two entry
points; inside them ``prefix_match`` (``begin_seq`` / ``restore_seq``),
``grow`` (``_grow``, copy-on-write and preemption included),
``sync_tables`` (a block-table push), ``inputs`` (device inputs built from
host values), ``dispatch`` (the call into a jitted decode until it
returns), ``device_wait`` (a host read of a device value), ``stage_run``
(planning a fused run), ``replay`` (the host bookkeeping of a run's
tokens) and ``page_io`` (page moves between tiers and copy-on-write).  The
BlockManager itself carries no telemetry.

``ServeEngine`` is a context manager: ``with ServeEngine(...) as eng:``
guarantees the shutdown leak detector runs even when the body raises
(active requests are aborted first so the original exception propagates).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import Model
from repro.serve.telemetry import Telemetry


def _init_cache(model: Model, slots: int, max_len: int) -> dict:
    """The zero cache pytree.  Under a mesh context with several KV shards
    the page pool is created already sharded over the KV axes, so each
    device holds its share of the frames from the start; every other leaf
    is replicated."""
    from repro.parallel import mesh_ctx
    from repro.parallel.paged_attention import paged_entries
    ctx = mesh_ctx.get_context()
    if ctx is None or ctx.n_kv_shards == 1 \
            or model.cfg.kv_layout not in ("paged", "pooled"):
        return model.init_cache(slots, max_len)
    from jax.sharding import NamedSharding, PartitionSpec as P
    kv = ctx.kv_axes if len(ctx.kv_axes) > 1 else ctx.kv_axes[0]
    pages = NamedSharding(ctx.mesh, P(None, kv))
    shapes = jax.eval_shape(lambda: model.init_cache(slots, max_len))
    shardings = jax.tree.map(lambda _: NamedSharding(ctx.mesh, P()), shapes)
    for key, _ in paged_entries(shapes):
        shardings[key] = {"k_pages": pages, "v_pages": pages}
    return jax.jit(lambda: model.init_cache(slots, max_len),
                   out_shardings=shardings)()


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray               # [S] int32
    max_new_tokens: int
    output: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class EngineConfig:
    slots: int = 4
    max_len: int = 256
    eos_id: int | None = None
    greedy: bool = True
    #: "swap" parks preempted sequences' pages on host and resumes them with
    #: a swap-in; "recompute" is the PR 2 requeue-and-re-prefill baseline.
    preempt_mode: str = "swap"
    #: device frames the BlockManager may keep holding completed prompts'
    #: prefix pages (0 disables the retention pool)
    retain_frames: int = 0
    #: host backing-store frames (None: one per device frame)
    host_frames: int | None = None
    #: third-tier spill-store frames the host tier demotes into under
    #: capacity pressure (0 disables the spill tier: host-full falls back
    #: to recompute exactly as before)
    spill_frames: int = 0
    #: directory backing the spill store (None: in-memory bytes)
    spill_path: str | None = None
    #: sliding-window size of the rolling TTFT monitor
    #: (telemetry.RollingMonitor: median + spike/regression detection)
    telemetry_window: int = 32
    #: upper bound on the decode steps fused into one jitted while-loop
    #: run between control-plane events (module docstring); ``1``
    #: reproduces step-at-a-time dispatch exactly
    max_fused_steps: int = 8
    #: prompt prefix index backing the BlockManager's match + retention
    #: pool: "tree" (radix tree, O(prompt-length) lookup) or "linear"
    #: (the retired scan-every-candidate oracle, kept for one PR)
    prefix_index: str = "tree"


class ServeEngine:
    def __init__(self, model: Model, params, ecfg: EngineConfig):
        if ecfg.preempt_mode not in ("swap", "recompute"):
            raise ValueError(f"unknown preempt_mode {ecfg.preempt_mode!r}")
        if ecfg.max_fused_steps < 1:
            raise ValueError(
                f"max_fused_steps must be >= 1, got {ecfg.max_fused_steps}")
        if ecfg.prefix_index not in ("tree", "linear"):
            raise ValueError(f"unknown prefix_index {ecfg.prefix_index!r}")
        self.model = model
        self.params = params
        self.ecfg = ecfg
        self.cache = _init_cache(model, ecfg.slots, ecfg.max_len)
        self.lengths = jnp.zeros((ecfg.slots,), jnp.int32)
        self.slot_req: list[Request | None] = [None] * ecfg.slots
        self.budget = np.zeros(ecfg.slots, np.int64)
        #: requests preempted since the last drain (scheduler requeues them)
        self.preempted: list[Request] = []
        #: requests completed since the last drain (scheduler accounts
        #: them).  Completion can happen inside admission-time preemption,
        #: before the request ever appears in a scheduler slot snapshot, so
        #: polling ``slot_req`` around ``step()`` misses it -- the engine is
        #: the only party that sees every completion
        self.completed_reqs: list[Request] = []
        self._admit_seq = np.zeros(ecfg.slots, np.int64)  # admission order
        self._admit_counter = 0
        #: positions per slot whose KV writes have actually committed (the
        #: decode ran); lengths may run one ahead mid-step, and a swap-out
        #: must only trust committed KV
        self._kv_committed = np.zeros(ecfg.slots, np.int64)
        self._shutdown_stats: dict | None = None
        #: per-request SLO telemetry (lifecycle traces, TTFT/ITL
        #: percentiles, rolling monitor); its StepClock ticks once per
        #: jitted decode, so every latency is decode-step denominated
        self.metrics = Telemetry(monitor_window=ecfg.telemetry_window)
        self.counters = {"admitted": 0, "completed": 0, "preempted": 0,
                         "swapped": 0, "swap_resumed": 0, "aborted": 0,
                         "decode_steps": 0, "dispatches": 0,
                         "shared_prompt_tokens": 0, "leaked_frames": 0,
                         "score_cache_hits": 0,
                         # tokens admit() ran through the decode path
                         "prefill_tokens": 0,
                         # bytes of block tables pushed to the device
                         "table_push_bytes": 0}
        cfg = model.cfg
        if cfg.kv_layout in ("paged", "pooled"):
            from repro.emem_vm import BlockManager, PageIO
            self.page_slots = cfg.kv_page_slots
            self.max_lpages = -(-ecfg.max_len // self.page_slots)
            if cfg.kv_layout == "pooled":
                policy = "on_demand"
                self.n_frames = (cfg.kv_pool_pages
                                 or ecfg.slots * self.max_lpages)
            else:
                policy = "reserved"
                self.n_frames = ecfg.slots * self.max_lpages
            # prefix sharing skips prefill of shared tokens, which is only
            # sound when every layer's per-token state lives in the shared
            # KV pages (no recurrent SSM state to rebuild); swap does not
            # care -- evicted slots' recurrent state is saved and restored
            # alongside the pages.  Retention rides on prefix sharing, so
            # asking for it on a model that cannot share is an error, not a
            # silent no-op.
            attn_only = all(cfg.layer_kind(i) == "attn"
                            for i in range(cfg.layer_period))
            if ecfg.retain_frames > 0 and not attn_only:
                raise ValueError(
                    "retain_frames requires an attention-only model: "
                    "retained pages hold KV only, and an admission that "
                    "skips prefill cannot rebuild recurrent (SSM) state")
            self.blocks = BlockManager(
                self.n_frames, ecfg.slots, self.max_lpages, self.page_slots,
                policy=policy, share_prefixes=attn_only,
                n_host_frames=ecfg.host_frames,
                retain_frames=ecfg.retain_frames,
                swap_enabled=ecfg.preempt_mode == "swap",
                n_spill_frames=ecfg.spill_frames,
                spill_path=ecfg.spill_path,
                prefix_index=ecfg.prefix_index)
            from repro.parallel.paged_attention import (read_frame_pages,
                                                        write_frame_pages)
            self.blocks.page_io = PageIO(read=self._read_frame_pages,
                                         write=self._apply_frame_writes)
            self._read_pages = read_frame_pages
            self._write_frame_pages = write_frame_pages
            self.blocks.dirty = True     # push the initial (empty) tables
        else:
            self.blocks = None

    # -- context manager ------------------------------------------------------
    def __enter__(self) -> "ServeEngine":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        """Run the shutdown leak detector on every exit path.  When the body
        raised, active requests are aborted first and a secondary shutdown
        failure is swallowed so the original exception propagates."""
        try:
            self.shutdown(abort=exc_type is not None)
        except Exception:
            if exc_type is None:
                raise
        return False

    def _decode(self, params, toks, cache, lengths, write_mask=None):
        """One jitted decode with greedy sampling in-jit, synced before
        returning.  Returns ``(sampled, logits, cache)``: ``sampled`` is
        the host-side ``int32[B]`` greedy argmax -- the only device value
        the hot path transfers -- and ``logits`` stays on device (tests
        and diagnostics may read it; the engine does not).

        ``write_mask`` limits which slots commit cache writes this step --
        decode runs the full batch, so without it a prefill would overwrite
        every other in-flight slot's newest KV position (and SSM state) with
        pad-token state.

        The sync matters: XLA CPU async dispatch has been seen to corrupt
        results when executions of the same executable overlap, as they do
        in the prefill loop which never reads its outputs between tokens.
        Materializing ``sampled`` blocks on the execution, which
        serializes consecutive dispatches; a fused run is ONE dispatch, so
        the same one-sync-per-dispatch rule costs it a single sync at loop
        exit (see :meth:`_step_fused`).  (The host-side ``toks`` and
        ``write_mask`` are always *copied* in with ``jnp.array`` --
        ``jnp.asarray`` zero-copies numpy memory, racing later in-place
        mutation of the same buffer.)
        """
        from repro.serve.fused_decode import sampled_decode_step
        if write_mask is None:
            write_mask = np.ones(self.ecfg.slots, bool)
        span = self.metrics.span
        with span("inputs"):
            toks, write_mask = jnp.array(toks), jnp.array(write_mask)
        with span("dispatch", steps=1):
            sampled, logits, cache = sampled_decode_step(
                self.model, params, toks, cache, lengths, write_mask)
        with span("device_wait"):
            sampled = np.asarray(sampled)    # the one host transfer + sync
        self.counters["decode_steps"] += 1
        self.counters["dispatches"] += 1
        self.metrics.clock.tick()
        return sampled, logits, cache

    # -- frame management (both paged layouts, via the BlockManager) ---------
    def _read_frame_pages(self, frames) -> list:
        """PageIO read callback: snapshot device frames to host payloads."""
        with self.metrics.span("page_io", pages=len(frames)):
            return self._read_pages(self.cache, frames)

    def _apply_frame_writes(self, assignments) -> None:
        """PageIO write callback: scatter host payloads into device frames."""
        with self.metrics.span("page_io", pages=len(assignments)):
            self.cache = self._write_frame_pages(self.cache, assignments)

    def _slot_state_read(self, slot: int) -> dict:
        """Snapshot a slot's non-paged per-slot cache state (SSM conv/ssd
        rows) so a swapped-out sequence can resume without replaying it."""
        from repro.parallel.paged_attention import slot_state_entries
        return {key: {name: np.asarray(arr[:, slot])
                      for name, arr in entry.items()}
                for key, entry in slot_state_entries(self.cache)}

    def _slot_state_write(self, slot: int, state: dict) -> None:
        for key, sub in state.items():
            entry = dict(self.cache[key])
            for name, arr in sub.items():
                entry[name] = entry[name].at[:, slot].set(
                    jnp.asarray(arr, entry[name].dtype))
            self.cache[key] = entry

    def _tokens_for(self, req: Request) -> np.ndarray:
        """The tokens a (re-)admission must account for: the prompt plus any
        tokens generated before a preemption.  An empty prompt becomes one
        implicit BOS so ``logits`` is always bound."""
        toks = np.asarray(req.prompt, np.int32).ravel()
        if req.output:
            toks = np.concatenate([toks,
                                   np.asarray(req.output, np.int32)])
        return toks if len(toks) else np.zeros(1, np.int32)

    def _swap_tag(self, req: Request):
        swap = getattr(req, "_swap", None)
        return swap["tag"] if swap is not None else None

    def _grow(self, slot: int, new_len: int, lengths: np.ndarray) -> bool:
        """Back position ``new_len - 1`` of ``slot`` with a writable frame,
        applying any copy-on-write and preempting the youngest sequence on
        pool exhaustion.  Returns False iff ``slot`` itself was preempted."""
        if self.blocks is None:
            return True
        from repro.emem_vm import OutOfFrames
        with self.metrics.span("grow") as sp:
            preemptions = 0
            while True:
                try:
                    copies = self.blocks.ensure_writable(slot, new_len - 1)
                except OutOfFrames:
                    victim = max(
                        (i for i, r in enumerate(self.slot_req)
                         if r is not None),
                        key=lambda s: self._admit_seq[s])
                    self._preempt(victim, lengths)
                    preemptions += 1
                    sp["preemptions"] = preemptions
                    if victim == slot:
                        return False
                    continue
                if copies:
                    from repro.parallel.paged_attention import cow_copy_pages
                    sp["cow_pages"] = len(copies)
                    with self.metrics.span("page_io", pages=len(copies)):
                        self.cache = cow_copy_pages(self.cache, copies)
                return True

    def _is_complete(self, req: Request, cur_len: int) -> bool:
        """The post-decode completion conditions, evaluable host-side: used
        at preemption so a request evicted right after its final token is
        finished, not re-run (an extra decode would break token identity)."""
        hit_eos = (self.ecfg.eos_id is not None
                   and req.output and req.output[-1] == self.ecfg.eos_id)
        return (len(req.output) >= req.max_new_tokens or hit_eos
                or cur_len >= self.ecfg.max_len - 1)

    def _preempt(self, slot: int, lengths: np.ndarray) -> None:
        """Evict ``slot``.  The BlockManager decides residency: when the
        swap tier is available the sequence's pages move to the host store
        and re-admission swaps them back in; otherwise its frames are freed
        and the generated tokens ride along as a prompt extension so the
        greedy re-run is token-identical.  A request that had already
        produced its last token completes instead of requeueing."""
        req = self.slot_req[slot]
        cur_len = int(lengths[slot])
        committed = int(self._kv_committed[slot])
        self.slot_req[slot] = None
        self.budget[slot] = 0
        lengths[slot] = 0
        self._kv_committed[slot] = 0
        if self._is_complete(req, cur_len):
            self._release(slot)
            req.done = True
            self.counters["completed"] += 1
            self.completed_reqs.append(req)
            self.metrics.on_complete(req)
            return
        swapped = False
        if self.blocks is not None:
            tag = id(req)
            if self.blocks.evict_seq(slot, tag) is not None:
                # resume state: committed KV length, the pending next token
                # (only valid when every committed position was decoded),
                # and the slot's recurrent (SSM) state
                req._swap = {"tag": tag, "committed": committed,
                             "next": getattr(req, "_next", None),
                             "slot_state": self._slot_state_read(slot)}
                self.counters["swapped"] += 1
                swapped = True
            else:
                self.blocks.release_seq(slot, completed=False)
        self.counters["preempted"] += 1
        self.metrics.on_preempt(req, swapped=swapped)
        self.preempted.append(req)

    def drain_preempted(self) -> list[Request]:
        out, self.preempted = self.preempted, []
        return out

    def drain_completed(self) -> list[Request]:
        """Requests completed since the last drain, wherever the completion
        happened (a decode step or a preemption that found the final token
        already landed)."""
        out, self.completed_reqs = self.completed_reqs, []
        return out

    def _release(self, slot: int) -> None:
        if self.blocks is not None:
            self.blocks.release_seq(slot, completed=True)

    def _sync_vm(self) -> None:
        """Push the BlockManager tables into the cache pytree if changed."""
        if self.blocks is not None and self.blocks.dirty:
            with self.metrics.span("sync_tables") as sp:
                tables = self.blocks.tables()
                self.cache["vm"] = {k: jnp.array(v)
                                    for k, v in tables.items()}
                self.blocks.dirty = False
                nbytes = sum(v.nbytes for v in tables.values())
                sp["bytes"] = nbytes
            self.counters["table_push_bytes"] += nbytes

    def pool_stats(self) -> dict:
        if self.blocks is None:
            return {}
        return self.blocks.stats()

    def telemetry(self) -> dict:
        """Live per-request SLO telemetry summary: exact p50/p95/p99 TTFT,
        inter-token-latency and queue-wait percentiles over completed
        requests (decode-step denominated) plus the rolling-monitor state.
        The same snapshot is folded into the ``shutdown()`` stats under
        the ``"telemetry"`` key."""
        return self.metrics.summary()

    def shutdown(self, abort: bool = False) -> dict:
        """Leak detector: at shutdown every frame reference -- device, host
        AND spill tier -- must have been released (the BlockManager drains
        its retention pool and unclaimed swap records first; a drained pool
        counts as zero).  A host- or spill-store leak fails shutdown
        exactly like a device leak: parked payloads nobody can restore are
        silently lost capacity.  Idempotent: a second call returns the
        recorded stats dict -- the telemetry summary is snapshotted into it
        ONCE, on the first call (abort paths included), so every later
        caller sees the same dict, telemetry keys and all.  ``abort=True``
        releases still-active requests instead of refusing (the
        context-manager exit path when the body raised).  Returns the
        engine counters (dispatch_stats-style) plus the ``"telemetry"``
        section; raises if any sequence is still active or any frame
        leaked."""
        if self._shutdown_stats is not None:
            return self._shutdown_stats
        active = [r.uid for r in self.slot_req if r is not None]
        if active and not abort:
            raise RuntimeError(f"shutdown with active requests {active}")
        for i, r in enumerate(self.slot_req):
            if r is None:
                continue
            self.slot_req[i] = None
            self.counters["aborted"] += 1
            self.metrics.on_abort(r)
            if self.blocks is not None:
                self.blocks.release_seq(i, completed=False)
        leaked = self.blocks.shutdown() if self.blocks is not None else 0
        tiers = (self.blocks.leak_counts() if self.blocks is not None
                 else {"device": 0, "host": 0, "spill": 0})
        self.counters["leaked_frames"] = leaked
        stats = dict(self.counters)
        stats.update({f"leaked_{t}_frames": n for t, n in tiers.items()})
        if self.blocks is not None:
            stats.update(self.blocks.counters)
            stats["shared_prompt_tokens"] = \
                self.blocks.counters["shared_tokens"]
        # snapshot the telemetry summary into the dict BEFORE caching, so
        # repeated shutdown() calls (abort-first included) all return the
        # identical dict with the recorded SLO section
        stats["telemetry"] = self.metrics.summary()
        if leaked:
            raise RuntimeError(
                f"KV frame leak at shutdown: {leaked} frames still "
                f"referenced ({tiers}; {stats})")
        self._shutdown_stats = stats
        return stats

    # -- admission ----------------------------------------------------------
    def free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def admission_cost(self, req: Request):
        """Residency cost terms for admitting ``req`` right now (an
        :class:`repro.emem_vm.AdmissionCost`), or None when there is no
        BlockManager (the batch layout carries no residency signal and any
        score built on top must degenerate to FIFO)."""
        if self.blocks is None:
            return None
        return self.blocks.admission_cost(self._tokens_for(req),
                                          tag=self._swap_tag(req))

    def can_admit(self, req: Request, cost=None) -> bool:
        """Admission control: the request must fit the engine at all (room
        for at least one generated token under max_len) and have a free
        slot.  With a frame pool, admission is *optimistic*: only the pages
        the admission immediately needs -- after consulting the retention
        pool and the live prefix match, or the swap record for a preempted
        request -- must be coverable, counting what reclaiming retained
        pages would free.  Decode-time growth is covered by preemption, not
        a worst-case reservation.  ``cost`` may pass an
        :meth:`admission_cost` result already in hand (the scheduler
        scores and checks every window candidate off one query)."""
        toks = self._tokens_for(req)
        if len(toks) > self.ecfg.max_len - 2:
            return False
        if not self.free_slots():
            return False
        if self.blocks is None:
            return True
        if cost is None:
            cost = self.blocks.admission_cost(toks, tag=self._swap_tag(req))
        return cost.admissible

    def admit(self, req: Request, slot: int) -> None:
        """Admit a request into a slot.

        A swapped-out request *resumes*: its pages swap back in from the
        host store, its recurrent state is restored, and only tokens beyond
        the committed KV (at most the one token appended mid-preemption)
        are decoded -- no re-prefill.  A fresh request prefills token by
        token through the decode path (so this works for every KV layout);
        prompt pages shared with the retention pool or a live sequence are
        skipped: prefill resumes at the first unshared token (the last
        prompt token always re-runs to bind the next-token logits; its
        write to a still-shared frame is dropped by the kernel's
        ``frame_ro`` bit)."""
        assert self.slot_req[slot] is None
        if not self.can_admit(req):      # before any state is mutated
            raise RuntimeError(
                "inadmissible request (prompt too long for max_len, or no "
                "free-frame headroom)")
        with self.metrics.span("admit", uid=req.uid, prompt=len(req.prompt),
                               shared=0, prefilled=0) as sp:
            self._admit(req, slot, sp)

    def _admit(self, req: Request, slot: int, sp) -> None:
        """The body of :meth:`admit`; ``sp`` is its span."""
        span = self.metrics.span
        toks = self._tokens_for(req)
        self.slot_req[slot] = req
        self.budget[slot] = req.max_new_tokens - len(req.output)
        self._admit_counter += 1
        self._admit_seq[slot] = self._admit_counter
        swap = getattr(req, "_swap", None)
        if swap is not None and self.blocks is not None \
                and self.blocks.has_swap(swap["tag"]):
            # no _reset_slot: the restore overwrites every per-slot field it
            # would zero (lengths, committed KV, the whole slot state)
            swap_in0 = self.blocks.counters["swap_in_pages"]
            spill_in0 = self.blocks.counters["spill_in_pages"]
            with span("prefix_match") as pm:
                self.blocks.restore_seq(slot, swap["tag"], toks)
                swap_in = self.blocks.counters["swap_in_pages"] - swap_in0
                pm["swap_in_pages"] = swap_in
            self.metrics.on_admit(
                req, resumed=True, swap_in_pages=swap_in,
                spill_in_pages=self.blocks.counters["spill_in_pages"]
                - spill_in0)
            with span("inputs"):
                self._slot_state_write(slot, swap["slot_state"])
            start = int(swap["committed"])
            req._next = swap["next"]
            del req._swap
            self.counters["swap_resumed"] += 1
            with span("device_wait"):
                lengths = np.array(self.lengths)
            lengths[slot] = start
            with span("inputs"):
                self.lengths = jnp.array(lengths)
            self._kv_committed[slot] = start
            if start >= len(toks):
                # fully committed: KV, recurrent state and the pending next
                # token were all restored -- nothing to decode
                self.counters["admitted"] += 1
                return
        else:
            self._reset_slot(slot)
            shared = 0
            if self.blocks is not None:
                with span("prefix_match") as pm:
                    shared = self.blocks.begin_seq(slot, toks)
                    pm["shared"] = shared
                self.counters["shared_prompt_tokens"] += shared
                sp["shared"] = shared
            self.metrics.on_admit(
                req, shared_tokens=shared,
                match_depth_pages=-(-shared // self.page_slots)
                if self.blocks is not None else 0)
            start = min(shared, len(toks) - 1)
        mask = np.zeros(self.ecfg.slots, bool)
        mask[slot] = True                # only this slot commits KV writes
        with span("device_wait"):
            lengths = np.array(self.lengths)
        # invariant allocations hoisted out of the prefill loop (prep for
        # chunked prefill): the token batch is reused across steps, and the
        # device lengths advance from a base with ``.at[slot].set`` instead
        # of a full host->device rebuild per token.  jnp.array (copy=True),
        # NOT jnp.asarray, for anything built from ``lengths``/``tok_batch``:
        # asarray zero-copies the numpy buffer on CPU, and with async
        # dispatch the in-flight decode would race the next iteration's
        # in-place mutation
        tok_batch = np.zeros((self.ecfg.slots, 1), np.int32)
        with span("inputs"):
            base = jnp.array(lengths)
        for t in range(start, len(toks)):
            lengths[slot] = t + 1
            with span("inputs"):
                self.lengths = base.at[slot].set(t + 1)
            n_pre = len(self.preempted)
            if not self._grow(slot, t + 1, lengths):
                sp["prefilled"] = t - start
                return          # preempted mid-prefill; requeued for retry
            if len(self.preempted) != n_pre:
                # a growth preemption zeroed a victim's length host-side;
                # refresh the device base to match
                with span("inputs"):
                    base = jnp.array(lengths)
            tok_batch[slot, 0] = toks[t]
            self._sync_vm()
            sampled, _, self.cache = self._decode(
                self.params, tok_batch, self.cache, self.lengths, mask)
            self._kv_committed[slot] = t + 1
            self.counters["prefill_tokens"] += 1
        sp["prefilled"] = len(toks) - start
        req._next = int(sampled[slot])
        self.metrics.on_token(req, len(req.output))
        self.counters["admitted"] += 1

    def _reset_slot(self, slot: int) -> None:
        span = self.metrics.span
        with span("device_wait"):
            lengths = np.array(self.lengths)
        lengths[slot] = 0
        self._kv_committed[slot] = 0
        with span("inputs"):
            self.lengths = jnp.array(lengths)
            # per-slot state (recurrent SSM rows, batch-layout KV) is
            # zeroed: recurrent state is cumulative, so a reused slot must
            # not leak the previous tenant's state into the new sequence
            from repro.parallel.paged_attention import slot_state_entries
            for key, entry in slot_state_entries(self.cache):
                e = dict(entry)
                for name, arr in e.items():
                    e[name] = arr.at[:, slot].set(0)
                self.cache[key] = e

    # -- decode -------------------------------------------------------------
    def _fused_horizon(self, order, lengths, max_steps: int | None) -> int:
        """Completion cap on a fused run: steps until the first active
        slot completes on budget or ``max_len`` (the completing step may
        BE the last run step, since completion handling happens after the
        run), bounded by ``max_fused_steps`` and ``max_steps`` (the
        scheduler's external bound -- e.g. steps until the next trace
        arrival).  Block-table feasibility is no longer part of this
        bound: the BlockManager *stages* the run
        (:meth:`BlockManager.stage_fused_run`), pre-allocating the
        boundary prefetches the stepwise loop would have granted, so page
        boundaries no longer end a run -- only events that cannot be
        staged (growth after a declined prefetch, copy-on-write, end of
        table) shorten the plan.  EOS cannot be bounded host-side -- the
        fused loop itself exits on it."""
        cap = self.ecfg.max_fused_steps
        if max_steps is not None:
            cap = min(cap, max_steps)
        for i in order:
            if cap <= 1:
                return 1
            cap = min(cap, int(self.budget[i]),
                      self.ecfg.max_len - 1 - int(lengths[i]))
        return max(cap, 1)

    def _step_fused(self, order, horizon: int, plan=None) -> int:
        """Run ``horizon`` decode steps (fewer on an EOS exit) as one
        jitted while-loop dispatch, then replay the per-step bookkeeping
        host-side from the sampled-token buffer -- byte-for-byte the
        counters, timestamps, budgets and completion decisions the
        stepwise path would have produced.  The staged ``plan`` owns the
        run's boundary prefetches: their (lpage, frame) mappings ride
        into the loop as per-iteration columns the device applies to the
        carried vm tables, and after the run the plan is committed for
        the steps that actually executed (EOS may end the run early) --
        unreached stagings are returned to the allocator with no counter
        traffic.  No other frame growth, preemption or admission
        opportunity can occur inside the run, so none of that code needs
        to run here."""
        from repro.serve.fused_decode import fused_decode_run
        span = self.metrics.span
        cap = int(self.ecfg.max_fused_steps)
        with span("device_wait"):
            lengths0 = np.array(self.lengths)
        with span("inputs"):
            active = np.zeros(self.ecfg.slots, bool)
            toks = np.zeros((self.ecfg.slots, 1), np.int32)
            staged_lp = np.full((self.ecfg.slots, cap), -1, np.int32)
            staged_fm = np.full((self.ecfg.slots, cap), -1, np.int32)
            for i in order:
                active[i] = True
                toks[i, 0] = self.slot_req[i]._next
            if plan is not None:
                for st in plan.allocs:
                    if st.k_hit < horizon:   # applied by iteration k_hit;
                        staged_lp[st.seq, st.k_hit] = st.lpage
                        staged_fm[st.seq, st.k_hit] = st.frame
                    # k_hit == horizon stagings commit host-side only --
                    # the dirty flag re-syncs the device tables before the
                    # next dispatch, exactly like a stepwise trailing
                    # prefetch
            eos = -1 if self.ecfg.eos_id is None else int(self.ecfg.eos_id)
            run_in = (jnp.array(active), jnp.int32(horizon), jnp.int32(eos),
                      jnp.array(staged_lp), jnp.array(staged_fm))
            toks_in = jnp.array(toks)
        self._sync_vm()
        with span("dispatch", steps=horizon):
            buf, n_done, self.cache, self.lengths = fused_decode_run(
                self.model, cap, self.params, toks_in, self.cache,
                self.lengths, *run_in)
        with span("device_wait"):
            buf = np.asarray(buf)        # the one host sync of the run
            n = int(n_done)
        seen = time.perf_counter_ns()    # when the host saw the run's tokens
        with span("replay", tokens=n * len(order)):
            if plan is not None:
                self.blocks.commit_fused_run(plan, n)
            self.counters["decode_steps"] += n
            self.counters["dispatches"] += 1
            c0 = self.metrics.clock.now()
            self.metrics.clock.tick(n)
            # token attribution: iteration k fed the pending ``_next``
            # (k == 0) or buf[k-1], and its decode (at clock c0 + k + 1)
            # sampled buf[k]
            for k in range(n):
                for i in order:
                    req = self.slot_req[i]
                    req.output.append(int(toks[i, 0]) if k == 0
                                      else int(buf[k - 1, i]))
                    self.metrics.on_token(req, len(req.output),
                                          at=c0 + k + 1, seen_ns=seen)
            for i in sorted(order):      # stepwise parity: slot-index order
                req = self.slot_req[i]
                new_len = int(lengths0[i]) + n
                self._kv_committed[i] = new_len
                req._next = int(buf[n - 1, i])
                self.budget[i] -= n
                hit_eos = (self.ecfg.eos_id is not None and req.output
                           and req.output[-1] == self.ecfg.eos_id)
                if self.budget[i] <= 0 or hit_eos or \
                        new_len >= self.ecfg.max_len - 1:
                    req.done = True
                    self.slot_req[i] = None
                    self.counters["completed"] += 1
                    self.completed_reqs.append(req)
                    self.metrics.on_complete(req)
                    self._kv_committed[i] = 0
                    self._release(i)
        return n

    def step(self, max_steps: int | None = None) -> int:
        """Advance every active slot by one decode step -- or, when the
        fused horizon allows, by a whole jitted run of them.  Returns the
        number of decode steps executed (0 when idle), so the scheduler
        can age its queue in real decode steps.

        ``max_steps`` bounds the fused run externally (the trace replayer
        caps it at the next arrival so arrival timestamps are unchanged);
        ``None`` leaves ``EngineConfig.max_fused_steps`` as the bound.

        On the stepwise path, frame growth runs oldest-sequence-first so
        that on pool exhaustion the youngest sequences are preempted while
        the oldest keep making progress (guaranteeing liveness).  After
        growing, the next page boundary each survivor will cross is
        prefetched (allocated one token early) so the boundary step never
        waits on the allocator.  A fused run never contains any of those
        events -- that is what makes it safe to fuse (see
        :meth:`_fused_horizon`)."""
        with self.metrics.span("step", iterations=0, seqs=0) as sp:
            return self._step(max_steps, sp)

    def _step(self, max_steps: int | None, sp) -> int:
        """The body of :meth:`step`; ``sp`` is its span, given the decode
        iterations run and the sequences each decoded."""
        span = self.metrics.span
        order = sorted((i for i, r in enumerate(self.slot_req)
                        if r is not None),
                       key=lambda s: self._admit_seq[s])
        if not order:
            return 0
        with span("device_wait"):
            lengths_np = np.asarray(self.lengths)
        with span("stage_run") as st:
            horizon = self._fused_horizon(order, lengths_np, max_steps)
            plan = None
            if horizon > 1 and self.blocks is not None:
                plan = self.blocks.stage_fused_run(
                    order, [int(lengths_np[i]) for i in order], horizon)
                if plan.n <= 1:          # immediate growth/COW: stepwise
                    self.blocks.cancel_fused_run(plan)
                    plan, horizon = None, 1
                else:
                    horizon = plan.n
            st["horizon"] = horizon
        if horizon > 1:
            n = self._step_fused(order, horizon, plan)
            sp["iterations"], sp["seqs"] = n, len(order)
            return n
        toks = np.zeros((self.ecfg.slots, 1), np.int32)
        with span("device_wait"):
            lengths = np.array(self.lengths)
        for i in order:
            req = self.slot_req[i]
            if req is None:              # preempted by an earlier grow
                continue
            req.output.append(req._next)
            toks[i, 0] = req._next
            lengths[i] += 1
            if self._grow(i, int(lengths[i]), lengths) and \
                    self.slot_req[i] is not None and self.blocks is not None:
                self.blocks.prefetch(i, int(lengths[i]))
        with span("inputs"):
            self.lengths = jnp.array(lengths)
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return 0
        mask = np.zeros(self.ecfg.slots, bool)
        mask[active] = True
        self._sync_vm()
        sampled, _, self.cache = self._decode(
            self.params, toks, self.cache, self.lengths, mask)
        seen = time.perf_counter_ns()
        with span("replay", tokens=len(active)):
            for i in active:
                self._kv_committed[i] = int(lengths[i])
                req = self.slot_req[i]
                req._next = int(sampled[i])
                self.metrics.on_token(req, len(req.output), seen_ns=seen)
                self.budget[i] -= 1
                hit_eos = (self.ecfg.eos_id is not None and req.output
                           and req.output[-1] == self.ecfg.eos_id)
                if self.budget[i] <= 0 or hit_eos or \
                        int(lengths[i]) >= self.ecfg.max_len - 1:
                    req.done = True
                    self.slot_req[i] = None
                    self.counters["completed"] += 1
                    self.completed_reqs.append(req)
                    self.metrics.on_complete(req)
                    self._kv_committed[i] = 0
                    self._release(i)
        sp["iterations"], sp["seqs"] = 1, len(active)
        return 1

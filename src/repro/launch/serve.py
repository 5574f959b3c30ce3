"""Serving driver: batched requests through the continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --requests 6 --prompt-len 12 --max-new 16

``--kv-layout pooled`` serves from the emulated-memory page pool (frames
allocated on demand, prefix sharing, swap); ``--kv-shards N`` spreads that
pool over N devices, each holding 1/N of the frames from the start:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
      --kv-layout pooled --page-slots 256 --slots 8 --max-len 2048

``--trace`` switches from submit-everything-up-front to a seeded synthetic
trace (Poisson arrivals, Zipf prompt popularity, bimodal lengths) replayed
against the engine's decode-step clock, so requests genuinely queue.  The
output JSON's ``telemetry`` holds the SLO summary: p50/p95/p99 TTFT,
inter-token latency and queue wait, in decode steps (``ttft_steps``,
``itl_steps``, ``queue_wait_steps``) and in seconds of the host's clock
(``ttft_s``, ``itl_s``, ``queue_wait_s``):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --trace --requests 24 --arrival-rate 0.3 --zipf-alpha 1.2

:func:`main` takes the same arguments as a list and returns the report the
command line prints, so a script can serve in-process.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config, list_archs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.model import Model
from repro.parallel import mesh_ctx
from repro.serve.engine import EngineConfig, Request, ServeEngine
from repro.serve.scheduler import Scheduler, SchedulerConfig
from repro.serve.tracegen import TraceConfig, generate, replay


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--kv-layout", choices=("batch", "paged", "pooled"),
                    default="batch",
                    help="KV store: per-slot arrays, or the emulated-memory "
                         "page pool with reserved (paged) or on-demand "
                         "(pooled) frames")
    ap.add_argument("--page-slots", type=int, default=None,
                    help="token positions per KV page (default: the "
                         "config's kv_page_slots)")
    ap.add_argument("--paged-kernel", choices=("auto", "fused", "composed"),
                    default="auto",
                    help="paged-decode impl: the fused Pallas kernels, the "
                         "composed jnp oracle, or auto (fused on TPU)")
    ap.add_argument("--kv-shards", type=int, default=1,
                    help="devices the KV page pool is spread over (the "
                         "'data' axis of a host mesh); 1 keeps it on one")
    ap.add_argument("--max-fused-steps", type=int, default=8,
                    help="decode steps fused into one jitted while-loop "
                         "run between control-plane events; 1 reproduces "
                         "step-at-a-time dispatch exactly")
    ap.add_argument("--preempt-mode", choices=("swap", "recompute"),
                    default="swap",
                    help="how preempted sequences resume: swap-in of "
                         "host-parked pages, or requeue-and-re-prefill")
    ap.add_argument("--retain-frames", type=int, default=0,
                    help="device frames the retention pool may keep holding "
                         "completed prompts' prefix pages (0 disables)")
    ap.add_argument("--prefix-index", choices=("tree", "linear"),
                    default="tree",
                    help="prompt prefix index: radix tree (O(prompt) "
                         "lookup) or the retired linear scan oracle")
    ap.add_argument("--host-frames", type=int, default=None,
                    help="host backing-store frames for swapped-out pages "
                         "(default: one per device frame)")
    ap.add_argument("--spill-frames", type=int, default=0,
                    help="third-tier spill-store frames the host tier "
                         "demotes into under pressure (0 disables the "
                         "spill tier)")
    ap.add_argument("--spill-path", type=str, default=None,
                    help="directory backing the spill store (default: "
                         "in-memory bytes)")
    ap.add_argument("--sched-window", type=int,
                    default=SchedulerConfig.window,
                    help="residency-aware admission reorder window "
                         "(1 = strict FIFO)")
    ap.add_argument("--aging-steps", type=int,
                    default=SchedulerConfig.aging_steps,
                    help="decode steps a passed-over request waits before "
                         "it outranks every admission score")
    ap.add_argument("--trace", action="store_true",
                    help="replay a seeded synthetic trace (Poisson "
                         "arrivals, Zipf prompt popularity) instead of "
                         "submitting every request up front")
    ap.add_argument("--trace-seed", type=int, default=0,
                    help="trace rng seed: the same seed reproduces the "
                         "schedule byte-for-byte")
    ap.add_argument("--arrival-rate", type=float, default=0.25,
                    help="mean trace arrivals per decode step")
    ap.add_argument("--zipf-alpha", type=float, default=1.2,
                    help="prompt-popularity skew (larger = hotter head)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def build(args: argparse.Namespace) -> tuple[Model, dict, ServeEngine]:
    """Model, params (from ``--seed``) and engine for parsed arguments.
    Installs the KV mesh context for ``--kv-shards`` > 1 and clears any
    installed one otherwise, so the engine's page pool is born where the
    arguments say."""
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = dataclasses.replace(cfg, kv_layout=args.kv_layout,
                              paged_kernel=args.paged_kernel)
    if args.page_slots is not None:
        cfg = dataclasses.replace(cfg, kv_page_slots=args.page_slots)
    if args.kv_shards > 1:
        mesh_ctx.set_context(make_host_mesh(args.kv_shards),
                             kv_axes=("data",))
    else:
        mesh_ctx.clear_context()
    model = Model(cfg)
    params = model.init(jax.random.key(args.seed))
    engine = ServeEngine(model, params, EngineConfig(
        slots=args.slots, max_len=args.max_len,
        preempt_mode=args.preempt_mode, retain_frames=args.retain_frames,
        host_frames=args.host_frames, spill_frames=args.spill_frames,
        spill_path=args.spill_path,
        max_fused_steps=args.max_fused_steps,
        prefix_index=args.prefix_index))
    return model, params, engine


def paged_impl(cfg) -> str | None:
    """The paged-decode impl the decode step resolves to (None for the
    batch layout, which reads no pages)."""
    if cfg.kv_layout == "batch":
        return None
    from repro.kernels.paged_decode.ops import resolve_impl
    ctx = mesh_ctx.get_context()
    tp = 1 if ctx is None else ctx.tp
    return resolve_impl(cfg.paged_kernel, cfg.n_heads // tp,
                        cfg.n_heads // cfg.n_kv_heads)


def main(argv: list[str] | None = None,
         requests: list[Request] | None = None) -> dict:
    """Serve and return the report the command line prints.  ``requests``
    replaces the generated ones (not with ``--trace``)."""
    enable_compile_cache()
    args = parse_args(argv)
    _, _, engine = build(args)
    return run(args, engine, requests)


def run(args: argparse.Namespace, engine: ServeEngine,
        requests: list[Request] | None = None) -> dict:
    """Serve on a built engine until every request is done, shut the
    engine down, and return the report.  Requests the caller already
    admitted into slots are served too, so ``requests=[]`` finishes
    those."""
    cfg = engine.model.cfg
    sched = Scheduler(engine, SchedulerConfig(window=args.sched_window,
                                              aging_steps=args.aging_steps))
    t0 = time.monotonic()
    if args.trace:
        tcfg = TraceConfig(
            seed=args.trace_seed, n_requests=args.requests,
            arrival_rate=args.arrival_rate, zipf_alpha=args.zipf_alpha,
            prompt_len_short=max(2, args.prompt_len // 2),
            prompt_len_long=args.prompt_len,
            out_len_short=max(1, args.max_new // 2),
            out_len_long=args.max_new, vocab_size=cfg.vocab_size)
        done = replay(generate(tcfg), sched)
    else:
        if requests is None:
            rng = np.random.default_rng(args.seed)
            requests = [Request(uid=i,
                                prompt=rng.integers(0, cfg.vocab_size,
                                                    args.prompt_len)
                                .astype(np.int32),
                                max_new_tokens=args.max_new)
                        for i in range(args.requests)]
        sched.submit(requests)
        done = sched.run()
    dt = time.monotonic() - t0
    stats = engine.shutdown()
    total_new = sum(len(r.output) for r in done)
    return {
        "completed": len(done), "new_tokens": total_new,
        "tokens_per_s": round(total_new / dt, 1),
        "paged_impl": paged_impl(cfg),
        "shared_prompt_tokens": stats["shared_prompt_tokens"],
        "outputs": {r.uid: r.output[:8] for r in done},
        "telemetry": stats["telemetry"],
    }


if __name__ == "__main__":
    print(json.dumps(main(), indent=1))

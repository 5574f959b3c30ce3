"""Smoke run of the serving path on a TPU: qwen3-0.6b at full width
(28 layers, d_model 1024, 16 query / 8 KV heads of 128, vocab 151,936,
bf16, random weights from ``--seed``) served through the pooled
emulated-memory KV store with the fused Pallas paged-decode kernels.

  python chip_smoke.py [--seed N] [--chips 4]

One process drives the chip; nothing here starts another.  Each phase
builds its own engine; an engine's page-IO callbacks close over it, so a
phase collects the cycles of the last one before it allocates.  Phases:

1. decode-step check: compile the decode step (seconds printed; the fused
   kernels must be in it as ``tpu_custom_call``), prefill the requests,
   then run one decode step on that state (fed each prompt's first token)
   with the fused kernels and with the composed jnp oracle; the logits
   must agree within ``RTOL``
   of their largest magnitude.  On the same state the two paged-decode
   impls alone, on layer 0's pages, must write identical pages and agree
   within ``ATTN_RTOL``.
2. serve: the same requests through ``repro.launch.serve.main``; all of
   them must complete with every new token, and two prompts that share a
   256-token prefix must share its page.

``--chips 4`` runs instead the four-chip path only, once with the page
pool spread over four chips (``--kv-shards 4``) and once on device 0
alone: the decode-step check, after which the same engine serves the
requests it prefilled to completion (``repro.launch.serve.run``), so each
side prefills once.  The first-step logits must agree, and each device
must hold exactly 1/4 of the pool's frames.

Exits non-zero, with no result line, when JAX finds no TPU (or fewer
chips than asked for), when the repository's ``src/`` is not beside this
script, or when any phase fails.  The last line of a passing run is
``{"ok": true, "device": {...}}``.  Its tokens/s is a smoke figure from
one short run, not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: fused against composed (and sharded against one device) first-step
#: logits: max |difference| over the largest |logit|.  Two compiled bf16
#: programs of this model round at different points (fusion decides
#: where), and 28 layers compound it: at 16 layers on CPU the bf16 logits
#: sit 3.75e-2 from float32 ones, and fused sits 1.8e-2 from composed.
RTOL = 5e-2
#: the paged-decode kernels alone, fused against composed, on one layer's
#: prefilled pages: attention is float32 at full matmul precision in both,
#: so only summation order separates them
ATTN_RTOL = 1e-4
N_REQUESTS = 6
PROMPT_LEN = (300, 600)
SHARED_PREFIX = 256
MAX_NEW = 32
PAGE_SLOTS = 256
SERVE_ARGV = ["--arch", "qwen3-0.6b", "--kv-layout", "pooled",
              "--page-slots", str(PAGE_SLOTS), "--paged-kernel", "auto",
              "--slots", "8", "--max-len", "2048",
              "--max-new", str(MAX_NEW)]


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def make_requests(seed: int, vocab: int, *, n: int = N_REQUESTS,
                  prompt_len: tuple[int, int] = PROMPT_LEN,
                  shared_prefix: int = SHARED_PREFIX,
                  page_slots: int = PAGE_SLOTS, max_new: int = MAX_NEW):
    """Seeded requests: prompt lengths drawn from ``prompt_len`` (never a
    whole number of pages, so the position after the prompt is already
    mapped), and requests 0 and 1 share their first ``shared_prefix``
    tokens."""
    import numpy as np
    from repro.serve.engine import Request
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_len[0], prompt_len[1] + 1, n)
    lens = np.where(lens % page_slots == 0, lens + 1, lens)
    prefix = rng.integers(0, vocab, shared_prefix).astype(np.int32)
    reqs = []
    for i, length in enumerate(lens):
        prompt = rng.integers(0, vocab, int(length)).astype(np.int32)
        if i < 2:
            prompt[:shared_prefix] = prefix
        reqs.append(Request(uid=i, prompt=prompt, max_new_tokens=max_new))
    return reqs


def pool_shards(cache: dict) -> list[tuple[str, int]]:
    """(device, frames held) for each addressable shard of the first
    attention layer's K pages."""
    k = cache["b0"]["k_pages"]
    return [(str(s.device), int(s.data.shape[1]))
            for s in k.addressable_shards]


def decode_step_check(argv: list[str], requests, kernels: bool = False,
                      serve_rest: bool = False) -> dict:
    """Prefill ``requests`` into a fresh engine, then run one decode step
    on that state with the configured impl and with the composed oracle.
    Returns the logits of both (active rows, float32), the tokens the
    prefill sampled, the
    resolved impl, compile seconds, whether the compiled step holds a TPU
    kernel, the pool's size, how its frames sit on the devices, with
    ``kernels`` (one device only) the :func:`kernel_check` result, and
    with ``serve_rest`` the report of serving the requests to completion
    on the same engine (``"served"``)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import serve
    from repro.models.model import Model
    from repro.parallel.paged_attention import paged_entries
    from repro.serve.fused_decode import sampled_decode_step

    gc.collect()        # the last phase's engine, params and pool
    t_phase = time.monotonic()
    serve_args = serve.parse_args(argv)
    model, params, engine = serve.build(serve_args)
    slots = engine.ecfg.slots
    n = len(requests)
    check(n <= slots, f"{n} requests for {slots} slots")
    t0 = time.monotonic()
    compiled = sampled_decode_step.lower(
        model, params, jnp.zeros((slots, 1), jnp.int32), engine.cache,
        engine.lengths, jnp.ones((slots,), bool)).compile()
    compile_s = time.monotonic() - t0
    has_kernel = "tpu_custom_call" in compiled.as_text()
    del compiled
    for slot, req in enumerate(requests):
        engine.admit(req, slot)
    check(not engine.blocks.dirty, "block tables changed after the prefill")
    # The checked step is fed each prompt's first token, not the token the
    # prefill sampled: random weights give near-flat logits, so two runs
    # whose prefills round differently can sample different tokens, and
    # their next logits would then differ entirely for no fault.
    toks = np.zeros((slots, 1), np.int32)
    toks[:n, 0] = [r.prompt[0] for r in requests]
    lengths = np.array(engine.lengths)
    lengths[:n] += 1
    bt = np.asarray(engine.cache["vm"]["block_table"])
    ps = model.cfg.kv_page_slots
    check(all(bt[i, (lengths[i] - 1) // ps] >= 0 for i in range(n)),
          "the decode position of a sequence has no frame")
    wm = np.arange(slots) < n
    kernel = kernel_check(engine, lengths, wm) if kernels else None
    args = (params, jnp.array(toks), engine.cache, jnp.array(lengths),
            jnp.array(wm))
    _, logits, _ = sampled_decode_step(model, *args)
    oracle = Model(dataclasses.replace(model.cfg, paged_kernel="composed"))
    _, logits_ref, _ = sampled_decode_step(oracle, *args)
    vocab = model.cfg.vocab_size
    out = {
        "impl": serve.paged_impl(model.cfg),
        "compile_s": compile_s,
        "tpu_custom_call": has_kernel,
        "logits": np.asarray(logits[:n, :vocab], np.float32),
        "logits_composed": np.asarray(logits_ref[:n, :vocab], np.float32),
        "pool_bytes": sum(e["k_pages"].nbytes + e["v_pages"].nbytes
                          for _, e in paged_entries(engine.cache)),
        "pool_frames": engine.n_frames,
        "shards": pool_shards(engine.cache),
        "shared_prompt_tokens": engine.counters["shared_prompt_tokens"],
        "kernels": kernel,
        "sampled": [int(r._next) for r in requests],
    }
    if serve_rest:
        out["served"] = served(serve.run(serve_args, engine, []), requests,
                               t_phase)
    else:
        engine.shutdown(abort=True)
    return out


def kernel_check(engine, lengths, wm) -> dict:
    """Both paged-decode impls on layer 0's prefilled pages (one device),
    with seeded queries and new K/V rows: returns whether the written
    pages are identical and the attention outputs' relative error."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_decode import ops as pd_ops
    from repro.parallel.paged_attention import paged_entries

    cfg = engine.model.cfg
    _, entry = next(paged_entries(engine.cache))
    kp, vp = entry["k_pages"][0], entry["v_pages"][0]
    vm = engine.cache["vm"]
    b, n = engine.ecfg.slots, int(np.sum(wm))
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, cfg.n_heads, cfg.hd)), kp.dtype)
    k_new, v_new = (jnp.asarray(rng.normal(size=(b, cfg.n_kv_heads,
                                                 cfg.hd)), kp.dtype)
                    for _ in range(2))
    outs = {}
    for impl in ("fused", "composed"):
        step = jax.jit(functools.partial(
            pd_ops.paged_decode_shard, sid=0, n_shards=1, head_start=0,
            group=cfg.n_heads // cfg.n_kv_heads, window=cfg.window,
            max_pages=engine.max_lpages, use_vm=True, impl=impl))
        acc, _, l, kp2, vp2 = step(
            q, k_new, v_new, kp, vp, jnp.array(lengths),
            vm["block_table"], vm["frame_lpage"], vm["frame_ro"],
            jnp.array(wm))
        outs[impl] = (np.asarray(acc[:n] / l[:n, :, None]), np.asarray(kp2),
                      np.asarray(vp2))
    (o_f, k_f, v_f), (o_c, k_c, v_c) = outs["fused"], outs["composed"]
    return {"pages_equal": bool(np.array_equal(k_f, k_c)
                                and np.array_equal(v_f, v_c)),
            "attn_error": logits_error(o_f, o_c)}


def logits_error(a, b) -> float:
    """max |a - b| over the largest |b|."""
    import numpy as np
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def served(report: dict, requests, t0: float) -> dict:
    """``report`` of serving ``requests`` (from ``repro.launch.serve``),
    with the wall seconds since ``t0``, once every request has completed
    with all its new tokens."""
    report["wall_s"] = time.monotonic() - t0
    n = len(requests)
    max_new = requests[0].max_new_tokens
    check(report["completed"] == n,
          f"{report['completed']} of {n} requests completed")
    check(report["new_tokens"] == n * max_new,
          f"{report['new_tokens']} new tokens, expected {n * max_new}")
    return report


def serve_phase(argv: list[str], requests) -> dict:
    from repro.launch import serve
    gc.collect()        # the last phase's engine, params and pool
    t0 = time.monotonic()
    return served(serve.main(argv, requests=requests), requests, t0)


def report_check(tag: str, r: dict) -> None:
    say(f"[{tag}] paged impl resolved to {r['impl']}; compiled decode "
        f"step holds tpu_custom_call: {r['tpu_custom_call']}")
    say(f"[{tag}] decode step compile: {r['compile_s']} s")
    say(f"[{tag}] KV pool: {r['pool_frames']} frames, {r['pool_bytes']} "
        f"bytes; frames per device: {r['shards']}")


def report_serve(tag: str, r: dict) -> None:
    say(f"[{tag}] served {r['completed']} requests, {r['new_tokens']} new "
        f"tokens, {r['shared_prompt_tokens']} shared prompt tokens, in "
        f"{r['wall_s']} s, compiles included (smoke tokens/s, not a "
        f"benchmark: {r['new_tokens'] / r['wall_s']})")


def run_one_chip(argv: list[str], requests, on_tpu: bool = True) -> None:
    """Default phases on one device: decode-step check, then serve."""
    r = decode_step_check(argv, requests(), kernels=True)
    report_check("1 chip", r)
    err = logits_error(r["logits"], r["logits_composed"])
    agree = int((r["logits"].argmax(-1)
                 == r["logits_composed"].argmax(-1)).sum())
    say(f"[1 chip] first-step logits, fused against composed: max error "
        f"{err} of the largest logit (limit {RTOL}); argmax agrees "
        f"on {agree} of {len(r['logits'])} rows")
    k = r["kernels"]
    say(f"[1 chip] layer-0 paged decode alone, fused against composed: "
        f"pages identical: {k['pages_equal']}; attention max error "
        f"{k['attn_error']} of the largest output (limit {ATTN_RTOL})")
    if on_tpu:
        check(r["impl"] == "fused", f"impl {r['impl']}, not fused")
        check(r["tpu_custom_call"], "no tpu_custom_call in the decode step")
    check(k["pages_equal"], "fused kernel wrote other pages than composed")
    check(k["attn_error"] <= ATTN_RTOL,
          f"fused attention off the composed oracle: {k['attn_error']:.3e}")
    check(err <= RTOL, f"fused logits off the composed oracle: {err:.3e}")
    s = serve_phase(argv, requests())
    report_serve("1 chip", s)
    check(s["paged_impl"] == r["impl"], "serve resolved another impl")
    check(s["shared_prompt_tokens"] > 0, "no prompt tokens were shared")


def run_four_chips(argv: list[str], requests, on_tpu: bool = True) -> None:
    """The page pool over four chips against the same on device 0."""
    sharded = argv + ["--kv-shards", "4"]
    r4 = decode_step_check(sharded, requests(), serve_rest=True)
    report_check("4 chips", r4)
    r1 = decode_step_check(argv, requests(), serve_rest=True)
    report_check("device 0", r1)
    for tag, r in (("4 chips", r4), ("device 0", r1)):
        say(f"[{tag}] first-step logits, fused against composed: max "
            f"error {logits_error(r['logits'], r['logits_composed'])} of "
            f"the largest logit")
        report_serve(tag, r["served"])
    err = logits_error(r4["logits"], r1["logits"])
    say(f"[4 chips] first-step logits, 4-way sharded pool against device "
        f"0 alone: max error {err} of the largest logit (limit {RTOL})")
    same = sum(a == b for a, b in zip(r4["sampled"], r1["sampled"]))
    say(f"[4 chips] token sampled after the prefill matches device 0 on "
        f"{same} of {len(r1['sampled'])} requests")
    s4, s1 = r4["served"], r1["served"]
    same = sum(s4["outputs"][u] == s1["outputs"][u] for u in s1["outputs"])
    say(f"[4 chips] requests whose first 8 tokens match device 0: {same} "
        f"of {len(s1['outputs'])}")
    frames = [f for _, f in r4["shards"]]
    check(len({d for d, _ in r4["shards"]}) == 4
          and frames == [r4["pool_frames"] // 4] * 4,
          f"pool not spread 1/4 per device: {r4['shards']}")
    check(r1["shards"] == [(r1["shards"][0][0], r1["pool_frames"])],
          f"single-device pool not whole on one device: {r1['shards']}")
    if on_tpu:
        check(r4["impl"] == "fused" and r4["tpu_custom_call"],
              f"4-chip step: impl {r4['impl']}, TPU kernel "
              f"{r4['tpu_custom_call']}")
    check(err <= RTOL, f"sharded logits off the one-device run: {err:.3e}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and the requests")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the four-chip path (and only that)")
    args = ap.parse_args()
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: FAIL: the repository's src/ is not beside "
              f"this script ({e})", file=sys.stderr)
        return 2
    enable_compile_cache()
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: FAIL: no device: {e}", file=sys.stderr)
        return 2
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: FAIL: needs {args.chips} TPU chip(s), JAX "
              f"found {len(devices)} {dev.platform} device(s)",
              file=sys.stderr)
        return 2
    say(f"device {dev.platform} {dev.device_kind} x{len(devices)}, "
        f"jax {jax.__version__}")
    from repro.configs import get_config
    vocab = get_config("qwen3-0.6b").vocab_size
    argv = SERVE_ARGV + ["--seed", str(args.seed)]

    def requests():
        return make_requests(args.seed, vocab)

    try:
        if args.chips == 4:
            run_four_chips(argv, requests)
        else:
            run_one_chip(argv, requests)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    say(f"peak_bytes_in_use on {dev}: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

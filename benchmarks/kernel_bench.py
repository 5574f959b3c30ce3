"""Kernel micro-benchmarks on the host device (oracle path) with analytic
TPU-target FLOP counts -- the per-kernel roofline inputs.

:func:`paged_decode_sweep` additionally runs the fused VM-walking Pallas
paged-decode step against its composed-ops oracle and returns the record
``benchmarks.vm_bench`` wires into ``BENCH_vm.json``'s ``paged_decode``
section (and its regression gate)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, timeit
from repro.launch.hlo_analysis import PEAK_FLOPS_BF16


def paged_decode_sweep() -> tuple[list[dict], dict]:
    """Fused VM-walking paged-decode step vs the composed oracle.

    One decode step for B sequences through both impls of
    ``paged_decode_shard`` on the same ragged block tables: the fused
    path walks the tables inside the Pallas grid (interpret mode off
    TPU), the composed path is the host-side owner-mask oracle tier-1
    runs on.  The sweep doubles as an oracle check -- pages must come
    back byte-identical and the attention statistics must agree to fp32
    tolerance -- so a silently-diverging kernel crashes the bench.

    Returns (csv rows, the ``BENCH_vm.json`` ``paged_decode`` record).
    The gated headline is ``page_read_ratio``: pool pages the composed
    impl must consider per sequence (all of them -- ownership is a
    host-computed membership mask over the whole pool) over the pages
    the fused kernel walks (its grid rides the block table, at most
    ``max_lpages``).  That is deterministic arithmetic of the sweep
    geometry -- per the dispatch section's precedent of never gating
    machine-load-sensitive wall ratios -- while the measured tokens/s
    land next to it as recorded (ungated) numbers; off-TPU the fused
    timing is interpret-mode, a correctness path, not a speed claim."""
    from repro.kernels.paged_decode import ops as pd_ops

    rng = np.random.default_rng(7)
    B, HKV, G, D = 4, 2, 2, 32          # Hl = HKV*G local query heads
    LP, PS, NP = 8, 8, 64               # max lpages, page slots, pool pages
    q = jnp.asarray(rng.normal(size=(B, HKV * G, D)).astype(np.float32))
    k_new = jnp.asarray(rng.normal(size=(B, HKV, D)).astype(np.float32))
    v_new = jnp.asarray(rng.normal(size=(B, HKV, D)).astype(np.float32))
    k_pages = jnp.asarray(rng.normal(size=(NP, HKV, PS, D)).astype(np.float32))
    v_pages = jnp.asarray(rng.normal(size=(NP, HKV, PS, D)).astype(np.float32))
    lengths_np = rng.integers(1, LP * PS + 1, size=B)
    n_mapped = -(-lengths_np // PS)                  # pages actually in use
    frames = rng.permutation(NP)[:B * LP].reshape(B, LP)
    bt_np = np.where(np.arange(LP)[None, :] < n_mapped[:, None], frames, -1)
    fl_np = np.zeros(NP, np.int32)
    for i in range(B):
        fl_np[frames[i, :n_mapped[i]]] = np.arange(n_mapped[i])
    lengths = jnp.asarray(lengths_np.astype(np.int32))
    bt = jnp.asarray(bt_np.astype(np.int32))
    fl = jnp.asarray(fl_np)
    fr = jnp.zeros((NP,), jnp.int32)
    wm = jnp.ones((B,), jnp.int32)

    step = functools.partial(
        pd_ops.paged_decode_shard, sid=0, n_shards=1, head_start=0,
        group=G, window=None, max_pages=LP, use_vm=True)
    args = (q, k_new, v_new, k_pages, v_pages, lengths, bt, fl, fr, wm)
    f_comp = jax.jit(functools.partial(step, impl="composed"))
    f_fused = jax.jit(functools.partial(step, impl="fused"))

    acc_c, m_c, l_c, kp_c, vp_c = jax.block_until_ready(f_comp(*args))
    acc_f, m_f, l_f, kp_f, vp_f = jax.block_until_ready(f_fused(*args))
    assert (kp_f == kp_c).all() and (vp_f == vp_c).all(), \
        "fused paged write diverged from the composed oracle"
    assert (m_f == m_c).all(), "fused attention max diverged"
    assert np.allclose(acc_f, acc_c, atol=1e-5, rtol=1e-5), \
        "fused attention accumulator diverged from the composed oracle"
    assert np.allclose(l_f, l_c, atol=1e-5, rtol=1e-5), \
        "fused attention normalizer diverged from the composed oracle"

    us_c = timeit(lambda: jax.block_until_ready(f_comp(*args)))
    us_f = timeit(lambda: jax.block_until_ready(f_fused(*args)))
    tok_c = B / us_c * 1e6
    tok_f = B / us_f * 1e6
    record = {
        "geometry": {"n_seqs": B, "n_kv_heads": HKV, "group": G,
                     "head_dim": D, "max_lpages": LP, "page_slots": PS,
                     "pool_pages": NP},
        "tokens_per_s_fused": round(tok_f, 1),
        "tokens_per_s_composed": round(tok_c, 1),
        "pool_pages_per_seq_composed": NP,
        "table_pages_per_seq_fused": LP,
        "page_read_ratio": round(NP / LP, 2),
    }
    rows_ = [
        row("kernel/paged_decode/fused", us_f,
            f"{tok_f:.0f} tok/s walking {LP} table pages/seq "
            f"(interpret off TPU)"),
        row("kernel/paged_decode/composed", us_c,
            f"{tok_c:.0f} tok/s masking all {NP} pool pages/seq "
            f"({NP / LP:.0f}x the fused read set)"),
    ]
    return rows_, record


def rows() -> list[dict]:
    out = []
    rng = np.random.default_rng(0)

    # flash attention (ref path timing; pallas path is TPU-target)
    from repro.kernels.flash_attention import flash_attention
    B, Hq, Hkv, S, D = 1, 8, 2, 1024, 64
    q = jnp.asarray(rng.normal(size=(B, Hq, S, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    us = timeit(lambda: flash_attention(q, k, v, causal=True,
                                        use_pallas=False).block_until_ready())
    flops = 2 * 2 * B * Hq * S * S * D * 0.5
    out.append(row("kernel/flash_attn/1x8x1024x64", us,
                   f"{flops / 1e9:.2f} GFLOP -> "
                   f"{flops / PEAK_FLOPS_BF16 * 1e6:.2f}us on v5e MXU"))

    # decode attention
    from repro.kernels.paged_decode import decode_attention
    kc = jnp.asarray(rng.normal(size=(4, Hkv, 4096, D)).astype(np.float32))
    vc = jnp.asarray(rng.normal(size=(4, Hkv, 4096, D)).astype(np.float32))
    qd = jnp.asarray(rng.normal(size=(4, Hq, D)).astype(np.float32))
    lengths = jnp.full((4,), 4096, jnp.int32)
    us = timeit(lambda: decode_attention(qd, kc, vc, lengths,
                                         use_pallas=False).block_until_ready())
    kv_bytes = 2 * 4 * Hkv * 4096 * D * 2
    out.append(row("kernel/decode_attn/4x8x4096", us,
                   f"kv={kv_bytes / 1e6:.1f}MB -> "
                   f"{kv_bytes / 819e9 * 1e6:.1f}us HBM-bound on v5e"))

    # mamba2 SSD
    from repro.kernels.mamba2_ssd import ssd
    Bt, Sm, H, P, N = 1, 2048, 8, 64, 64
    x = jnp.asarray(rng.normal(size=(Bt, Sm, H, P)).astype(np.float32))
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (Bt, Sm, H)).astype(np.float32))
    A = jnp.asarray(-rng.uniform(0.5, 2, (H,)).astype(np.float32))
    Bm = jnp.asarray(rng.normal(size=(Bt, Sm, 1, N)).astype(np.float32))
    Cm = jnp.asarray(rng.normal(size=(Bt, Sm, 1, N)).astype(np.float32))
    Dm = jnp.asarray(rng.normal(size=(H,)).astype(np.float32))
    us = timeit(lambda: ssd(x, dt, A, Bm, Cm, Dm, chunk=128,
                            use_pallas=False).block_until_ready())
    q_ = 128
    ssd_flops = Bt * H * (Sm // q_) * (2 * q_ * q_ * N + 2 * q_ * q_ * P
                                       + 4 * q_ * N * P)
    out.append(row("kernel/mamba2_ssd/2048x8x64", us,
                   f"{ssd_flops / 1e9:.2f} GFLOP chunked"))

    # emem paged gather
    from repro.kernels.paged_decode import gather_pages
    pages = jnp.asarray(rng.normal(size=(256, 128, 128)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 256, 64).astype(np.int32))
    us = timeit(lambda: gather_pages(pages, ids,
                                     use_pallas=False).block_until_ready())
    gbytes = 64 * 128 * 128 * 4
    out.append(row("kernel/emem_gather/64pages", us,
                   f"{gbytes / 1e6:.1f}MB -> "
                   f"{gbytes / 819e9 * 1e6:.1f}us HBM-bound on v5e"))

    # fused VM-walking paged decode vs composed oracle
    out.extend(paged_decode_sweep()[0])
    return out

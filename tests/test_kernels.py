"""Per-kernel shape/dtype sweeps against the pure-jnp oracles.

All Pallas kernels run in interpret mode on CPU (the TPU BlockSpec tiling is
exercised structurally; numerics match the oracle)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import kernel as fa_k, ref as fa_ref
from repro.kernels.mamba2_ssd import kernel as ssd_k, ref as ssd_ref
from repro.kernels.paged_decode import flash as dec_k, flash_ref as dec_ref
from repro.kernels.paged_decode import gather as eg_k, gather_ref as eg_ref
from repro.kernels.paged_decode import ops as pd_ops


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)


# -- emem_gather ---------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 8, 16), (8, 16, 4), (2, 32, 128)])
def test_gather_slots_sweep(rng, shape, dtype):
    n_pages, page_slots, width = shape
    pages = jnp.asarray(rng.normal(size=shape), dtype)
    slots = jnp.asarray(np.concatenate([
        rng.integers(0, n_pages * page_slots, 17), [-1]]).astype(np.int32))
    out = eg_k.gather_slots(pages, slots, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(eg_ref.gather_slots(pages, slots), np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_pages_sweep(rng, dtype):
    pages = jnp.asarray(rng.normal(size=(6, 8, 32)), dtype)
    ids = jnp.asarray(np.array([5, -1, 0, 3], np.int32))
    out = eg_k.gather_pages(pages, ids, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(eg_ref.gather_pages(pages, ids), np.float32), **_tol(dtype))


def test_scatter_then_gather_roundtrip(rng):
    pages = jnp.zeros((4, 8, 8), jnp.float32)
    slots = jnp.asarray(rng.permutation(32)[:10].astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(10, 8)).astype(np.float32))
    pages = eg_ref.scatter_slots(pages, slots, vals)
    np.testing.assert_allclose(eg_ref.gather_slots(pages, slots), vals)


# -- flash attention -------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 16), (False, 8)])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
def test_flash_attention_sweep(rng, dtype, causal, window, hq, hkv):
    B, S, D = 2, 32, 16
    q = jnp.asarray(rng.normal(size=(B, hq, S, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, hkv, S, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, hkv, S, D)), dtype)
    out = fa_k.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=8, block_k=8, interpret=True)
    ref = fa_ref.mha(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_tail_queries(rng):
    """Sq < Skv: queries at the sequence tail (prefill continuation)."""
    B, H, S, D = 1, 2, 32, 8
    q = jnp.asarray(rng.normal(size=(B, H, 8, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    out = fa_k.flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                               interpret=True)
    ref = fa_ref.mha(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


# -- decode attention ------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 8])
def test_flash_decode_sweep(rng, dtype, window):
    B, Hkv, G, S, D = 3, 2, 4, 32, 16
    q = jnp.asarray(rng.normal(size=(B, Hkv, G, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), dtype)
    lengths = jnp.asarray([32, 9, 17], jnp.int32)
    out, m, l = dec_k.flash_decode(q, k, v, lengths, window=window,
                                   block_k=8, interpret=True)
    ref = dec_ref.decode_attention(
        q.reshape(B, Hkv * G, D), k, v, lengths, window=window)
    np.testing.assert_allclose(np.asarray(out.reshape(B, Hkv * G, D), np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_decode_partial_merge_equals_full(rng):
    from repro.kernels.paged_decode import flash_ops as ops
    B, Hq, Hkv, S, D, P = 2, 4, 2, 64, 8, 4
    q = jnp.asarray(rng.normal(size=(B, Hq, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    lengths = jnp.asarray([64, 40], jnp.int32)
    sp = S // P
    parts = []
    for p in range(P):
        lp = jnp.clip(lengths - p * sp, 0, sp)
        parts.append(ops.decode_attention_partial(
            q, k[:, :, p * sp:(p + 1) * sp], v[:, :, p * sp:(p + 1) * sp],
            lp, use_pallas=True, interpret=True, block_k=8))
    merged = ops.merge_partials(jnp.stack([p[0] for p in parts]),
                                jnp.stack([p[1] for p in parts]),
                                jnp.stack([p[2] for p in parts]))
    full = dec_ref.decode_attention(q, k, v, lengths)
    np.testing.assert_allclose(merged, full, rtol=1e-5, atol=1e-5)


# -- fused paged decode (VM walk in-kernel) vs composed oracle -------------------
def _mk_vm(rng, b, max_lpages, page_slots, lengths, shared_page0=False):
    """Random-but-valid BlockManager-style tables: every live page of every
    sequence mapped to a distinct frame (scrambled assignment -- the walk
    must not rely on contiguity), optionally one read-only frame backing
    page 0 of EVERY sequence (prefix sharing)."""
    n_frames = b * max_lpages
    bt = np.full((b, max_lpages), -1, np.int32)
    fl = np.zeros((n_frames,), np.int32)
    fr = np.zeros((n_frames,), bool)
    free = list(rng.permutation(n_frames))
    sh = None
    if shared_page0:
        sh = int(free.pop())
        fl[sh], fr[sh] = 0, True
    for s in range(b):
        for lp in range((int(lengths[s]) + page_slots - 1) // page_slots):
            if sh is not None and lp == 0:
                bt[s, 0] = sh
                continue
            f = int(free.pop())
            bt[s, lp], fl[f] = f, lp
    return jnp.asarray(bt), jnp.asarray(fl), jnp.asarray(fr)


def _run_shard(rng, impl, *, b=3, max_lpages=4, page_slots=8, hkv=2, group=2,
               window=None, lengths=(25, 9, 17), shared_page0=False,
               write_mask=None, use_vm=True, layers=None, layer=0, sid=0,
               n_shards=1, inputs=None):
    """One shard's step.  ``layers`` makes the pages a stacked pool
    [layers, np_loc, ...] of which ``layer`` is used; ``inputs``, a dict,
    receives the pages as given."""
    hl, hd = hkv * group, 16
    n_frames = b * max_lpages
    lengths = np.asarray(lengths, np.int32)
    q = jnp.asarray(rng.normal(size=(b, hl, hd)).astype(np.float32))
    k_new = jnp.asarray(rng.normal(size=(b, hkv, hd)).astype(np.float32))
    v_new = jnp.asarray(rng.normal(size=(b, hkv, hd)).astype(np.float32))
    shape = (n_frames // n_shards, hkv, page_slots, hd)
    if layers is not None:
        shape = (layers,) + shape
    kp = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    if inputs is not None:
        inputs.update(k_pages=kp, v_pages=vp)
    bt, fl, fr = _mk_vm(rng, b, max_lpages, page_slots, lengths,
                        shared_page0=shared_page0)
    wm = jnp.asarray(np.ones(b, bool) if write_mask is None
                     else np.asarray(write_mask, bool))
    return pd_ops.paged_decode_shard(
        q, k_new, v_new, kp, vp, jnp.asarray(lengths), bt, fl, fr, wm,
        sid=sid, n_shards=n_shards, head_start=0, group=group, window=window,
        max_pages=max_lpages, use_vm=use_vm, impl=impl, layer=layer,
        interpret=True)


def _assert_shard_match(fused, composed):
    acc_f, m_f, l_f, kp_f, vp_f = fused
    acc_c, m_c, l_c, kp_c, vp_c = composed
    # pages must be BYTE-identical: the write path either lands the same
    # row or drops it, there is no arithmetic to round
    np.testing.assert_array_equal(np.asarray(kp_f), np.asarray(kp_c))
    np.testing.assert_array_equal(np.asarray(vp_f), np.asarray(vp_c))
    np.testing.assert_array_equal(np.asarray(m_f), np.asarray(m_c))
    np.testing.assert_allclose(np.asarray(acc_f), np.asarray(acc_c),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(l_f), np.asarray(l_c),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layer", [None, 0, 1, 3])
@pytest.mark.parametrize("window", [None, 8])
@pytest.mark.parametrize("lengths", [(25, 9, 17), (1, 32, 8), (31, 2, 24)])
def test_paged_decode_fused_matches_composed(rng, window, lengths, layer):
    """The fused kernels walking cache['vm'] inside the grid reproduce the
    composed host-side-translation oracle over ragged lengths, scrambled
    frame assignments and sliding windows: same written pages (byte-equal),
    same running max (exact), same softmax statistics.

    With ``layer`` set the pages are a stacked pool of four layers, used in
    place at that layer's rows, and the shard is the second of two with
    page 0 shared read-only and one write masked off: not-my-shard visits,
    clamped rows and dropped writes must all stay inside the layer, so
    every other layer comes out byte-unchanged."""
    kw = dict(window=window, lengths=lengths)
    if layer is not None:
        kw.update(layers=4, layer=layer, sid=1, n_shards=2,
                  shared_page0=True, write_mask=(True, True, False))
    seed = int(rng.integers(1 << 31))
    given = {}
    fused = _run_shard(np.random.default_rng(seed), "fused", inputs=given,
                       **kw)
    composed = _run_shard(np.random.default_rng(seed), "composed", **kw)
    _assert_shard_match(fused, composed)
    if layer is not None:
        others = np.arange(4) != layer
        for out, pages in zip(fused[3:], given.values()):
            np.testing.assert_array_equal(np.asarray(out)[others],
                                          np.asarray(pages)[others])


@pytest.mark.parametrize("window", [None, 40])
@pytest.mark.parametrize("page_slots,lengths", [(16, (33, 16, 61)),
                                                (256, (300, 256, 1000))])
def test_paged_decode_fused_matches_composed_tpu_page_slots(
        rng, page_slots, lengths, window):
    """The ``[n_pages, Hkv, slots, D]`` layout at the page sizes the chip
    compiles (16 and 256 slots): the write lands inside a page and at its
    last slot, page 0 is a shared read-only frame, and the read walks
    several pages per sequence.  Pages must match byte for byte; at 256
    slots XLA sums the composed scores in another order than the kernel,
    so the running max may differ in its last bit."""
    seed = int(rng.integers(1 << 31))
    kw = dict(page_slots=page_slots, lengths=lengths, window=window,
              shared_page0=True)
    fused = _run_shard(np.random.default_rng(seed), "fused", **kw)
    composed = _run_shard(np.random.default_rng(seed), "composed", **kw)
    for f, c in zip(fused[3:], composed[3:]):
        np.testing.assert_array_equal(np.asarray(f), np.asarray(c))
    for f, c in zip(fused[:3], composed[:3]):
        np.testing.assert_allclose(np.asarray(f), np.asarray(c),
                                   rtol=1e-5, atol=1e-5)


def test_paged_decode_fused_write_drop(rng):
    """Write suppression inside the kernel: a masked-off sequence
    (write_mask) and a sequence whose current page is a shared read-only
    frame must both leave the pages untouched -- the in-kernel frame_ro /
    write-mask test, not a host-computed scatter target."""
    seed = int(rng.integers(1 << 31))
    # lengths <= page_slots: every sequence is still writing page 0, which
    # is the SHARED read-only frame -> every write drops; wm masks seq 2
    kw = dict(lengths=(5, 3, 8), shared_page0=True,
              write_mask=(True, True, False))
    fused = _run_shard(np.random.default_rng(seed), "fused", **kw)
    composed = _run_shard(np.random.default_rng(seed), "composed", **kw)
    _assert_shard_match(fused, composed)
    # and the drop actually happened: pages came through unmodified
    base = _run_shard(np.random.default_rng(seed), "composed",
                      write_mask=(False, False, False), **{
                          k: v for k, v in kw.items() if k != "write_mask"})
    np.testing.assert_array_equal(np.asarray(fused[3]), np.asarray(base[3]))


def test_paged_decode_fused_shared_frame_attends_once(rng):
    """A frame shared by several sequences (prefix sharing) is attended by
    EACH member exactly once -- membership is the in-kernel ownership test
    -- with divergent suffix pages private per sequence."""
    seed = int(rng.integers(1 << 31))
    kw = dict(lengths=(25, 9, 17), shared_page0=True)
    fused = _run_shard(np.random.default_rng(seed), "fused", **kw)
    composed = _run_shard(np.random.default_rng(seed), "composed", **kw)
    _assert_shard_match(fused, composed)


def test_paged_decode_fused_no_vm_identity_tables(rng):
    """use_vm=False (the batch kv_layout): the fused path synthesizes the
    fixed arithmetic mapping as identity tables in-jit and must agree with
    the composed bt-is-None arithmetic."""
    seed = int(rng.integers(1 << 31))
    fused = _run_shard(np.random.default_rng(seed), "fused", use_vm=False)
    composed = _run_shard(np.random.default_rng(seed), "composed",
                          use_vm=False)
    _assert_shard_match(fused, composed)


def test_paged_decode_resolve_impl(monkeypatch):
    """Dispatch policy: 'composed' always honored; 'fused' honored whenever
    the local head count splits into whole KV groups (interpret mode makes
    it CPU-runnable) and refused otherwise; 'auto' is fused only on TPU."""
    assert pd_ops.resolve_impl("composed", 8, 2) == "composed"
    assert pd_ops.resolve_impl("fused", 8, 2) == "fused"
    with pytest.raises(ValueError, match="whole KV-head groups"):
        pd_ops.resolve_impl("fused", 7, 2)              # ragged group
    with pytest.raises(ValueError, match="unknown paged_kernel"):
        pd_ops.resolve_impl("pallas", 8, 2)
    assert pd_ops.resolve_impl("auto", 8, 2) == "composed"    # CPU
    monkeypatch.setattr(pd_ops, "_on_tpu", lambda: True)
    assert pd_ops.resolve_impl("auto", 8, 2) == "fused"
    assert pd_ops.resolve_impl("auto", 7, 2) == "composed"


# -- mamba2 SSD -------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("groups", [1, 2])
def test_ssd_sweep(rng, dtype, chunk, groups):
    Bt, S, H, P, N = 2, 64, 4, 8, 16
    x = jnp.asarray(rng.normal(size=(Bt, S, H, P)), dtype)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(Bt, S, H)).astype(np.float32))
    A = jnp.asarray(-rng.uniform(0.5, 2.0, size=(H,)).astype(np.float32))
    B = jnp.asarray(rng.normal(size=(Bt, S, groups, N)), dtype)
    C = jnp.asarray(rng.normal(size=(Bt, S, groups, N)), dtype)
    D = jnp.asarray(rng.normal(size=(H,)).astype(np.float32))
    y = ssd_k.ssd(x, dt, A, B, C, D, chunk=chunk, interpret=True)
    y_ref, _ = ssd_ref.ssd_scan(x, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=3e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_ssd_chunked_matches_sequential(rng):
    Bt, S, H, P, N = 1, 48, 2, 4, 8
    x = jnp.asarray(rng.normal(size=(Bt, S, H, P)).astype(np.float32))
    dt = jnp.asarray(rng.uniform(0.01, 0.3, size=(Bt, S, H)).astype(np.float32))
    A = jnp.asarray(-rng.uniform(0.5, 2.0, size=(H,)).astype(np.float32))
    B = jnp.asarray(rng.normal(size=(Bt, S, 1, N)).astype(np.float32))
    C = jnp.asarray(rng.normal(size=(Bt, S, 1, N)).astype(np.float32))
    D = jnp.asarray(rng.normal(size=(H,)).astype(np.float32))
    y1, s1 = ssd_ref.ssd_scan(x, dt, A, B, C, D)
    y2, s2 = ssd_ref.ssd_chunked(x, dt, A, B, C, D, chunk=12)
    np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s1, s2, rtol=1e-5, atol=1e-5)


def test_ssd_decode_chain_matches_scan(rng):
    Bt, S, H, P, N = 2, 8, 2, 4, 8
    x = jnp.asarray(rng.normal(size=(Bt, S, H, P)).astype(np.float32))
    dt = jnp.asarray(rng.uniform(0.01, 0.3, size=(Bt, S, H)).astype(np.float32))
    A = jnp.asarray(-rng.uniform(0.5, 2.0, size=(H,)).astype(np.float32))
    B = jnp.asarray(rng.normal(size=(Bt, S, 1, N)).astype(np.float32))
    C = jnp.asarray(rng.normal(size=(Bt, S, 1, N)).astype(np.float32))
    D = jnp.asarray(rng.normal(size=(H,)).astype(np.float32))
    y_ref, _ = ssd_ref.ssd_scan(x, dt, A, B, C, D)
    state = jnp.zeros((Bt, H, N, P))
    ys = []
    for t in range(S):
        y1, state = ssd_ref.ssd_decode_step(x[:, t], dt[:, t], A, B[:, t],
                                            C[:, t], D, state)
        ys.append(y1)
    np.testing.assert_allclose(jnp.stack(ys, 1), y_ref, rtol=1e-5, atol=1e-5)

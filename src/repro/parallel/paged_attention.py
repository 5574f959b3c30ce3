"""Paged decode attention over an emulated KV memory (DESIGN.md §3.1).

THIN DISPATCH layer: the per-shard compute lives in
:mod:`repro.kernels.paged_decode` (a fused VM-walking Pallas path and the
composed-ops oracle, selected per platform/`ModelConfig.paged_kernel` by
``resolve_impl``); this module contributes only what is genuinely
control-plane --

  * the shard_map plumbing: the KV pages are cyclically owned by the
    devices of the ``kv_axes`` mesh axes (the paper's emulated-memory
    distribution, one home: :mod:`repro.emem_vm.layout`), query heads stay
    sharded over the tensor-parallel axis, and the per-shard partial
    statistics are merged with a log-sum-exp-weighted psum over
    ``kv_axes``.  The merge consumes the impl-independent (acc, m, l)
    contract, so fused and composed shards mix freely;
  * the host-side page movers (swap, COW, spill) the serving engine hands
    the BlockManager as ``PageIO`` callbacks.

Frame ownership is described by the ``vm`` translation state exported by
the serving engine's :class:`repro.emem_vm.BlockManager` (``cache["vm"]``):

  * ``block_table`` [B, max_lpages] -- logical page -> physical frame
    (-1 = unmapped).  A frame may appear in SEVERAL sequences' rows: prefix
    sharing backs a common prompt prefix with one physical copy, so
    ownership is *membership* (``block_table[b, frame_lpage[f]] == f``),
    not a single inverse map;
  * ``frame_lpage`` [n_frames]   -- which in-sequence logical page a frame
    holds (identical for every sharer: prefixes start at position 0);
  * ``frame_ro``    [n_frames]   -- the shared bit (refcount > 1).  Writes
    targeting a read-only frame are DROPPED: the host resolves copy-on-write
    before the step, so a surviving write to a shared frame can only be the
    idempotent re-run of a shared prompt token.

Without ``vm`` the mapping is the fixed arithmetic layout (sequence ``b``
owns pages ``b*max_pages .. (b+1)*max_pages-1``), kept for direct callers;
``init_cache`` materializes the same mapping as identity tables so both
``kv_layout`` values route through one code path in serving.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.emem_vm.layout import frame_rows
from repro.kernels.paged_decode import ops as pd_ops
from repro.models.config import ModelConfig
from repro.parallel import mesh_ctx

NEG_INF = -1e30


def _flat_axis_index(axes: tuple[str, ...]) -> jax.Array:
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def paged_decode_attention(cfg: ModelConfig, q, k_new, v_new, k_pages,
                           v_pages, lengths, vm: dict | None = None,
                           write_mask=None, layer=0):
    """q: [B, H, hd]; k_new/v_new: [B, Hkv, hd] (rope'd at position len-1);
    k/v_pages: one layer's global pages [n_pages, Hkv, slots, hd], or the
    stacked pool [n_layers, n_pages, Hkv, slots, hd] of which layer
    ``layer`` (may be traced) is written and read in place.  Returns
    (out, pages') with the pages in their input shape.

    ``vm`` is the BlockManager translation state documented in the module
    docstring; without it the fixed arithmetic mapping applies.

    ``write_mask`` [B] suppresses the K/V write for masked-off sequences --
    the serving engine's admit() runs the whole decode batch to prefill one
    slot, and without the mask every other in-flight slot would have its
    latest position overwritten with pad-token K/V."""
    ctx = mesh_ctx.get_context()
    b, h, hd = q.shape
    n_pages = k_pages.shape[-4]
    max_pages = n_pages // b
    group = cfg.n_heads // cfg.n_kv_heads
    if write_mask is None:
        write_mask = jnp.ones((b,), bool)
    pooled = vm is not None
    if vm is None:
        bt = jnp.zeros((1, 1), jnp.int32)
        fl = jnp.zeros((1,), jnp.int32)
        fr = jnp.zeros((1,), bool)
    else:
        bt, fl, fr = vm["block_table"], vm["frame_lpage"], vm["frame_ro"]

    if ctx is None or ctx.n_kv_shards * ctx.tp == 1:
        # single-device fallback: same per-shard entry, no collectives
        impl = pd_ops.resolve_impl(cfg.paged_kernel, h, group)
        acc, m, l, kp, vp = pd_ops.paged_decode_shard(
            q, k_new, v_new, k_pages, v_pages, lengths, bt, fl, fr,
            write_mask, sid=0, n_shards=1, head_start=0, group=group,
            window=cfg.window, max_pages=max_pages, use_vm=pooled,
            impl=impl, layer=layer)
        out = (acc / jnp.where(l == 0.0, 1.0, l)[..., None]).astype(q.dtype)
        return out, kp, vp

    n_shards = ctx.n_kv_shards
    assert n_pages % n_shards == 0, (n_pages, n_shards)
    assert h % ctx.tp == 0, (h, ctx.tp)
    hl = h // ctx.tp
    kv_axes = ctx.kv_axes
    tp_axis = ctx.tp_axis
    impl = pd_ops.resolve_impl(cfg.paged_kernel, hl, group)

    def body(q_l, k_new_l, v_new_l, kp_l, vp_l, len_l, bt, fl, fr, wm,
             layer):
        sid = _flat_axis_index(kv_axes)
        tp_idx = jax.lax.axis_index(tp_axis)
        acc, m, l, kp_l, vp_l = pd_ops.paged_decode_shard(
            q_l, k_new_l, v_new_l, kp_l, vp_l, len_l, bt, fl, fr, wm,
            sid=sid, n_shards=n_shards, head_start=tp_idx * hl, group=group,
            window=cfg.window, max_pages=max_pages, use_vm=pooled, impl=impl,
            layer=layer)
        # merge partials across the emulated-memory shards
        m_glob = jax.lax.pmax(m, kv_axes)
        w = jnp.exp(m - m_glob)
        num = jax.lax.psum(acc * w[..., None], kv_axes)
        den = jax.lax.psum(l * w, kv_axes)
        out = (num / jnp.where(den == 0.0, 1.0, den)[..., None]).astype(q_l.dtype)
        return out, kp_l, vp_l

    # the page axis is sharded; a stacked pool's layer axis is not
    kv_spec = P(*(None,) * (k_pages.ndim - 4),
                kv_axes if len(kv_axes) > 1 else kv_axes[0])
    fn = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(None, tp_axis, None), P(), P(), kv_spec, kv_spec, P(),
                  P(), P(), P(), P(), P()),
        out_specs=(P(None, tp_axis, None), kv_spec, kv_spec),
        check_vma=False)
    return fn(q, k_new, v_new, k_pages, v_pages, lengths, bt, fl, fr,
              write_mask, jnp.asarray(layer, jnp.int32))


def paged_decode_block(cfg: ModelConfig, p_attn: dict, h: jax.Array,
                       entry: dict, lengths: jax.Array,
                       vm: dict | None = None, write_mask=None, layer=0):
    """Attention sub-block for decode with the paged/pooled KV layout.

    h: [B, 1, d] (already normed); ``entry`` holds the stacked pool of
    every period, of which layer ``layer`` is used in place.  Returns
    (out [B, 1, d], new entry)."""
    from repro.models import layers as L
    b = h.shape[0]
    positions = (lengths - 1)[:, None]
    q, k_new, v_new = L._project_qkv(cfg, p_attn, h, positions)
    out, kp, vp = paged_decode_attention(
        cfg, q[:, :, 0], k_new[:, :, 0], v_new[:, :, 0],
        entry["k_pages"], entry["v_pages"], lengths, vm, write_mask, layer)
    out = out.reshape(b, 1, cfg.n_heads * cfg.hd) @ p_attn["wo"]
    return out, {"k_pages": kp, "v_pages": vp}


def paged_entries(cache: dict):
    """(key, entry) pairs of the cache pytree holding paged KV pages --
    THE predicate for what swap/COW page movers touch; per-slot recurrent
    state is everything else (:func:`slot_state_entries`)."""
    for key, entry in cache.items():
        if key.startswith("b") and isinstance(entry, dict) \
                and "k_pages" in entry:
            yield key, entry


def slot_state_entries(cache: dict):
    """(key, entry) pairs holding per-SLOT state (SSM conv/ssd rows, or the
    batch layout's k/v) rather than shared paged KV -- what a slot reset
    zeroes and a swapped-out sequence carries in its resume record."""
    for key, entry in cache.items():
        if key.startswith("b") and isinstance(entry, dict) \
                and "k_pages" not in entry:
            yield key, entry


def _frame_rows(frames: jax.Array, n_pages: int) -> jax.Array:
    """Frame id -> row of the *global* k/v_pages array, under the current
    mesh context (identity without one).  The mapping itself lives in
    :func:`repro.emem_vm.layout.frame_rows` -- host-side page movers (COW,
    swap) must permute through it, or they would touch the wrong physical
    pages on any multi-shard mesh."""
    ctx = mesh_ctx.get_context()
    n_shards = 1 if ctx is None else ctx.n_kv_shards
    return frame_rows(frames, n_pages, n_shards)


def read_frame_pages(cache: dict, frames) -> list:
    """Snapshot physical frames off the device (DEVICE -> HOST direction of
    the residency state machine): returns one opaque payload per frame,
    ``{layer_key: (k_row, v_row)}`` as host numpy, suitable for the
    BlockManager's host backing store.  One gather + one transfer per layer,
    not per frame."""
    import numpy as np
    idx = jnp.asarray(list(frames), jnp.int32)
    payloads = [dict() for _ in range(len(idx))]
    for key, entry in paged_entries(cache):
        rows = _frame_rows(idx, entry["k_pages"].shape[1])
        k = np.asarray(entry["k_pages"][:, rows])      # [np_, n, Hkv, ...]
        v = np.asarray(entry["v_pages"][:, rows])
        for i in range(len(idx)):
            payloads[i][key] = (k[:, i], v[:, i])
    return payloads


def write_frame_pages(cache: dict, assignments) -> dict:
    """Write swapped-out page payloads back into device frames (HOST ->
    DEVICE): ``assignments`` is ``[(frame, payload), ...]`` with payloads
    from :func:`read_frame_pages`.  One scatter per layer."""
    import numpy as np
    if not assignments:
        return cache
    dst = jnp.asarray([f for f, _ in assignments], jnp.int32)
    out = dict(cache)
    for key, entry in paged_entries(cache):
        rows = _frame_rows(dst, entry["k_pages"].shape[1])
        k_rows = jnp.asarray(np.stack([p[key][0] for _, p in assignments],
                                      axis=1))
        v_rows = jnp.asarray(np.stack([p[key][1] for _, p in assignments],
                                      axis=1))
        out[key] = {
            "k_pages": entry["k_pages"].at[:, rows].set(
                k_rows.astype(entry["k_pages"].dtype)),
            "v_pages": entry["v_pages"].at[:, rows].set(
                v_rows.astype(entry["v_pages"].dtype)),
        }
    return out


def cow_copy_pages(cache: dict, copies) -> dict:
    """Apply BlockManager CowCopy records to every attention layer's pages.

    Device-side row copies (k/v_pages are [n_periods, n_pages, Hkv, ...]);
    host-driven, outside the jitted decode -- COW is a control-plane event.
    """
    if not copies:
        return cache
    src = jnp.asarray([c.src for c in copies], jnp.int32)
    dst = jnp.asarray([c.dst for c in copies], jnp.int32)
    out = dict(cache)
    for key, entry in paged_entries(cache):
        n_pages = entry["k_pages"].shape[1]
        src_r = _frame_rows(src, n_pages)
        dst_r = _frame_rows(dst, n_pages)
        out[key] = {
            "k_pages": entry["k_pages"].at[:, dst_r].set(
                entry["k_pages"][:, src_r]),
            "v_pages": entry["v_pages"].at[:, dst_r].set(
                entry["v_pages"][:, src_r]),
        }
    return out

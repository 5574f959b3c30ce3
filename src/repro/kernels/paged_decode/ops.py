"""Impl selection for the per-shard paged decode step.

:func:`paged_decode_shard` is the single entry the dispatch layer
(`repro.parallel.paged_attention`) calls from inside its shard_map body
(and from the single-device fallback with ``sid=0, n_shards=1``).  Both
impls honor one contract -- masked K/V WRITE into the owning pages, then
UNNORMALIZED partial-attention statistics (acc, m, l) over the pages this
shard owns -- so the caller's log-sum-exp merge is impl-independent:

``composed``   host-computed owner masks + jnp scatter/einsum
               (`repro.kernels.paged_decode.ref`) -- the oracle, and the
               default off-TPU;
``fused``      the VM-walking Pallas kernels
               (`repro.kernels.paged_decode.kernel`), interpret-mode off
               TPU.  Requires whole KV-head groups per tp shard
               (``hl % group == 0``); :func:`resolve_impl` refuses an
               explicit ``fused`` otherwise.

Without VM tables (batch ``kv_layout``; ``use_vm=False``) the fused path
synthesizes the identity block table in-jit -- sequence ``b`` owns frames
``b*max_pages ..`` -- so the kernels always walk a table, while the
composed path keeps its direct arithmetic mapping.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged_decode import kernel as _k
from repro.kernels.paged_decode import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(paged_kernel: str, hl: int, group: int) -> str:
    """Map the ModelConfig ``paged_kernel`` flag + platform to an impl.

    ``"auto"`` is ``fused`` on TPU when the ``hl`` local query heads split
    into whole KV-head groups, else ``composed``.  An explicit ``"fused"``
    that cannot run is a ValueError, never a quiet switch to the oracle."""
    if paged_kernel not in ("auto", "fused", "composed"):
        raise ValueError(f"unknown paged_kernel {paged_kernel!r}")
    whole_groups = hl % group == 0
    if paged_kernel == "fused" and not whole_groups:
        raise ValueError(
            f"paged_kernel='fused' needs whole KV-head groups per tensor-"
            f"parallel shard: {hl} local heads, groups of {group}")
    if paged_kernel == "auto":
        return "fused" if _on_tpu() and whole_groups else "composed"
    return paged_kernel


def _identity_tables(b: int, max_pages: int):
    """The batch layout's fixed mapping, materialized as VM tables."""
    bt = (jnp.arange(b, dtype=jnp.int32)[:, None] * max_pages
          + jnp.arange(max_pages, dtype=jnp.int32)[None, :])
    fr = jnp.zeros((b * max_pages,), jnp.int32)
    return bt, fr


def paged_decode_shard(q, k_new, v_new, k_pages, v_pages, lengths, bt, fl,
                       fr, wm, *, sid, n_shards, head_start, group, window,
                       max_pages, use_vm, impl, layer=0, interpret=None):
    """One shard of the paged decode step.

    q: [B, Hl, hd] local query heads (whole KV-head groups for ``fused``);
    k_new/v_new: [B, Hkv, hd]; k/v_pages: one layer's local pages
    [np_loc, Hkv, slots, hd], or the stacked pool [n_layers, np_loc, Hkv,
    slots, hd] of which layer ``layer`` is written and read in place;
    bt/fl/fr: replicated VM tables (ignored when ``use_vm`` is False);
    wm: [B] write mask; sid/head_start/layer may be traced.
    Returns (acc [B, Hl, hd] f32 unnormalized, m [B, Hl], l [B, Hl],
    k_pages', v_pages') with the pages in their input shape."""
    if impl == "composed":
        return _ref.paged_decode_shard(
            q, k_new, v_new, k_pages, v_pages, lengths, bt, fl, fr, wm,
            sid=sid, n_shards=n_shards, head_start=head_start, group=group,
            window=window, max_pages=max_pages, use_vm=use_vm, layer=layer)

    assert impl == "fused", impl
    interpret = (not _on_tpu()) if interpret is None else interpret
    b, hl, hd = q.shape
    if use_vm:
        bt_use, fr_use = bt, fr
    else:
        bt_use, fr_use = _identity_tables(b, max_pages)
    shape = k_pages.shape
    np_loc = shape[-4]
    row_base = 0
    if k_pages.ndim == 5:
        # merging the layer and page axes is a bitcast: the kernels alias
        # the stacked pool itself and reach the layer through row_base
        row_base = layer * np_loc
        k_pages = k_pages.reshape((-1,) + shape[2:])
        v_pages = v_pages.reshape((-1,) + shape[2:])
    kv_start = head_start // group
    meta = jnp.stack([jnp.asarray(sid, jnp.int32),
                      jnp.asarray(n_shards, jnp.int32),
                      jnp.asarray(kv_start, jnp.int32),
                      jnp.asarray(row_base, jnp.int32)])
    k_pages, v_pages = _k.paged_kv_write(
        k_new, v_new, k_pages, v_pages, bt_use, lengths, fr_use, wm, meta,
        np_loc=np_loc, interpret=interpret)
    qg = q.reshape(b, hl // group, group, hd)
    acc, m, l = _k.paged_gather_attend(
        qg, k_pages, v_pages, bt_use, lengths, meta, window=window,
        np_loc=np_loc, interpret=interpret)
    return (acc.reshape(b, hl, hd), m.reshape(b, hl), l.reshape(b, hl),
            k_pages.reshape(shape), v_pages.reshape(shape))

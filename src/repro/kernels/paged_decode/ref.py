"""Composed-ops oracle for the fused paged-decode kernels.

One shard's worth of the paged decode step, written as plain jnp over
host-computed owner masks -- the translation logic the fused kernels moved
into the grid (`repro.kernels.paged_decode.kernel`) lives here in its
original control-plane form: :func:`write_target` (frame lookup + frame_ro
write drop), :func:`owner_mask` (frame-membership test per physical page),
and a single-max softmax over every owned token.  This is the reference the
fused path is property-tested against, and the impl tier-1 runs on CPU.

All functions are per-shard: they see the local page arrays plus the
(replicated) VM tables and the shard's identity, exactly like a shard_map
body.  ``bt is None`` selects the fixed arithmetic mapping (sequence ``b``
owns pages ``b*max_pages ..``) used by the batch ``kv_layout``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.emem_vm.layout import shard_frames

NEG_INF = -1e30


def write_target(bt, fr, wm, pidx, b, max_pages):
    """Global frame each sequence writes this step, with drops applied.

    Returns (gpage [B], ok [B]): ``ok`` is False for masked-off sequences,
    unmapped pages, and shared (read-only) frames."""
    if bt is not None:
        gpage = bt[jnp.arange(b), pidx]
        ro = fr[jnp.clip(gpage, 0)] & (gpage >= 0)
        ok = wm & (gpage >= 0) & ~ro
    else:
        gpage = jnp.arange(b) * max_pages + pidx
        ok = wm
    return gpage, ok


def owner_mask(bt, fl, g_all, b, max_pages):
    """[B, n_local_pages] membership: does page g back sequence b?"""
    if bt is not None:
        lpage = fl[g_all]
        return bt[:, lpage] == g_all[None, :], lpage
    b_of, lpage = g_all // max_pages, g_all % max_pages
    return b_of[None, :] == jnp.arange(b)[:, None], lpage


def partial_attend(q, k_pages, v_pages, lengths, *, owner, lpage,
                   head_start, group, window):
    """Partial attention of q against this shard's pages.

    q: [B, Hl, hd] (local heads); k/v_pages: [np_loc, Hkv, slots, hd];
    owner: [B, np_loc] -- whether each local page belongs to sequence b
    (several rows may claim one page under prefix sharing); lpage: [np_loc]
    logical in-sequence page of each local page.
    Returns (acc [B, Hl, hd] unnormalized, m [B, Hl], l [B, Hl])."""
    b, hl, hd = q.shape
    np_loc, hkv, slots, _ = k_pages.shape
    scale = hd ** -0.5

    # in-sequence position of each local token, and who may attend it
    pos = lpage[:, None] * slots + jnp.arange(slots)
    tok_pos = pos.reshape(-1)                              # [T_loc]
    tok_owned = jnp.broadcast_to(owner[:, :, None],
                                 (b, np_loc, slots)).reshape(b, -1)

    # per-local-head KV head selection
    kvh = (head_start + jnp.arange(hl)) // group           # [Hl]
    kf = k_pages.transpose(0, 2, 1, 3).reshape(np_loc * slots, hkv, hd)
    vf = v_pages.transpose(0, 2, 1, 3).reshape(np_loc * slots, hkv, hd)
    kf, vf = kf.astype(jnp.float32), vf.astype(jnp.float32)
    k_sel = jnp.take(kf, kvh, axis=1)                      # [T_loc, Hl, hd]
    v_sel = jnp.take(vf, kvh, axis=1)

    hi = jax.lax.Precision.HIGHEST         # as the fused kernel's dots
    logits = jnp.einsum("bhd,thd->bht", q.astype(jnp.float32), k_sel,
                        precision=hi) * scale
    valid = tok_owned & (tok_pos[None, :] < lengths[:, None])  # [B, T_loc]
    if window is not None:
        valid &= tok_pos[None, :] >= (lengths[:, None] - window)
    logits = jnp.where(valid[:, None, :], logits, NEG_INF)
    m = logits.max(-1)                                     # [B, Hl]
    p = jnp.exp(logits - m[..., None])
    p = jnp.where(valid[:, None, :], p, 0.0)
    l = p.sum(-1)
    acc = jnp.einsum("bht,thd->bhd", p, v_sel, precision=hi)
    return acc, m, l


def paged_decode_shard(q, k_new, v_new, k_pages, v_pages, lengths, bt, fl,
                       fr, wm, *, sid, n_shards, head_start, group, window,
                       max_pages, use_vm, layer=0):
    """Composed per-shard decode step: masked WRITE scatter + partial
    attention over owned pages.  Same contract as the fused path in
    ``ops.paged_decode_shard``: returns (acc, m, l, k_pages, v_pages) with
    ``acc`` unnormalized so the caller can merge across shards.  A stacked
    pool ``[n_layers, np_loc, ...]`` has layer ``layer`` sliced out, run
    and written back (copies the fused path avoids)."""
    if k_pages.ndim == 5:
        kl = jax.lax.dynamic_index_in_dim(k_pages, layer, keepdims=False)
        vl = jax.lax.dynamic_index_in_dim(v_pages, layer, keepdims=False)
        acc, m, l, kl, vl = paged_decode_shard(
            q, k_new, v_new, kl, vl, lengths, bt, fl, fr, wm, sid=sid,
            n_shards=n_shards, head_start=head_start, group=group,
            window=window, max_pages=max_pages, use_vm=use_vm)
        return (acc, m, l,
                jax.lax.dynamic_update_index_in_dim(k_pages, kl, layer, 0),
                jax.lax.dynamic_update_index_in_dim(v_pages, vl, layer, 0))
    b = q.shape[0]
    np_loc, slots = k_pages.shape[0], k_pages.shape[2]
    bt_ = bt if use_vm else None
    fl_ = fl if use_vm else None
    # WRITE: scatter the new K/V row into its owning shard's page
    pidx = (lengths - 1) // slots
    gpage, ok = write_target(bt_, fr, wm, pidx, b, max_pages)
    rows = jnp.where(ok & (gpage % n_shards == sid),
                     gpage // n_shards, np_loc)
    off = (lengths - 1) % slots
    k_pages = k_pages.at[rows, :, off].set(k_new.astype(k_pages.dtype),
                                           mode="drop")
    v_pages = v_pages.at[rows, :, off].set(v_new.astype(v_pages.dtype),
                                           mode="drop")
    # READ/compute: partial attention over owned pages
    g_all = shard_frames(jnp.arange(np_loc), sid, n_shards)  # global frames
    owner, lpage = owner_mask(bt_, fl_, g_all, b, max_pages)
    acc, m, l = partial_attend(q, k_pages, v_pages, lengths, owner=owner,
                               lpage=lpage, head_start=head_start,
                               group=group, window=window)
    return acc, m, l, k_pages, v_pages

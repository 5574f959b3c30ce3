"""Fused VM-aware paged-decode Pallas kernels (PAPER.md §2.1).

The paper's point about emulated large memories is that address translation
is cheap when it *rides the memory access* -- READ/WRITE messages carry the
owner computation with them instead of paying a separate indirection
round-trip.  These kernels do exactly that: the BlockManager's translation
state (``cache["vm"]``: ``block_table``, ``frame_lpage``, ``frame_ro``) is
scalar-prefetched into SMEM and walked *inside* the kernel grid, so the
logical-page -> frame -> physical-row translation, the frame-membership
ownership test, and the ``frame_ro`` write-drop all happen on the scalar
core while the vector core streams pages -- no host-side owner masks, no
gather of translated indices through HBM.

Two kernels, mirroring the WRITE / READ halves of the paper's protocol:

``paged_kv_write``
    grid = (B,).  Sequence ``b``'s block-table row names the frame its next
    token lands in; the index map translates frame -> local physical row
    (cyclic distribution: shard ``f % S`` holds frame ``f`` at row
    ``f // S``).  Several sequences can map to the same local row (every
    not-my-shard sequence clamps somewhere), so the body is *row-oriented
    and idempotent*: each visit re-derives which sequence (if any) writes
    the visited row by scanning the block tables, making repeated visits
    write identical content -- safe under output aliasing regardless of
    pipeline flush order.  Pages are HBM-aliased in/out
    (``input_output_aliases``) so only the <= B visited pages move.

``paged_gather_attend``
    grid = (B, Hkv_loc, max_lpages).  The innermost axis walks sequence
    ``b``'s block-table row page by page: frame membership IS the walk
    (``block_table[b, j] == f`` by construction), ownership is
    ``f % S == sid``, and the online-softmax scratch accumulates exactly
    the pages this shard owns for this sequence -- the fused
    ``emem_gather`` + ``decode_attention``.  Emits UNNORMALIZED
    (acc, m, l) so the sequence-parallel dispatch layer can log-sum-exp
    merge partials across KV shards, identically to the composed path.

Pages are laid out ``[n_pages, Hkv, slots, D]``: one KV head of one page
is a contiguous ``[slots, D]`` slab, so the read kernel's block has legal
TPU trailing dimensions, and the write kernel moves whole pages.

Both take a ``meta`` scalar operand ``[sid, n_shards, kv_start, row_base]``
so one compiled kernel serves every shard of a shard_map body (sid/kv_start
are traced axis indices) and every layer of a layer scan: the page arrays
may be the whole stacked pool viewed flat, ``[n_layers * np_loc, Hkv,
slots, D]``, with ``row_base = layer * np_loc`` the layer's first row.  A
row is clamped to the layer's ``np_loc`` rows before the base is added, so
no visit leaves the layer.  Interpret mode keeps tier-1 running on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: attention matmuls run at full float32 precision, as in the composed
#: oracle (``ref.py``), so the two impls agree to float32 rounding on TPU
#: too, where the default precision rounds operands to bf16
HIGHEST = jax.lax.Precision.HIGHEST


# -- WRITE: scatter the new K/V token row into its owning frame ---------------

def _write_page_index(b, bt_ref, len_ref, fr_ref, wm_ref, meta_ref, *,
                      page_slots: int, max_lpages: int, np_loc: int):
    """Local physical row sequence ``b``'s write lands in (clamped)."""
    pidx = jnp.clip((len_ref[b] - 1) // page_slots, 0, max_lpages - 1)
    f = bt_ref[b * max_lpages + pidx]
    ns = meta_ref[1]
    return jnp.clip(jnp.where(f >= 0, f // ns, 0), 0, np_loc - 1)


def _kv_write_kernel(bt_ref, len_ref, fr_ref, wm_ref, meta_ref,
                     k_new_ref, v_new_ref, k_in_ref, v_in_ref,
                     k_out_ref, v_out_ref, *, page_slots: int,
                     max_lpages: int, np_loc: int):
    """Row-oriented body: re-derive the visited row's writer from the VM
    tables, so every visit of a row writes identical content."""
    b_vis = pl.program_id(0)
    n_seqs = k_new_ref.shape[0]
    sid, ns = meta_ref[0], meta_ref[1]
    row = _write_page_index(
        b_vis, bt_ref, len_ref, fr_ref, wm_ref, meta_ref,
        page_slots=page_slots, max_lpages=max_lpages, np_loc=np_loc)
    g = row * ns + sid                       # global frame id of this row

    def scan(b, carry):
        writer, off = carry
        length = len_ref[b]
        pidx = jnp.clip((length - 1) // page_slots, 0, max_lpages - 1)
        f = bt_ref[b * max_lpages + pidx]
        hit = ((wm_ref[b] != 0) & (length > 0) & (f == g)
               & (fr_ref[jnp.where(f >= 0, f, 0)] == 0) & (f >= 0))
        return (jnp.where(hit, b, writer),
                jnp.where(hit, (length - 1) % page_slots, off))

    writer, off = jax.lax.fori_loop(0, n_seqs, scan,
                                    (jnp.int32(-1), jnp.int32(0)))
    # Mosaic cannot store at a dynamic slot (sublane) offset, so the row
    # lands by a select over the whole [Hkv, slots, D] page
    hit = ((jax.lax.broadcasted_iota(jnp.int32, k_in_ref.shape[1:], 1)
            == off) & (writer >= 0))
    w = jnp.where(writer >= 0, writer, 0)
    k_out_ref[0] = jnp.where(
        hit, k_new_ref[w].astype(k_out_ref.dtype)[:, None, :], k_in_ref[0])
    v_out_ref[0] = jnp.where(
        hit, v_new_ref[w].astype(v_out_ref.dtype)[:, None, :], v_in_ref[0])


def paged_kv_write(k_new: jax.Array, v_new: jax.Array, k_pages: jax.Array,
                   v_pages: jax.Array, block_table: jax.Array,
                   lengths: jax.Array, frame_ro: jax.Array,
                   write_mask: jax.Array, meta: jax.Array, *,
                   np_loc: int | None = None, interpret: bool = False):
    """k_new/v_new: [B, Hkv, D]; k/v_pages: [rows, Hkv, slots, D] holding
    this shard's ``np_loc`` pages of the written layer from row
    ``meta[3]`` on (``np_loc`` defaults to all rows); block_table:
    [B, max_lpages] GLOBAL frame ids; meta: [sid, n_shards, kv_start,
    row_base].  Returns updated (k_pages, v_pages), HBM-aliased with the
    inputs."""
    b, hkv, d = k_new.shape
    page_slots = k_pages.shape[2]
    np_loc = k_pages.shape[0] if np_loc is None else np_loc
    max_lpages = block_table.shape[1]

    def page_map(bb, bt_ref, len_ref, fr_ref, wm_ref, meta_ref):
        row = _write_page_index(bb, bt_ref, len_ref, fr_ref, wm_ref,
                                meta_ref, page_slots=page_slots,
                                max_lpages=max_lpages, np_loc=np_loc)
        return (row + meta_ref[3], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((b, hkv, d), lambda bb, *_: (0, 0, 0)),
            pl.BlockSpec((b, hkv, d), lambda bb, *_: (0, 0, 0)),
            pl.BlockSpec((1, hkv, page_slots, d), page_map),
            pl.BlockSpec((1, hkv, page_slots, d), page_map),
        ],
        out_specs=[
            pl.BlockSpec((1, hkv, page_slots, d), page_map),
            pl.BlockSpec((1, hkv, page_slots, d), page_map),
        ],
    )
    kernel = functools.partial(_kv_write_kernel, page_slots=page_slots,
                               max_lpages=max_lpages, np_loc=np_loc)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # inputs are counted including the scalar-prefetch operands
        input_output_aliases={7: 0, 8: 1},
        interpret=interpret,
        name="paged_kv_write",
    )(block_table.reshape(-1).astype(jnp.int32),
      lengths.astype(jnp.int32), frame_ro.astype(jnp.int32),
      write_mask.astype(jnp.int32), meta.astype(jnp.int32),
      k_new, v_new, k_pages, v_pages)


# -- READ: walk the block table, gather + attend in one pass ------------------

def _gather_attend_kernel(bt_ref, len_ref, meta_ref, q_ref, k_ref, v_ref,
                          acc_out, m_out, l_out, m_sc, l_sc, acc_sc, *,
                          scale: float, page_slots: int, window: int | None):
    b = pl.program_id(0)
    j = pl.program_id(2)
    n_lp = pl.num_programs(2)
    length = len_ref[b]
    sid, ns = meta_ref[0], meta_ref[1]
    f = bt_ref[b * n_lp + j]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    owned = (f >= 0) & (f % ns == sid)
    run = owned & (j * page_slots < length)
    if window is not None:
        run = run & ((j + 1) * page_slots > length - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)                  # [PS, D]
        v = v_ref[0, 0].astype(jnp.float32)                  # [PS, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=HIGHEST,
                                preferred_element_type=jnp.float32) * scale
        pos = j * page_slots + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = pos < length
        if window is not None:
            valid = valid & (pos >= length - window)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(j == n_lp - 1)
    def _finalize():
        acc_out[0, 0] = acc_sc[...]
        m_out[0, 0] = m_sc[...]
        l_out[0, 0] = l_sc[...]


def paged_gather_attend(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        block_table: jax.Array, lengths: jax.Array,
                        meta: jax.Array, *, scale: float | None = None,
                        window: int | None = None,
                        np_loc: int | None = None, interpret: bool = False):
    """q: [B, Hkv_loc, G, D] (this tp shard's query-head groups);
    k/v_pages: [rows, Hkv, slots, D] holding the layer's ``np_loc`` local
    pages from row ``meta[3]`` on (``np_loc`` defaults to all rows);
    block_table: [B, max_lpages] GLOBAL frame ids; meta: [sid, n_shards,
    kv_start, row_base] with kv_start the first KV head of this tp shard.
    Returns UNNORMALIZED partials (acc [B, Hkv_loc, G, D] f32, m, l
    [B, Hkv_loc, G, 1] f32).

    Each grid cell streams one head's ``[slots, D]`` slab of one page, the
    tile-aligned trailing block a TPU BlockSpec accepts (``slots`` a
    multiple of 8, ``D`` of 128, or the whole axis)."""
    b, hkv_loc, g, d = q.shape
    page_slots = k_pages.shape[2]
    np_loc = k_pages.shape[0] if np_loc is None else np_loc
    max_lpages = block_table.shape[1]
    scale = (d ** -0.5) if scale is None else scale

    def row_map(bb, h, j, bt_ref, len_ref, meta_ref):
        f = bt_ref[bb * max_lpages + j]
        ns = meta_ref[1]
        ok = (f >= 0) & (f % ns == meta_ref[0])
        row = jnp.clip(jnp.where(ok, f // ns, 0), 0, np_loc - 1)
        return (row + meta_ref[3], meta_ref[2] + h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv_loc, max_lpages),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bb, h, j, *_: (bb, h, 0, 0)),
            pl.BlockSpec((1, 1, page_slots, d), row_map),
            pl.BlockSpec((1, 1, page_slots, d), row_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bb, h, j, *_: (bb, h, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda bb, h, j, *_: (bb, h, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda bb, h, j, *_: (bb, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_gather_attend_kernel, scale=scale,
                               page_slots=page_slots, window=window)
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv_loc, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv_loc, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv_loc, g, 1), jnp.float32),
        ],
        interpret=interpret,
        name="paged_gather_attend",
    )(block_table.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      meta.astype(jnp.int32), q, k_pages, v_pages)
    return acc, m, l

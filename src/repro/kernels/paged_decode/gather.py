"""Pallas TPU kernels for paged gather (the emulated-memory DMA hot loop).

Two granularities, matching the paper's §2.1 access modes:

* ``gather_slots``  -- random single-slot READs.  The scalar-prefetched slot
  vector drives the ``BlockSpec`` index map, so the page containing each
  request is DMA'd HBM->VMEM ahead of the compute step that selects the slot
  row -- the software analogue of the paper's NIC-driven remote DMA.

* ``gather_pages``  -- bulk page transfers (the KV-cache path).

Block shapes: one page per grid step; ``width`` padded to the 128-lane TPU
tiling by the ops wrapper.  VMEM working set per step = page_slots x width x 4
bytes (two buffers with pipelining), e.g. 128 x 512 x 4 x 2 = 512 KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_slots_kernel(slots_ref, page_ref, out_ref, *, page_slots: int):
    q = pl.program_id(0)
    slot = slots_ref[q]

    @pl.when(slot >= 0)
    def _valid():
        offset = slot % page_slots
        out_ref[0, :] = page_ref[0, offset, :]

    @pl.when(slot < 0)
    def _empty():
        out_ref[0, :] = jnp.zeros_like(out_ref[0, :])


def gather_slots(pages: jax.Array, slots: jax.Array, *,
                 interpret: bool = False) -> jax.Array:
    """pages: [n_pages, page_slots, width]; slots: [q] -> [q, width]."""
    n_pages, page_slots, width = pages.shape
    q = slots.shape[0]

    def page_index_map(qi, slots_ref):
        slot = slots_ref[qi]
        page = jnp.where(slot >= 0, slot // page_slots, 0)
        return (page, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q,),
        in_specs=[pl.BlockSpec((1, page_slots, width), page_index_map)],
        out_specs=pl.BlockSpec((1, width), lambda qi, s: (qi, 0)),
    )
    kernel = functools.partial(_gather_slots_kernel, page_slots=page_slots)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((q, width), pages.dtype),
        interpret=interpret,
    )(slots.astype(jnp.int32), pages)


def _gather_pages_kernel(ids_ref, page_ref, out_ref):
    p = pl.program_id(0)

    @pl.when(ids_ref[p] >= 0)
    def _valid():
        out_ref[...] = page_ref[...]

    @pl.when(ids_ref[p] < 0)
    def _empty():
        out_ref[...] = jnp.zeros_like(out_ref)


def gather_pages(pages: jax.Array, page_ids: jax.Array, *,
                 interpret: bool = False) -> jax.Array:
    """pages: [n_pages, page_slots, width]; page_ids: [p] -> [p, page_slots, width]."""
    n_pages, page_slots, width = pages.shape
    p = page_ids.shape[0]

    def page_index_map(pi, ids_ref):
        pid = ids_ref[pi]
        return (jnp.where(pid >= 0, pid, 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(p,),
        in_specs=[pl.BlockSpec((1, page_slots, width), page_index_map)],
        out_specs=pl.BlockSpec((1, page_slots, width), lambda pi, s: (pi, 0, 0)),
    )
    return pl.pallas_call(
        _gather_pages_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((p, page_slots, width), pages.dtype),
        interpret=interpret,
    )(page_ids.astype(jnp.int32), pages)

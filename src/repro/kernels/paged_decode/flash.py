"""Pallas TPU flash-decode kernel.

One new token per sequence attends to its cached history.  Grid =
(B, Hkv, S/BK): the KV-sequence axis is innermost/sequential with the
online-softmax state in VMEM scratch; all G = Hq/Hkv grouped query heads of
one KV head are processed together so the q block is [G, D] (MXU-aligned
after the ops wrapper pads G to 8 sublanes).

Emits BOTH the normalized output and the (m, l) statistics so the
sequence-parallel serving path can merge partials across KV shards (the
emulated-memory decode: each shard owns a subset of the pages).

VMEM per step (BK=512, D=128): k 256 KB + v 256 KB + q/acc tiny -> well
within budget; lengths are scalar-prefetched to mask the valid region.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_out, l_out,
                   m_sc, l_sc, acc_sc, *, scale: float, block_k: int,
                   window: int | None):
    b = pl.program_id(0)
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)
    length = len_ref[b]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    k_off = j * block_k
    lo = length - window if window is not None else 0
    run = k_off < length
    if window is not None:
        run = jnp.logical_and(run, k_off + block_k > lo)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                   # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)                   # [BK, D]
        v = v_ref[0, 0].astype(jnp.float32)                   # [BK, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = k_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = pos < length
        if window is not None:
            valid = jnp.logical_and(valid, pos >= lo)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = l_sc[...]
        o_ref[0, 0] = (acc_sc[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
        m_out[0, 0] = m_sc[...]
        l_out[0, 0] = l_sc[...]


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 lengths: jax.Array, *, scale: float | None = None,
                 window: int | None = None, block_k: int = 512,
                 interpret: bool = False):
    """q: [B, Hkv, G, D]; k, v: [B, Hkv, S, D]; lengths: [B].

    Returns (out [B, Hkv, G, D], m [B, Hkv, G, 1], l [B, Hkv, G, 1]).
    ``out`` is normalized by the local ``l``; (m, l) allow cross-shard merge.
    """
    b, hkv, g, d = q.shape
    _, _, s, _ = k.shape
    scale = (d ** -0.5) if scale is None else scale
    block_k = min(block_k, s)
    assert s % block_k == 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, s // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bb, h, j, L: (bb, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bb, h, j, L: (bb, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bb, h, j, L: (bb, h, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bb, h, j, L: (bb, h, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda bb, h, j, L: (bb, h, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda bb, h, j, L: (bb, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k,
                               window=window)
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
            jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32),
        ],
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v)
    return out, m, l

"""Pallas TPU flash-attention kernel (GQA, causal, sliding-window).

Tiling: grid = (B * Hq, Sq/BQ, Skv/BK); the KV dimension is the innermost,
sequential grid axis, so the online-softmax running state (m, l, acc) lives
in VMEM scratch that persists across KV steps.  Fully-masked KV blocks are
skipped with ``pl.when`` (zero-FLOP skip for the causal upper triangle and
outside the sliding window).

VMEM working set per step (BQ=BK=512, D=128, f32 acc):
  q 256 KB + k 256 KB + v 256 KB + acc 256 KB + p 1 MB -> ~2 MB, double-
  buffered well under the ~16 MB v5e budget; MXU dims are multiples of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
               scale: float, causal: bool, window: int | None,
               block_q: int, block_k: int, q_len: int, kv_len: int):
    i = pl.program_id(1)          # query block
    j = pl.program_id(2)          # kv block (sequential)
    n_kv = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # block extents in absolute positions (queries sit at the sequence tail)
    q_off = (kv_len - q_len) + i * block_q
    k_off = j * block_k
    run = jnp.asarray(True)
    if causal:  # skip blocks fully above the diagonal
        run = jnp.logical_and(run, k_off <= q_off + block_q - 1)
    if window is not None:  # skip blocks entirely left of every query's window
        run = jnp.logical_and(run, k_off + block_k > q_off - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                      # [BQ, D]
        k = k_ref[0].astype(jnp.float32)                      # [BK, D]
        v = v_ref[0].astype(jnp.float32)                      # [BK, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_sc[...]                                    # [BQ, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                                # [BQ, BK]
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_sc[...] = acc_sc[...] * alpha + pv
        m_sc[...] = m_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = l_sc[...]
        o_ref[0] = (acc_sc[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q: [B, Hq, Sq, D]; k, v: [B, Hkv, Skv, D] -> [B, Hq, Sq, D]."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    g = hq // hkv
    scale = (d ** -0.5) if scale is None else scale
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0, (sq, block_q, skv, block_k)

    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)

    def q_map(h, i, j):
        return (h, i, 0)

    def kv_map(h, i, j):
        return (h // g, j, 0)

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, q_len=sq, kv_len=skv)

    scratch = [
        pltpu.VMEM((block_q, 1), jnp.float32),   # m
        pltpu.VMEM((block_q, 1), jnp.float32),   # l
        pltpu.VMEM((block_q, d), jnp.float32),   # acc
    ]
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, sq // block_q, skv // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qf, kf, vf)
    return out.reshape(b, hq, sq, d)

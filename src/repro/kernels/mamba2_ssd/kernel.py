"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid = (Bt * H, S/Q): the chunk axis is innermost/sequential, with the
inter-chunk SSM state [N, P] carried in VMEM scratch across chunk steps
(reset at chunk 0).  Within a chunk the computation is three MXU matmuls
(C @ B^T, masked-decay weighted (CB) @ X, and the rank-Q state update
B^T @ X), which is exactly the "duality" the paper exploits: the quadratic
intra-chunk part uses the MXU, the linear inter-chunk part is a cheap
recurrence at chunk granularity.

VMEM per step (Q=128, P=64, N=128): x/y 32 KB, B/C 64 KB, M 64 KB, state
32 KB -- far under budget; Q and N are 128-aligned for the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, d_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_sc, *,
                n_heads: int, chunk: int):
    bh = pl.program_id(0)
    c = pl.program_id(1)
    h = bh % n_heads

    @pl.when(c == 0)
    def _init():
        state_sc[...] = jnp.zeros_like(state_sc)

    a = a_ref[h]                                       # scalar A_h (negative)
    d = d_ref[h]                                       # scalar D_h
    x = x_ref[0].astype(jnp.float32)                   # [Q, P]
    dt = dt_ref[0].astype(jnp.float32)                 # [Q]
    bmat = b_ref[0].astype(jnp.float32)                # [Q, N]
    cmat = c_ref[0].astype(jnp.float32)                # [Q, N]

    loga = dt * a                                      # [Q]
    lcum = jnp.cumsum(loga)                            # [Q] inclusive

    # intra-chunk (quadratic, MXU): masked decay matrix
    diff = lcum[:, None] - lcum[None, :]               # [Q, Q]
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    m = jnp.exp(jnp.where(tri, diff, -1e30))
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, Q]
    xdt = x * dt[:, None]                              # [Q, P]
    y = jax.lax.dot_general(cb * m, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [Q, P]

    # inter-chunk: contribution of the incoming state
    state = state_sc[...]                              # [N, P]
    y += jnp.exp(lcum)[:, None] * jax.lax.dot_general(
        cmat, state, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: decay + rank-Q injection
    w = jnp.exp(lcum[-1] - lcum) * dt                  # [Q]
    upd = jax.lax.dot_general(bmat, x * w[:, None], (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [N, P]
    state_sc[...] = state * jnp.exp(lcum[-1]) + upd

    y_ref[0] = (y + d * x).astype(y_ref.dtype)


def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
        C: jax.Array, D: jax.Array, *, chunk: int = 128,
        interpret: bool = False) -> jax.Array:
    """Chunked SSD scan.  Shapes as in ref.ssd_scan; returns y only."""
    bt, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert s % chunk == 0
    heads_per_group = h // g

    # layouts: x/dt head-major, B/C group-major
    xf = x.transpose(0, 2, 1, 3).reshape(bt * h, s, p)
    dtf = dt.transpose(0, 2, 1).reshape(bt * h, s)
    bf = B.transpose(0, 2, 1, 3).reshape(bt * g, s, n)
    cf = C.transpose(0, 2, 1, 3).reshape(bt * g, s, n)

    def bc_map(bh, c, a_ref, d_ref):
        batch, head = bh // h, bh % h
        return (batch * g + head // heads_per_group, c, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bt * h, s // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, c, a, d: (bh, c, 0)),
            pl.BlockSpec((1, chunk), lambda bh, c, a, d: (bh, c)),
            pl.BlockSpec((1, chunk, n), bc_map),
            pl.BlockSpec((1, chunk, n), bc_map),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda bh, c, a, d: (bh, c, 0)),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
    )
    kernel = functools.partial(_ssd_kernel, n_heads=h, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bt * h, s, p), x.dtype),
        interpret=interpret,
    )(A.astype(jnp.float32), D.astype(jnp.float32), xf, dtf, bf, cf)
    return y.reshape(bt, h, s, p).transpose(0, 2, 1, 3)

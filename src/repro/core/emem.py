"""EMem -- an executable emulated large memory over a collection of small ones.

This is the paper's §2.1 emulation scheme mapped onto a JAX device mesh
(DESIGN.md §2): a flat logical address space of ``n_slots`` slots (each slot a
``width``-vector) is split into pages, and pages are block-cyclically owned by
the devices of one or more mesh axes -- exactly the controller's distribution
of "a contiguous address range ... over the tiles".

Random-access reads and writes are communication sequences, vectorized: a
batch of addresses is binned by owner shard, routed with ``all_to_all``
(the READ/WRITE request messages), served by a local gather/scatter on the
owner (the DMA engine -- on TPU this is the ``emem_gather`` Pallas kernel),
and routed back.  All shapes are static: each (requester, owner) pair gets a
fixed ``capacity`` of request slots, sized by a capacity factor, mirroring a
fixed-size hardware message queue.  Overflowing requests are dropped (reads
return 0) -- tests pin the no-drop regime, and :func:`dispatch_stats` exposes
the overflow probability so callers can size the capacity.

Addressing:
    page, offset = divmod(addr, page_slots)
    owner        = page %  n_shards          (cyclic distribution)
    local_page   = page // n_shards
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PSpec


@dataclasses.dataclass(frozen=True)
class EMemSpec:
    """Static description of an emulated memory."""
    n_slots: int                    # logical slots
    width: int                      # payload elements per slot
    page_slots: int = 128           # slots per page
    n_shards: int = 1               # devices emulating the memory
    dtype: jnp.dtype = jnp.float32

    def __post_init__(self):
        if self.n_slots % self.page_slots != 0:
            raise ValueError("n_slots must be a multiple of page_slots")
        if self.n_pages % self.n_shards != 0:
            raise ValueError("n_pages must be a multiple of n_shards")

    @property
    def n_pages(self) -> int:
        return self.n_slots // self.page_slots

    @property
    def pages_per_shard(self) -> int:
        return self.n_pages // self.n_shards

    @property
    def slots_per_shard(self) -> int:
        return self.pages_per_shard * self.page_slots

    @property
    def bytes_total(self) -> int:
        return self.n_slots * self.width * jnp.dtype(self.dtype).itemsize

    def global_shape(self) -> tuple[int, int, int]:
        return (self.n_pages, self.page_slots, self.width)

    def owner_of(self, addr):
        return (addr // self.page_slots) % self.n_shards

    def local_slot_of(self, addr):
        """Slot index within the owner's local [slots_per_shard, width] view."""
        page, offset = addr // self.page_slots, addr % self.page_slots
        return (page // self.n_shards) * self.page_slots + offset


def create(spec: EMemSpec) -> jax.Array:
    """A zero-initialized emulated memory (global logical view)."""
    return jnp.zeros(spec.global_shape(), spec.dtype)


def capacity_for(spec: EMemSpec, n_requests_per_shard: int,
                 capacity_factor: float = 2.0) -> int:
    """Request-queue capacity per (requester, owner) pair."""
    mean = n_requests_per_shard / spec.n_shards
    cap = int(math.ceil(mean * capacity_factor))
    return max(1, min(cap, n_requests_per_shard))


# ---------------------------------------------------------------------------
# Reference (single logical view) paths -- the oracle for all tests
# ---------------------------------------------------------------------------
def read_ref(spec: EMemSpec, data: jax.Array, addrs: jax.Array) -> jax.Array:
    """Gather slots at ``addrs``: [R] -> [R, width]."""
    flat = data.reshape(spec.n_slots, spec.width)
    return flat[addrs]


def write_ref(spec: EMemSpec, data: jax.Array, addrs: jax.Array,
              values: jax.Array) -> jax.Array:
    flat = data.reshape(spec.n_slots, spec.width)
    flat = flat.at[addrs].set(values)
    return flat.reshape(spec.global_shape())


# ---------------------------------------------------------------------------
# Dispatch plan (pure, shape-static) -- shared by read and write
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _Dispatch:
    owners: jax.Array        # [R] owner shard per request
    pos: jax.Array           # [R] slot within the (requester, owner) queue
    valid: jax.Array         # [R] fits within capacity
    send_addr: jax.Array     # [S, C] local slot index at owner (-1 = empty)


def _plan(spec: EMemSpec, addrs: jax.Array, capacity: int) -> _Dispatch:
    n_shards = spec.n_shards
    owners = spec.owner_of(addrs)                                # [R]
    onehot = owners[:, None] == jnp.arange(n_shards)[None, :]    # [R, S]
    pos_all = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1   # [R, S]
    pos = jnp.take_along_axis(pos_all, owners[:, None], axis=1)[:, 0]
    valid = pos < capacity
    local_slot = spec.local_slot_of(addrs)
    # scatter only valid entries; invalid rows target row n_shards -> dropped
    send_addr = jnp.full((n_shards, capacity), -1, jnp.int32).at[
        jnp.where(valid, owners, n_shards),
        jnp.where(valid, pos, 0)].set(local_slot.astype(jnp.int32), mode="drop")
    return _Dispatch(owners, pos, valid, send_addr)


# ---------------------------------------------------------------------------
# Shard-local bodies (run inside shard_map over the memory axes)
# ---------------------------------------------------------------------------
def _local_gather(spec: EMemSpec, local_data: jax.Array,
                  slots: jax.Array) -> jax.Array:
    """Gather local slots; slot -1 returns zeros. [Q] -> [Q, width].

    On TPU this is the ``repro.kernels.emem_gather`` Pallas kernel; the jnp
    form below is its oracle and the CPU execution path.
    """
    flat = local_data.reshape(spec.slots_per_shard, spec.width)
    safe = jnp.where(slots >= 0, slots, 0)
    vals = flat[safe]
    return jnp.where((slots >= 0)[:, None], vals, 0).astype(spec.dtype)


def _local_scatter(spec: EMemSpec, local_data: jax.Array, slots: jax.Array,
                   values: jax.Array) -> jax.Array:
    flat = local_data.reshape(spec.slots_per_shard, spec.width)
    oob = spec.slots_per_shard  # out-of-range index -> dropped
    idx = jnp.where(slots >= 0, slots, oob)
    flat = flat.at[idx].set(values.astype(spec.dtype), mode="drop")
    return flat.reshape(spec.pages_per_shard, spec.page_slots, spec.width)


def read_shard(spec: EMemSpec, axis: str | tuple[str, ...], local_data: jax.Array,
               addrs: jax.Array, capacity: int) -> jax.Array:
    """Distributed read body. ``local_data``: this shard's pages
    [pages_per_shard, page_slots, width]; ``addrs``: this shard's requests [R].
    Returns [R, width] (zeros for dropped/overflowed requests)."""
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    if spec.n_shards == 1:
        return _local_gather(spec, local_data, addrs.astype(jnp.int32))
    d = _plan(spec, addrs, capacity)
    # request messages: [S, C] routed so owner o receives row per requester
    recv_addr = _all_to_all(d.send_addr, axes)                    # [S, C]
    served = _local_gather(spec, local_data, recv_addr.reshape(-1))
    served = served.reshape(spec.n_shards, capacity, spec.width)
    recv_vals = _all_to_all(served, axes)                         # [S, C, W]
    out = recv_vals[d.owners, jnp.where(d.valid, d.pos, 0)]
    return jnp.where(d.valid[:, None], out, 0)


def write_shard(spec: EMemSpec, axis: str | tuple[str, ...], local_data: jax.Array,
                addrs: jax.Array, values: jax.Array, capacity: int) -> jax.Array:
    """Distributed write body; returns the updated local pages."""
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    if spec.n_shards == 1:
        return _local_scatter(spec, local_data, addrs.astype(jnp.int32), values)
    d = _plan(spec, addrs, capacity)
    send_vals = jnp.zeros((spec.n_shards, capacity, spec.width), spec.dtype)
    send_vals = send_vals.at[
        jnp.where(d.valid, d.owners, spec.n_shards),
        jnp.where(d.valid, d.pos, 0)].set(values.astype(spec.dtype), mode="drop")
    recv_addr = _all_to_all(d.send_addr, axes)
    recv_vals = _all_to_all(send_vals, axes)
    return _local_scatter(spec, local_data, recv_addr.reshape(-1),
                          recv_vals.reshape(-1, spec.width))


def _all_to_all(x: jax.Array, axes: tuple[str, ...]) -> jax.Array:
    """Tiled all_to_all over (possibly multiple) mesh axes on leading dim."""
    return jax.lax.all_to_all(x, axes, split_axis=0, concat_axis=0, tiled=True)


# ---------------------------------------------------------------------------
# Mesh-level wrappers (pjit entry points)
# ---------------------------------------------------------------------------
def _mem_pspec(axes: Sequence[str]) -> PSpec:
    return PSpec(tuple(axes) if len(axes) > 1 else axes[0])


@functools.lru_cache(maxsize=None)
def _sharded(shard_fn, spec: EMemSpec, mesh: Mesh, axes: tuple[str, ...],
             capacity: int, n_args: int):
    """``shard_fn`` over the memory shards, jitted once per geometry.  A
    bare ``shard_map`` called outside a jit runs its body op by op and
    compiles every op again on each call."""
    body = functools.partial(shard_fn, spec, axes, capacity=capacity)
    mem_ps = _mem_pspec(axes)
    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(mem_ps,) * n_args,
                                 out_specs=mem_ps, check_vma=False))


def read(spec: EMemSpec, mesh: Mesh, axes: Sequence[str], data: jax.Array,
         addrs: jax.Array, capacity_factor: float = 2.0) -> jax.Array:
    """Distributed random read of ``addrs`` (global [R]) -> [R, width]."""
    axes = tuple(axes)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    assert n_shards == spec.n_shards, (n_shards, spec.n_shards)
    r_shard = addrs.shape[0] // n_shards
    cap = capacity_for(spec, r_shard, capacity_factor)
    return _sharded(read_shard, spec, mesh, axes, cap, 2)(data, addrs)


def write(spec: EMemSpec, mesh: Mesh, axes: Sequence[str], data: jax.Array,
          addrs: jax.Array, values: jax.Array,
          capacity_factor: float = 2.0) -> jax.Array:
    """Distributed random write; returns updated memory."""
    axes = tuple(axes)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    assert n_shards == spec.n_shards
    r_shard = addrs.shape[0] // n_shards
    cap = capacity_for(spec, r_shard, capacity_factor)
    return _sharded(write_shard, spec, mesh, axes, cap, 3)(data, addrs,
                                                           values)


def sharding_for(spec: EMemSpec, mesh: Mesh, axes: Sequence[str]) -> NamedSharding:
    return NamedSharding(mesh, PSpec(tuple(axes) if len(axes) > 1 else axes[0]))


# ---------------------------------------------------------------------------
# Layout conversion: physical (block-sharded, cyclically owned) <-> logical
# ---------------------------------------------------------------------------
def _page_perm(spec: EMemSpec) -> np.ndarray:
    """physical row of logical page p = (p % S) * pages_per_shard + p // S."""
    p = np.arange(spec.n_pages)
    return (p % spec.n_shards) * spec.pages_per_shard + p // spec.n_shards


def to_logical(spec: EMemSpec, data: jax.Array) -> jax.Array:
    """Physical (device-block) page order -> logical page order."""
    return jnp.asarray(data)[jnp.asarray(_page_perm(spec))]


def from_logical(spec: EMemSpec, data: jax.Array) -> jax.Array:
    """Logical page order -> physical page order for device_put."""
    inv = np.empty(spec.n_pages, np.int64)
    inv[_page_perm(spec)] = np.arange(spec.n_pages)
    return jnp.asarray(data)[jnp.asarray(inv)]


# ---------------------------------------------------------------------------
# Analytics: expected traffic + overflow (feeds the roofline and §Perf)
# ---------------------------------------------------------------------------
def dispatch_stats(spec: EMemSpec, n_requests_per_shard: int,
                   capacity_factor: float = 2.0) -> dict:
    """Expected all-to-all bytes and binomial overflow bound for uniform
    random addressing (the paper's workload)."""
    itemsize = jnp.dtype(spec.dtype).itemsize
    cap = capacity_for(spec, n_requests_per_shard, capacity_factor)
    s = spec.n_shards
    addr_bytes = s * cap * 4
    val_bytes = s * cap * spec.width * itemsize
    # per-queue overflow: Binomial(R, 1/S) > C, normal-approximation tail
    mean = n_requests_per_shard / s
    if cap >= n_requests_per_shard or s == 1:
        p_overflow = 0.0
    else:
        var = n_requests_per_shard * (1.0 / s) * (1.0 - 1.0 / s)
        z = (cap - mean) / math.sqrt(max(var, 1e-12))
        p_overflow = 0.5 * math.erfc(z / math.sqrt(2.0))
    return {
        "capacity": cap,
        "a2a_bytes_per_shard": 2 * (addr_bytes + val_bytes),  # out + back
        "p_queue_overflow": p_overflow,
    }

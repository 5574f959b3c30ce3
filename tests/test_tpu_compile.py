"""Ahead-of-time compiles of the serving path for a TPU v5e chip.

The chip is described, not attached: the TPU compiler (Mosaic for the
Pallas kernels, XLA for the whole step) refuses here what the chip would
refuse -- block shapes off the (8, 128) tiling, more VMEM than a kernel may
use, a program larger than HBM.  Interpret-mode tests cannot see any of
that.  Widths are qwen3-0.6b's (8 KV heads of 128, 2 query heads per KV
head) at the serving geometry ``chip_smoke.py`` runs: 8 slots, 2048
positions.

The topology is described inside a fixture only: loading the TPU library
while a module is imported would take its lock in every pytest worker.
"""
import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, HKV, G, D, MAX_LEN = 8, 8, 2, 128, 2048
V5E_HBM_BYTES = 16 * 1024 ** 3
#: the benchmark cell's serving geometry (``bench/configs/qwen3-0.6b.json``)
CELL_SLOTS, CELL_POOL_PAGES = 16, 128
ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        # a TPU executable written to the persistent cache cannot be read
        # back without the chip
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _tables(one_chip, page_slots):
    max_lpages = -(-MAX_LEN // page_slots)
    n_frames = B * max_lpages
    i32 = jnp.int32
    return (n_frames, _sds(one_chip, (B, max_lpages), i32),
            _sds(one_chip, (B,), i32), _sds(one_chip, (4,), i32))


@pytest.mark.parametrize("page_slots", [16, 256])
def test_paged_kv_write_compiles_for_v5e(one_chip, page_slots):
    from repro.kernels.paged_decode import kernel as K
    n_frames, bt, lengths, meta = _tables(one_chip, page_slots)
    pages = _sds(one_chip, (n_frames, HKV, page_slots, D), jnp.bfloat16)
    new = _sds(one_chip, (B, HKV, D), jnp.bfloat16)
    fr = _sds(one_chip, (n_frames,), jnp.int32)
    wm = _sds(one_chip, (B,), jnp.int32)
    compiled = jax.jit(K.paged_kv_write, donate_argnums=(2, 3)).lower(
        new, new, pages, pages, bt, lengths, fr, wm, meta).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("page_slots", [16, 256])
def test_paged_gather_attend_compiles_for_v5e(one_chip, page_slots):
    from repro.kernels.paged_decode import kernel as K
    n_frames, bt, lengths, meta = _tables(one_chip, page_slots)
    pages = _sds(one_chip, (n_frames, HKV, page_slots, D), jnp.bfloat16)
    q = _sds(one_chip, (B, HKV, G, D), jnp.bfloat16)
    compiled = jax.jit(K.paged_gather_attend).lower(
        q, pages, pages, bt, lengths, meta).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pooled_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """The whole sampled decode step of pooled qwen3-0.6b at full width
    and depth, with the fused kernels really lowered.  ``_on_tpu`` reads
    the default backend, which is the CPU here: left alone, ``auto`` would
    pick the composed oracle and any forced ``fused`` would lower in
    interpret mode, and the compile would prove nothing about the kernels."""
    from repro.configs import get_config
    from repro.kernels.paged_decode import ops as pd_ops
    from repro.models.model import Model
    from repro.serve.fused_decode import sampled_decode_step
    monkeypatch.setattr(pd_ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("qwen3-0.6b"), kv_layout="pooled",
                              kv_page_slots=256)
    assert pd_ops.resolve_impl(cfg.paged_kernel, cfg.n_heads,
                               cfg.n_heads // cfg.n_kv_heads) == "fused"
    model = Model(cfg)

    def on_chip(tree):
        return jax.tree.map(
            lambda s: _sds(one_chip, s.shape, s.dtype), tree)

    params = on_chip(model.shapes())
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(B, MAX_LEN)))
    assert cache["b0"]["k_pages"].shape == (28, 64, HKV, 256, D)
    compiled = sampled_decode_step.lower(
        model, params, _sds(one_chip, (B, 1), jnp.int32), cache,
        _sds(one_chip, (B,), jnp.int32),
        _sds(one_chip, (B,), jnp.bool_)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    # both kernels carry their stable names into the compiled step
    assert "paged_gather_attend" in hlo and "paged_kv_write" in hlo
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, mem


#: a computation's first line, ``[ENTRY ]%name (params) -> type {``
_COMPUTATION = re.compile(r"^(ENTRY )?%(?P<name>[\w.\-]+) ")
#: an instruction with one array output: ``%x = bf16[2,3]{...} opcode(``
_ARRAY_OP = re.compile(r"= (?P<dtype>\w+)\[(?P<dims>[\d,]+)\]\S* "
                       r"(?P<opcode>[a-z][\w\-]*)\(")


def _page_moves(hlo: str, page: tuple, rows: int):
    """(computation, opcode, dims) of every copy, dynamic-slice and
    dynamic-update-slice whose output is made of pages of shape ``page``
    and holds at least ``rows`` of them (one layer's slab or more)."""
    moves, comp = [], None
    for line in hlo.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = "ENTRY" if head.group(1) else head["name"]
            continue
        op = _ARRAY_OP.search(line)
        if not op or op["opcode"] not in (
                "copy", "dynamic-slice", "dynamic-update-slice"):
            continue
        dims = tuple(int(d) for d in op["dims"].split(","))
        lead = dims[:-len(page)]
        if dims[-len(page):] == page and lead and \
                int(jnp.prod(jnp.array(lead))) >= rows:
            moves.append((comp, op["opcode"], dims))
    return moves


@pytest.mark.parametrize("program", ["sampled_decode_step",
                                     "fused_decode_run"])
def test_pooled_decode_updates_pool_in_place_for_v5e(one_chip, monkeypatch,
                                                     program):
    """The step programs of pooled qwen3-0.6b at the benchmark cell's
    geometry (16 slots, 2048 positions, 128 frames of 256) keep the
    stacked KV pool in one buffer: the layer scan carries it and each
    layer's kernels alias it at that layer's rows.  No layer's slab is
    sliced out or updated back, no pool is copied inside a loop, and the
    only pool copy left is one per pool array at entry (the argument is
    not donated).  The write kernel still returns two 4-D page arrays of
    one shape, which the benchmark's ``kv_write_roofline`` reads."""
    from repro.configs import get_config
    from repro.kernels.paged_decode import ops as pd_ops
    from repro.models.model import Model
    from repro.serve import fused_decode
    sys.path.insert(0, ROOT)
    from bench.metrics.kv_write_roofline import KERNEL
    from bench.trace import op_label
    monkeypatch.setattr(pd_ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("qwen3-0.6b"), kv_layout="pooled",
                              kv_page_slots=256,
                              kv_pool_pages=CELL_POOL_PAGES,
                              paged_kernel="fused")
    model = Model(cfg)
    params = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype),
                          model.shapes())
    cache = jax.tree.map(
        lambda s: _sds(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda: model.init_cache(CELL_SLOTS, MAX_LEN)))
    pool = cache["b0"]["k_pages"].shape
    assert pool == (28, CELL_POOL_PAGES, HKV, 256, D)
    i32, b = jnp.int32, CELL_SLOTS
    mask = _sds(one_chip, (b,), jnp.bool_)
    args = (params, _sds(one_chip, (b, 1), i32), cache,
            _sds(one_chip, (b,), i32), mask)
    if program == "sampled_decode_step":
        lowered = fused_decode.sampled_decode_step.lower(model, *args)
    else:
        staged = _sds(one_chip, (b, 8), i32)
        lowered = fused_decode.fused_decode_run.lower(
            model, 8, *args, _sds(one_chip, (), i32),
            _sds(one_chip, (), i32), staged, staged)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    moves = _page_moves(hlo, pool[2:], CELL_POOL_PAGES)
    assert [m for m in moves if m[1] != "copy"] == [], moves
    assert [m for m in moves if m[0] != "ENTRY"] == [], moves
    assert len(moves) <= 2, moves          # one per pool array, K and V
    assert all(m[2] == pool for m in moves), moves
    if program == "fused_decode_run":
        # 4.13 GB while the scan restacked the pool
        assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
    writes = [op_label(line.strip()) for line in hlo.splitlines()
              if re.match(r"\s*%paged_kv_write[.\d]* = \(", line)]
    assert writes and all(re.search(KERNEL, w) for w in writes), writes

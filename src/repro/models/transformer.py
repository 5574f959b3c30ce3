"""Decoder-only transformer stack covering dense / MoE / hybrid / SSM families.

Layers are grouped into a repeating *period* (cfg.layer_period): e.g. jamba's
pattern is 8 layers (1 attention + 7 mamba, MoE on odd layers).  Parameters
and caches are stacked over periods ([n_periods, ...] leading axis) and the
stack is applied with ``lax.scan`` so HLO size is independent of depth --
required to compile 80-layer models for 512 devices in reasonable time.

Cache layouts (cfg.kv_layout):
  "batch" -- k/v: [B, Hkv, S_max, hd] per attention layer (batch-sharded).
  "paged" -- k/v pages: [n_pages, Hkv, page_slots, hd] per attention layer,
             pages cyclically owned by the KV mesh axes (the emulated-memory
             scheme, `repro.core.emem`); decode merges per-shard partials.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.config import ModelConfig
from repro.parallel.sharding import maybe_constrain

Params = dict


# ---------------------------------------------------------------------------
# Parameter tables
# ---------------------------------------------------------------------------
def block_defs(cfg: ModelConfig, i: int) -> dict:
    d: dict = {}
    if cfg.layer_kind(i) == "attn":
        d["ln_mix"] = L.norm_defs(cfg)
        d["attn"] = L.attention_defs(cfg)
    else:
        d["ln_mix"] = L.norm_defs(cfg)
        d["mamba"] = S.ssm_defs(cfg)
    if cfg.layer_has_mlp(i):
        d["ln_mlp"] = L.norm_defs(cfg)
        if cfg.layer_has_moe(i):
            d["moe"] = M.moe_defs(cfg)
        else:
            d["mlp"] = L.mlp_defs(cfg)
    return d


def decoder_defs(cfg: ModelConfig) -> dict:
    defs: dict = {"embed": L.embedding_defs(cfg), "ln_f": L.norm_defs(cfg)}
    for i in range(cfg.layer_period):
        defs[f"b{i}"] = L.stack_defs(block_defs(cfg, i), cfg.n_periods)
    return defs


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------
def _mixer(cfg: ModelConfig, i: int, p: Params, x: jax.Array,
           positions: jax.Array) -> jax.Array:
    h = L.rms_norm(x, p["ln_mix"]["w"], cfg.rms_eps)
    if cfg.layer_kind(i) == "attn":
        return x + L.attention_block(cfg, p["attn"], h, positions)
    return x + S.ssm_block(cfg, p["mamba"], h)


def _ffn(cfg: ModelConfig, i: int, p: Params, x: jax.Array) -> jax.Array:
    if not cfg.layer_has_mlp(i):
        return x
    h = L.rms_norm(x, p["ln_mlp"]["w"], cfg.rms_eps)
    if cfg.layer_has_moe(i):
        return x + M.moe_block(cfg, p["moe"], h)
    return x + L.mlp_block(p["mlp"], h, constrain=cfg.constrain_inner)


def block_apply(cfg: ModelConfig, i: int, p: Params, x: jax.Array,
                positions: jax.Array) -> jax.Array:
    return _ffn(cfg, i, p, _mixer(cfg, i, p, x, positions))


# ---------------------------------------------------------------------------
# Full stack (train / no-cache forward)
# ---------------------------------------------------------------------------
def stack_apply(cfg: ModelConfig, params: Params, x: jax.Array,
                positions: jax.Array) -> jax.Array:
    def period_step(h, period_params):
        for i in range(cfg.layer_period):
            h = block_apply(cfg, i, period_params[f"b{i}"], h, positions)
        h = maybe_constrain(h, ("dp", None, None))
        if cfg.block_barrier:
            h = jax.lax.optimization_barrier(h)
        return h, None

    if cfg.remat == "dots":
        # keep matmul outputs, recompute elementwise: trades HBM for FLOPs
        period_step = jax.checkpoint(
            period_step, prevent_cse=False,
            policy=jax.checkpoint_policies.checkpoint_dots)
    elif cfg.remat in ("block", "full"):
        period_step = jax.checkpoint(period_step,
                                     prevent_cse=False)  # type: ignore[assignment]
    stacked = {k: v for k, v in params.items() if k.startswith("b")}
    if cfg.unroll_layers:
        for j in range(cfg.n_periods):
            x, _ = period_step(x, jax.tree.map(lambda v: v[j], stacked))
        return x
    x, _ = jax.lax.scan(period_step, x, stacked)
    return x


def forward(cfg: ModelConfig, params: Params, batch: dict) -> jax.Array:
    """Embed -> stack -> final norm.  Returns hidden states [B, S, d]."""
    if "embeds" in batch:
        x = batch["embeds"].astype(cfg.compute_dtype)
    else:
        x = L.embed_tokens(cfg, params["embed"], batch["tokens"])
        x = x.astype(cfg.compute_dtype)
    b, s = x.shape[:2]
    x = maybe_constrain(x, ("dp", None, None))
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = stack_apply(cfg, params, x, positions)
    return L.rms_norm(x, params["ln_f"]["w"], cfg.rms_eps)


def lm_loss(cfg: ModelConfig, params: Params, batch: dict) -> jax.Array:
    """Masked next-token cross entropy (labels already shifted by the data
    pipeline).  Softmax in float32 with padded-vocab masking."""
    x = forward(cfg, params, batch)
    logits = L.unembed(cfg, params["embed"], x).astype(jnp.float32)
    logits = maybe_constrain(logits, ("dp", None, "tp"))
    if cfg.vocab_padded != cfg.vocab_size:
        pad_mask = jnp.arange(cfg.vocab_padded) >= cfg.vocab_size
        logits = jnp.where(pad_mask, L.NEG_INF, logits)
    labels = batch["labels"]
    mask = batch.get("mask")
    logz = jax.nn.logsumexp(logits, axis=-1)
    # one-hot contraction, NOT take_along_axis: gathering along the
    # model-sharded vocab axis would force XLA to all-gather the full
    # [tokens, vocab] logits (a ~40 GB collective at train_4k scale); the
    # one-hot product fuses into a sharded reduction instead.
    onehot = (labels[..., None] ==
              jnp.arange(cfg.vocab_padded)[None, None, :])
    gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    nll = logz - gold
    if mask is not None:
        nll = nll * mask
        denom = jnp.maximum(mask.sum(), 1.0)
    else:
        denom = float(nll.size)
    return nll.sum() / denom


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch_size: int, max_len: int,
               dtype=None) -> dict:
    """Zero cache pytree, stacked over periods per block position."""
    kv_dtype = dtype or cfg.kv_dtype or cfg.compute_dtype  # attention K/V only
    dtype = dtype or cfg.compute_dtype                     # SSM states
    np_, hkv, hd = cfg.n_periods, cfg.n_kv_heads, cfg.hd
    cache: dict = {}
    for i in range(cfg.layer_period):
        if cfg.layer_kind(i) == "attn":
            if cfg.kv_layout in ("paged", "pooled"):
                slots = cfg.kv_page_slots
                max_pages = -(-max_len // slots)
                if cfg.kv_layout == "pooled":
                    n_pages = cfg.kv_pool_pages or batch_size * max_pages
                else:
                    n_pages = batch_size * max_pages
                entry = {
                    "k_pages": jnp.zeros((np_, n_pages, hkv, slots, hd),
                                         kv_dtype),
                    "v_pages": jnp.zeros((np_, n_pages, hkv, slots, hd),
                                         kv_dtype),
                }
            else:
                entry = {
                    "k": jnp.zeros((np_, batch_size, hkv, max_len, hd),
                                   kv_dtype),
                    "v": jnp.zeros((np_, batch_size, hkv, max_len, hd),
                                   kv_dtype),
                }
        else:
            conv_ch = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
            entry = {
                "conv": jnp.zeros((np_, batch_size, cfg.ssm_conv - 1, conv_ch),
                                  dtype),
                "ssd": jnp.zeros((np_, batch_size, cfg.ssm_heads,
                                  cfg.ssm_state, cfg.ssm_head_dim),
                                 jnp.float32),
            }
        cache[f"b{i}"] = entry
    if cfg.kv_layout in ("paged", "pooled") and any(
            cfg.layer_kind(i) == "attn" for i in range(cfg.layer_period)):
        # BlockManager translation state, shared by every attention layer and
        # maintained host-side by the serving engine (repro.serve.engine).
        # "paged" starts from the identity tables (slot b owns frames
        # b*max_pages..(b+1)*max_pages-1) so direct decode callers get the
        # fixed layout without any host bookkeeping; "pooled" starts empty.
        slots = cfg.kv_page_slots
        max_pages = -(-max_len // slots)
        if cfg.kv_layout == "pooled":
            n_frames = cfg.kv_pool_pages or batch_size * max_pages
            block_table = jnp.full((batch_size, max_pages), -1, jnp.int32)
            frame_lpage = jnp.zeros((n_frames,), jnp.int32)
        else:
            n_frames = batch_size * max_pages
            block_table = jnp.arange(n_frames, dtype=jnp.int32).reshape(
                batch_size, max_pages)
            frame_lpage = jnp.tile(jnp.arange(max_pages, dtype=jnp.int32),
                                   batch_size)
        cache["vm"] = {
            "block_table": block_table,
            "frame_lpage": frame_lpage,
            "frame_ro": jnp.zeros((n_frames,), bool),
        }
    return cache


def cache_spec(cfg: ModelConfig, batch_size: int, max_len: int,
               dtype=None) -> dict:
    """ShapeDtypeStruct pytree matching init_cache (for dry runs)."""
    return jax.eval_shape(
        lambda: init_cache(cfg, batch_size, max_len, dtype))


# ---------------------------------------------------------------------------
# Prefill (batch KV layout)
# ---------------------------------------------------------------------------
def block_prefill(cfg: ModelConfig, i: int, p: Params, x: jax.Array,
                  positions: jax.Array, max_len: int):
    """Like block_apply but also returns this block's cache entry."""
    h = L.rms_norm(x, p["ln_mix"]["w"], cfg.rms_eps)
    if cfg.layer_kind(i) == "attn":
        b, s, _ = x.shape
        q, k, v = L._project_qkv(cfg, p["attn"], h, positions)
        out = L.full_attention(cfg, q, k, v, causal=True, window=cfg.window)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.hd)
        x = x + out @ p["attn"]["wo"]
        pad = max_len - s
        kc = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))).astype(cfg.compute_dtype)
        vc = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))).astype(cfg.compute_dtype)
        entry = {"k": kc, "v": vc}
    else:
        out, (conv, ssd) = S.ssm_block(cfg, p["mamba"], h, return_state=True)
        x = x + out
        entry = {"conv": conv.astype(cfg.compute_dtype), "ssd": ssd}
    return _ffn(cfg, i, p, x), entry


def prefill(cfg: ModelConfig, params: Params, batch: dict, max_len: int):
    """Run the prompt, return (last-position logits [B, vocab], cache).

    Uses the batch KV layout (prefill writes are local by construction)."""
    if "embeds" in batch:
        x = batch["embeds"].astype(cfg.compute_dtype)
    else:
        x = L.embed_tokens(cfg, params["embed"], batch["tokens"])
        x = x.astype(cfg.compute_dtype)
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def period_step(h, period_params):
        entries = {}
        for i in range(cfg.layer_period):
            h, entries[f"b{i}"] = block_prefill(
                cfg, i, period_params[f"b{i}"], h, positions, max_len)
        return maybe_constrain(h, ("dp", None, None)), entries

    if cfg.remat in ("block", "full"):
        period_step = jax.checkpoint(period_step, prevent_cse=False)
    stacked = {k: v for k, v in params.items() if k.startswith("b")}
    if cfg.unroll_layers:
        entries_list = []
        for j in range(cfg.n_periods):
            x, e = period_step(x, jax.tree.map(lambda v: v[j], stacked))
            entries_list.append(e)
        cache = jax.tree.map(lambda *xs: jnp.stack(xs), *entries_list)
    else:
        x, cache = jax.lax.scan(period_step, x, stacked)
    x = L.rms_norm(x, params["ln_f"]["w"], cfg.rms_eps)
    logits = L.unembed(cfg, params["embed"], x[:, -1]).astype(jnp.float32)
    if cfg.vocab_padded != cfg.vocab_size:
        logits = jnp.where(jnp.arange(cfg.vocab_padded) >= cfg.vocab_size,
                           L.NEG_INF, logits)
    return logits, cache


# ---------------------------------------------------------------------------
# Decode (one token; batch or paged KV layout)
# ---------------------------------------------------------------------------
def _mask_entry(new: dict, old: dict, write_mask: jax.Array) -> dict:
    """Keep ``old`` state for batch elements masked off from writing.
    Every leaf here is batch-leading ([B, ...])."""
    return {k: jnp.where(write_mask.reshape((-1,) + (1,) * (v.ndim - 1)),
                         v, old[k])
            for k, v in new.items()}


def block_decode(cfg: ModelConfig, i: int, p: Params, x: jax.Array,
                 entry: dict, lengths: jax.Array, vm: dict | None = None,
                 write_mask=None, layer=0):
    """``layer`` is the period index: a paged entry holds every period's
    pages and is written and read in place at that layer's rows."""
    h = L.rms_norm(x, p["ln_mix"]["w"], cfg.rms_eps)
    if cfg.layer_kind(i) == "attn":
        if cfg.kv_layout in ("paged", "pooled"):
            from repro.parallel.paged_attention import paged_decode_block
            out, entry = paged_decode_block(cfg, p["attn"], h, entry, lengths,
                                            vm, write_mask, layer)
        else:
            old = entry
            out, k, v = L.decode_attention_block(
                cfg, p["attn"], h, entry["k"], entry["v"], lengths)
            entry = {"k": k, "v": v}
            if write_mask is not None:
                entry = _mask_entry(entry, old, write_mask)
        x = x + out
    else:
        old = entry
        out, conv, ssd = S.ssm_decode_step(cfg, p["mamba"], h,
                                           entry["conv"], entry["ssd"])
        x = x + out
        entry = {"conv": conv, "ssd": ssd}
        if write_mask is not None:
            entry = _mask_entry(entry, old, write_mask)
    return _ffn(cfg, i, p, x), entry


def decode_step(cfg: ModelConfig, params: Params, tokens: jax.Array,
                cache: dict, lengths: jax.Array, write_mask=None):
    """One decode step for every sequence.

    tokens: [B, 1] int32 (the tokens just sampled); lengths: [B] valid length
    INCLUDING these tokens.  Returns (logits [B, vocab], new cache).

    write_mask: optional [B] bool -- sequences masked off keep their cache
    (KV and SSM state) unchanged.  The serving engine uses it so that
    prefilling one slot through the shared decode batch cannot clobber the
    other slots' latest KV position or recurrent state.
    """
    x = L.embed_tokens(cfg, params["embed"], tokens).astype(cfg.compute_dtype)
    # pooled layout: the frame-pool tables ride outside the period scan
    # (engine-managed, identical for every layer, no leading period axis)
    vm = cache.get("vm")
    blocks = {k: v for k, v in cache.items() if k.startswith("b")}
    # paged entries ride the carry whole: each layer's attention writes and
    # reads the stacked pool in place at its own rows, so no layer's pages
    # are sliced out and stacked back.  Per-slot state (batch-layout k/v,
    # SSM conv/ssd) is scanned over as before.
    pools = {k: e for k, e in blocks.items() if "k_pages" in e}
    scanned = {k: e for k, e in blocks.items() if k not in pools}

    def period_step(carry, scanees):
        h, pools = carry
        layer, period_params, entries = scanees
        pools, new_entries = dict(pools), {}
        for i in range(cfg.layer_period):
            key = f"b{i}"
            src, dst = ((pools, pools) if key in pools
                        else (entries, new_entries))
            h, dst[key] = block_decode(cfg, i, period_params[key], h,
                                       src[key], lengths, vm, write_mask,
                                       layer)
        return (maybe_constrain(h, ("dp", None, None)), pools), new_entries

    stacked = {k: v for k, v in params.items() if k.startswith("b")}
    if cfg.unroll_layers:
        entries_list = []
        for j in range(cfg.n_periods):
            (x, pools), e = period_step(
                (x, pools), (j, jax.tree.map(lambda v: v[j], stacked),
                             jax.tree.map(lambda v: v[j], scanned)))
            entries_list.append(e)
        cache = jax.tree.map(lambda *xs: jnp.stack(xs), *entries_list)
    else:
        (x, pools), cache = jax.lax.scan(
            period_step, (x, pools),
            (jnp.arange(cfg.n_periods), stacked, scanned))
    cache = {**cache, **pools}
    if vm is not None:
        cache = {**cache, "vm": vm}
    x = L.rms_norm(x, params["ln_f"]["w"], cfg.rms_eps)
    logits = L.unembed(cfg, params["embed"], x[:, -1]).astype(jnp.float32)
    if cfg.vocab_padded != cfg.vocab_size:
        logits = jnp.where(jnp.arange(cfg.vocab_padded) >= cfg.vocab_size,
                           L.NEG_INF, logits)
    return logits, cache

"""Qwen3's dense decoder (``model_type`` ``qwen3``): its sizes, weights,
program tree, plain reference and work counts.

The architecture: a decoder of RMS-normed pre-norm blocks, grouped-query
causal attention with rotary positions (split halves, ``rope_theta``) and
an RMS norm over each query and key head before the rotation; a SwiGLU
MLP; a final RMS norm and the (tied) unembedding.  The same code counts a
dense sibling without the head norms (``qk_norm`` false for any other
``model_type``).

Every layer holds paged K/V, and a decode step reads every weight once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import _mm, _rms, _rope
from bench.work import ELEM_BYTES


def dims(c: dict) -> dict:
    """The sizes the counts use."""
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // h
    return {"L": c["num_hidden_layers"], "d": c["hidden_size"], "H": h,
            "Hkv": hkv, "hd": hd, "ff": c["intermediate_size"],
            "V": c["vocab_size"], "tied": bool(c["tie_word_embeddings"]),
            "qk_norm": c.get("model_type") == "qwen3"}


# -- weights and the program's tree -----------------------------------------

def shapes(c: dict) -> dict:
    """name -> (shape, init, std) for configuration ``c``: every matrix
    and the embedding normal with the published ``initializer_range`` as
    its std, every norm weight 1 (the published model's initializer)."""
    k = dims(c)
    L, d, H, Hkv, hd, ff = k["L"], k["d"], k["H"], k["Hkv"], k["hd"], k["ff"]
    std = c["initializer_range"]
    s = {
        "embed": ((k["V"], d), "normal", std),
        "final_norm": ((d,), "ones", None),
        "attn_norm": ((L, d), "ones", None),
        "mlp_norm": ((L, d), "ones", None),
        "wq": ((L, d, H * hd), "normal", std),
        "wk": ((L, d, Hkv * hd), "normal", std),
        "wv": ((L, d, Hkv * hd), "normal", std),
        "wo": ((L, H * hd, d), "normal", std),
        "w_gate": ((L, d, ff), "normal", std),
        "w_up": ((L, d, ff), "normal", std),
        "w_down": ((L, ff, d), "normal", std),
    }
    if k["qk_norm"]:
        s["q_norm"] = ((L, hd), "ones", None)
        s["k_norm"] = ((L, hd), "ones", None)
    if not k["tied"]:
        s["head"] = ((d, k["V"]), "normal", std)
    return s


def model_config(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``: the
    registry entry of ``c["arch"]`` at the sizes the file gives."""
    import dataclasses as dc

    from repro.configs import get_config
    k, sv = dims(c), c["serving"]
    base = get_config(c["arch"])
    if base.tie_embeddings != k["tied"] or base.qk_norm != k["qk_norm"]:
        raise ValueError(f"{c['arch']}: the registry's tie/qk_norm differ "
                         f"from the configuration file")
    dtype = c["torch_dtype"]
    return dc.replace(
        base, n_layers=k["L"], d_model=k["d"], n_heads=k["H"],
        n_kv_heads=k["Hkv"], head_dim=k["hd"], d_ff=k["ff"],
        vocab_size=k["V"], rope_theta=float(c["rope_theta"]),
        rms_eps=float(c["rms_norm_eps"]), param_dtype=dtype,
        compute_dtype=dtype, kv_layout=sv["kv_layout"],
        paged_kernel=sv["paged_kernel"], kv_page_slots=sv["page_slots"])


def program_params(w: dict, cfg) -> dict:
    """The benchmark's weights in the program's parameter tree (the
    embedding padded with zero rows to the program's padded vocabulary)."""
    emb = w["embed"]
    pad = cfg.vocab_padded - emb.shape[0]
    if pad:
        emb = jnp.pad(emb, ((0, pad), (0, 0)))
    attn = {n: w[n] for n in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
            if n in w}
    p = {"embed": {"tok": emb}, "ln_f": {"w": w["final_norm"]},
         "b0": {"ln_mix": {"w": w["attn_norm"]}, "attn": attn,
                "ln_mlp": {"w": w["mlp_norm"]},
                "mlp": {n: w[n] for n in ("w_gate", "w_up", "w_down")}}}
    if "head" in w:
        p["embed"]["head"] = jnp.pad(w["head"], ((0, 0), (0, pad)))
    return p


# -- the plain reference --------------------------------------------------

def forward_key(c: dict) -> tuple:
    """The static sizes ``forward`` is compiled for."""
    k = dims(c)
    return (k["L"], k["d"], k["H"], k["Hkv"], k["hd"], k["qk_norm"],
            k["tied"], float(c["rms_norm_eps"]), float(c["rope_theta"]))


@functools.partial(jax.jit, static_argnums=(0, 3))
def forward(k: tuple, w: dict, tokens, mode: str):
    """Logits [S, V] of one token sequence (float32)."""
    L, d, H, Hkv, hd, qk_norm, tied, eps, theta = k
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[:, None] >= pos[None, :]
    g = H // Hkv
    x = w["embed"][tokens]

    def layer(x, p):
        h = _rms(x, p["attn_norm"], eps)
        q = _mm(h, p["wq"], mode).reshape(s, H, hd).transpose(1, 0, 2)
        kk = _mm(h, p["wk"], mode).reshape(s, Hkv, hd).transpose(1, 0, 2)
        v = _mm(h, p["wv"], mode).reshape(s, Hkv, hd).transpose(1, 0, 2)
        if qk_norm:
            q = _rms(q, p["q_norm"], eps)
            kk = _rms(kk, p["k_norm"], eps)
        q, kk = _rope(q, pos, theta), _rope(kk, pos, theta)
        kk, v = jnp.repeat(kk, g, axis=0), jnp.repeat(v, g, axis=0)
        sc = _mm(q, kk.transpose(0, 2, 1), mode) * hd ** -0.5
        sc = jnp.where(causal, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = _mm(pr, v, mode).transpose(1, 0, 2).reshape(s, H * hd)
        x = x + _mm(o, p["wo"], mode)
        h = _rms(x, p["mlp_norm"], eps)
        m = jax.nn.silu(_mm(h, p["w_gate"], mode)) * _mm(h, p["w_up"], mode)
        return x + _mm(m, p["w_down"], mode), None

    per_layer = {n: a for n, a in w.items()
                 if n not in ("embed", "final_norm", "head")}
    x, _ = jax.lax.scan(layer, x, per_layer)
    x = _rms(x, w["final_norm"], eps)
    head = w["embed"].T if tied else w["head"]
    return _mm(x, head, mode)


# -- work counts ------------------------------------------------------------

def paged_layers(c: dict) -> int:
    """Layers that hold paged K/V: all of them."""
    return c["num_hidden_layers"]


def layer_matmul_params(c: dict) -> int:
    """Weights of one layer's matrix multiplications."""
    k = dims(c)
    attn = k["d"] * (k["H"] + 2 * k["Hkv"]) * k["hd"] + k["H"] * k["hd"] * k["d"]
    return attn + 3 * k["d"] * k["ff"]


def param_count(c: dict) -> int:
    """Every parameter: embedding (once when tied), layers, norms."""
    k = dims(c)
    per_layer = layer_matmul_params(c) + 2 * k["d"]
    if k["qk_norm"]:
        per_layer += 2 * k["hd"]
    emb = k["V"] * k["d"] * (1 if k["tied"] else 2)
    return emb + k["L"] * per_layer + k["d"]


def weight_bytes(c: dict) -> int:
    """Bytes of the weights one decode step reads: all of them, the tied
    table once (the unembedding reads it whole)."""
    return param_count(c) * ELEM_BYTES


def kv_row_bytes(c: dict) -> int:
    """K and V of one position in every layer."""
    k = dims(c)
    return k["L"] * 2 * k["Hkv"] * k["hd"] * ELEM_BYTES


def token_flops(c: dict, ctx: int) -> int:
    """FLOPs to decode one token whose context, itself included, is
    ``ctx`` positions: the matmuls, attention over the context, the
    unembedding."""
    k = dims(c)
    attn = 4 * k["H"] * k["hd"] * ctx
    return 2 * k["L"] * layer_matmul_params(c) + k["L"] * attn \
        + 2 * k["d"] * k["V"]


def step_flops(c: dict, ctxs) -> int:
    """FLOPs of one decode dispatch iteration that decodes one token for
    each context length in ``ctxs``."""
    return sum(token_flops(c, int(n)) for n in ctxs)


def step_bytes(c: dict, ctxs) -> int:
    """Necessary HBM bytes of one decode iteration over ``ctxs``: the
    weights once, the K/V of each context read, one new K/V row written
    per sequence."""
    ctxs = [int(n) for n in ctxs]
    if not ctxs:
        return 0
    return weight_bytes(c) + kv_row_bytes(c) * (sum(ctxs) + len(ctxs))


def gather_attend_work(c: dict, ctxs) -> tuple[int, int]:
    """(FLOPs, bytes) of one layer's ``paged_gather_attend`` over the
    sequences of ``ctxs``: QK and PV over each context, the K/V of each
    context read, the queries read and the f32 partials (acc, m, l)
    written."""
    k = dims(c)
    flops = sum(4 * k["H"] * k["hd"] * int(n) for n in ctxs)
    kv = sum(2 * k["Hkv"] * k["hd"] * int(n) for n in ctxs) * ELEM_BYTES
    io = len(ctxs) * k["H"] * (k["hd"] * ELEM_BYTES + (k["hd"] + 2) * 4)
    return flops, kv + io


def kv_write_work(c: dict, n_seqs: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one layer's ``paged_kv_write``: one K row and one
    V row per sequence written."""
    k = dims(c)
    return 0, n_seqs * 2 * k["Hkv"] * k["hd"] * ELEM_BYTES

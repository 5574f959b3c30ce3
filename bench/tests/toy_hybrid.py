"""A second architecture module, for the tests only (``model_type``
``toy_hybrid``): a hybrid decoder whose layers alternate a Mamba-2 mixer
and grouped-query attention, as Jamba's and Granite 4.0-H's do, each
followed by a SwiGLU MLP; no query/key norm; an untied head.  Only the
attention layers hold paged K/V (``paged_layers`` below the depth); each
Mamba-2 layer keeps a per-slot recurrent state instead.

It fills in the interface of ``bench/work.py`` as a module of
``bench/archs/`` would.  The program runs it as the registry's hybrid
family at the file's sizes, with a dense MLP on every layer.

Configuration keys beyond the dense decoder's (Jamba's names):
``attn_layer_period``, ``attn_layer_offset`` (attention at the layers whose
index modulo the period is the offset), ``mamba_d_state``,
``mamba_d_head``, ``mamba_expand``, ``mamba_d_conv``, ``mamba_n_groups``.

The Mamba-2 mixer (arXiv:2405.21060), per position t: one input
projection gives [z | xBC | dt]; a depthwise causal convolution of width
``d_conv`` over xBC, then SiLU, gives x (heads of ``d_head``), B and C
(``n_groups`` of ``d_state``); dt = softplus(dt + dt_bias), A = -exp(a_log);
state = state * exp(dt A) + dt B x^T; y = C state + D x; out =
RMSNorm(y * SiLU(z)) W_out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import HIGHEST, _mm, _rms, _rope
from bench.work import ELEM_BYTES


def dims(c: dict) -> dict:
    L, period = c["num_hidden_layers"], c["attn_layer_period"]
    d, expand = c["hidden_size"], c["mamba_expand"]
    di = expand * d
    k = {"L": L, "period": period, "d": d, "H": c["num_attention_heads"],
         "Hkv": c["num_key_value_heads"], "hd": c["head_dim"],
         "ff": c["intermediate_size"], "V": c["vocab_size"], "di": di,
         "P": c["mamba_d_head"], "N": c["mamba_d_state"],
         "G": c["mamba_n_groups"], "K": c["mamba_d_conv"]}
    k["heads"] = di // k["P"]
    k["conv_ch"] = di + 2 * k["G"] * k["N"]
    k["kinds"] = tuple("attn" if i == c["attn_layer_offset"] else "mamba"
                       for i in range(period))
    return k


# -- weights and the program's tree -----------------------------------------

def shapes(c: dict) -> dict:
    """Weights named by their path in the program's tree, stacked over the
    periods of the layer pattern; the embedding and the head unpadded."""
    k = dims(c)
    n, d, std = k["L"] // k["period"], k["d"], c["initializer_range"]
    s = {"embed": ((k["V"], d), "normal", std),
         "head": ((d, k["V"]), "normal", std),
         "final_norm": ((d,), "ones", None)}
    for i, kind in enumerate(k["kinds"]):
        b = f"b{i}"
        s[f"{b}/ln_mix/w"] = ((n, d), "ones", None)
        s[f"{b}/ln_mlp/w"] = ((n, d), "ones", None)
        s[f"{b}/mlp/w_gate"] = ((n, d, k["ff"]), "normal", std)
        s[f"{b}/mlp/w_up"] = ((n, d, k["ff"]), "normal", std)
        s[f"{b}/mlp/w_down"] = ((n, k["ff"], d), "normal", std)
        if kind == "attn":
            q, kv = k["H"] * k["hd"], k["Hkv"] * k["hd"]
            s[f"{b}/attn/wq"] = ((n, d, q), "normal", std)
            s[f"{b}/attn/wk"] = ((n, d, kv), "normal", std)
            s[f"{b}/attn/wv"] = ((n, d, kv), "normal", std)
            s[f"{b}/attn/wo"] = ((n, q, d), "normal", std)
        else:
            m = f"{b}/mamba"
            s[f"{m}/w_in"] = ((n, d, k["di"] + k["conv_ch"] + k["heads"]),
                              "normal", std)
            s[f"{m}/conv_w"] = ((n, k["K"], k["conv_ch"]), "normal", std)
            s[f"{m}/conv_b"] = ((n, k["conv_ch"]), "normal", std)
            s[f"{m}/a_log"] = ((n, k["heads"]), "normal", std)
            s[f"{m}/dt_bias"] = ((n, k["heads"]), "normal", std)
            s[f"{m}/d_skip"] = ((n, k["heads"]), "ones", None)
            s[f"{m}/norm_w"] = ((n, k["di"]), "ones", None)
            s[f"{m}/w_out"] = ((n, k["di"], d), "normal", std)
    return s


def model_config(c: dict):
    """The registry's hybrid entry ``c["arch"]`` at the file's sizes, with
    a dense MLP on every layer."""
    import dataclasses as dc

    from repro.configs import get_config
    k, sv, dtype = dims(c), c["serving"], c["torch_dtype"]
    base = get_config(c["arch"])
    if base.family != "hybrid":
        raise ValueError(f"{c['arch']} is not a hybrid in the registry")
    return dc.replace(
        base, n_layers=k["L"], d_model=k["d"], n_heads=k["H"],
        n_kv_heads=k["Hkv"], head_dim=k["hd"], d_ff=k["ff"],
        vocab_size=k["V"], rope_theta=float(c["rope_theta"]),
        rms_eps=float(c["rms_norm_eps"]), qk_norm=False,
        tie_embeddings=False, n_experts=0, n_experts_active=0, moe_period=1,
        moe_offset=0, attn_period=k["period"],
        attn_offset=c["attn_layer_offset"], ssm_state=k["N"],
        ssm_head_dim=k["P"], ssm_groups=k["G"], ssm_conv=k["K"],
        ssm_expand=c["mamba_expand"], param_dtype=dtype, compute_dtype=dtype,
        kv_layout=sv["kv_layout"], paged_kernel=sv["paged_kernel"],
        kv_page_slots=sv["page_slots"])


def program_params(w: dict, cfg) -> dict:
    pad = cfg.vocab_padded - w["embed"].shape[0]
    p = {"embed": {"tok": jnp.pad(w["embed"], ((0, pad), (0, 0))),
                   "head": jnp.pad(w["head"], ((0, 0), (0, pad)))},
         "ln_f": {"w": w["final_norm"]}}
    for name, a in w.items():
        if "/" in name:
            *path, leaf = name.split("/")
            node = p
            for part in path:
                node = node.setdefault(part, {})
            node[leaf] = a
    return p


# -- the plain reference --------------------------------------------------

def forward_key(c: dict) -> tuple:
    k = dims(c)
    return (k["kinds"], k["H"], k["Hkv"], k["hd"], k["di"], k["heads"],
            k["P"], k["N"], k["G"], k["K"], float(c["rms_norm_eps"]),
            float(c["rope_theta"]))


def _attention(h, p, b, pos, k, mode):
    _, H, Hkv, hd = k[:4]
    s = h.shape[0]
    q = _mm(h, p[f"{b}/attn/wq"], mode).reshape(s, H, hd).transpose(1, 0, 2)
    kk = _mm(h, p[f"{b}/attn/wk"], mode).reshape(s, Hkv, hd).transpose(1, 0, 2)
    v = _mm(h, p[f"{b}/attn/wv"], mode).reshape(s, Hkv, hd).transpose(1, 0, 2)
    q, kk = _rope(q, pos, k[-1]), _rope(kk, pos, k[-1])
    kk, v = jnp.repeat(kk, H // Hkv, axis=0), jnp.repeat(v, H // Hkv, axis=0)
    sc = _mm(q, kk.transpose(0, 2, 1), mode) * hd ** -0.5
    sc = jnp.where(pos[:, None] >= pos[None, :], sc, -jnp.inf)
    o = _mm(jax.nn.softmax(sc, axis=-1), v, mode)
    return _mm(o.transpose(1, 0, 2).reshape(s, H * hd), p[f"{b}/attn/wo"],
               mode)


def _mamba(h, p, b, k, mode):
    _, _, _, _, di, heads, P, N, G, K, eps, _ = k
    m, s = f"{b}/mamba", h.shape[0]
    zxbcdt = _mm(h, p[f"{m}/w_in"], mode)
    z, xbc = zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * G * N]
    dt = jax.nn.softplus(zxbcdt[:, 2 * di + 2 * G * N:] + p[f"{m}/dt_bias"])
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    conv = sum(xp[j:j + s] * p[f"{m}/conv_w"][j] for j in range(K))
    xbc = jax.nn.silu(conv + p[f"{m}/conv_b"])
    xs = xbc[:, :di].reshape(s, heads, P)
    bm = jnp.repeat(xbc[:, di:di + G * N].reshape(s, G, N), heads // G, 1)
    cm = jnp.repeat(xbc[:, di + G * N:].reshape(s, G, N), heads // G, 1)
    a = -jnp.exp(p[f"{m}/a_log"])

    def step(state, t):
        x, dtt, bt, ct = t
        state = state * jnp.exp(dtt * a)[:, None, None] + jnp.einsum(
            "hn,hp->hnp", bt, x * dtt[:, None], precision=HIGHEST)
        y = jnp.einsum("hn,hnp->hp", ct, state, precision=HIGHEST)
        return state, y + x * p[f"{m}/d_skip"][:, None]

    _, y = jax.lax.scan(step, jnp.zeros((heads, N, P), jnp.float32),
                        (xs, dt, bm, cm))
    y = _rms(y.reshape(s, di) * jax.nn.silu(z), p[f"{m}/norm_w"], eps)
    return _mm(y, p[f"{m}/w_out"], mode)


@functools.partial(jax.jit, static_argnums=(0, 3))
def forward(k: tuple, w: dict, tokens, mode: str):
    """Logits [S, V] of one token sequence (float32)."""
    kinds, eps = k[0], k[-2]
    pos = jnp.arange(tokens.shape[0])

    def period(x, p):
        for i, kind in enumerate(kinds):
            b = f"b{i}"
            h = _rms(x, p[f"{b}/ln_mix/w"], eps)
            x = x + (_attention(h, p, b, pos, k, mode) if kind == "attn"
                     else _mamba(h, p, b, k, mode))
            h = _rms(x, p[f"{b}/ln_mlp/w"], eps)
            g = jax.nn.silu(_mm(h, p[f"{b}/mlp/w_gate"], mode))
            x = x + _mm(g * _mm(h, p[f"{b}/mlp/w_up"], mode),
                        p[f"{b}/mlp/w_down"], mode)
        return x, None

    x, _ = jax.lax.scan(period, w["embed"][tokens],
                        {n: a for n, a in w.items() if "/" in n})
    return _mm(_rms(x, w["final_norm"], eps), w["head"], mode)


# -- work counts ------------------------------------------------------------

def paged_layers(c: dict) -> int:
    """Only the attention layers hold paged K/V."""
    k = dims(c)
    return k["L"] // k["period"] * k["kinds"].count("attn")


def param_count(c: dict) -> int:
    n = 0
    for shape, _, _ in shapes(c).values():
        size = 1
        for x in shape:
            size *= x
        n += size
    return n


def weight_bytes(c: dict) -> int:
    """All the weights, the untied head included, once a step."""
    return param_count(c) * ELEM_BYTES


def kv_row_bytes(c: dict) -> int:
    k = dims(c)
    return paged_layers(c) * 2 * k["Hkv"] * k["hd"] * ELEM_BYTES


def state_bytes(c: dict) -> int:
    """One sequence's recurrent state over every Mamba-2 layer: the
    convolution's window (served dtype) and the SSD state (float32)."""
    k = dims(c)
    mamba = k["L"] - paged_layers(c)
    return mamba * ((k["K"] - 1) * k["conv_ch"] * ELEM_BYTES
                    + k["heads"] * k["N"] * k["P"] * 4)


def token_flops(c: dict, ctx: int) -> int:
    """The matmuls (every weight once, the embedding's gather aside),
    attention over the context in each attention layer, and each Mamba-2
    layer's state update and read-out."""
    k = dims(c)
    matmul = param_count(c) - k["V"] * k["d"]
    mamba = k["L"] - paged_layers(c)
    return 2 * matmul + paged_layers(c) * 4 * k["H"] * k["hd"] * ctx \
        + mamba * 4 * k["heads"] * k["N"] * k["P"]


def step_flops(c: dict, ctxs) -> int:
    return sum(token_flops(c, int(n)) for n in ctxs)


def step_bytes(c: dict, ctxs) -> int:
    """The weights once, the K/V of each context, one new K/V row and the
    recurrent state read and written per sequence."""
    ctxs = [int(n) for n in ctxs]
    if not ctxs:
        return 0
    return weight_bytes(c) + kv_row_bytes(c) * (sum(ctxs) + len(ctxs)) \
        + 2 * state_bytes(c) * len(ctxs)


def gather_attend_work(c: dict, ctxs) -> tuple[int, int]:
    k = dims(c)
    flops = sum(4 * k["H"] * k["hd"] * int(n) for n in ctxs)
    kv = sum(2 * k["Hkv"] * k["hd"] * int(n) for n in ctxs) * ELEM_BYTES
    io = len(ctxs) * k["H"] * (k["hd"] * ELEM_BYTES + (k["hd"] + 2) * 4)
    return flops, kv + io


def kv_write_work(c: dict, n_seqs: int) -> tuple[int, int]:
    k = dims(c)
    return 0, n_seqs * 2 * k["Hkv"] * k["hd"] * ELEM_BYTES

"""Plain reference of the served model, and the comparison that decides
``correct``.

The reference is the published architecture written out in ``jax.numpy``:
a decoder of RMS-normed pre-norm blocks, grouped-query causal attention
with rotary positions (split halves, ``rope_theta``) and, for Qwen3, an
RMS norm over each query and key head before the rotation; a SwiGLU MLP;
a final RMS norm and the (tied) unembedding.  It runs one whole sequence
at a time, in float32 at the highest matmul precision, over the weights
``bench.weights`` makes from the seed (the bf16 values the model is served
with, widened).  It imports nothing of the program.

The compared number is the widest gap, over every served token of a
sample of finished requests, by which the served token's reference logit
lies below the reference's best logit at that position.  Greedy decoding
that follows the reference reads 0 there, bar rounding.

The control is the same reference computed in float8 (e4m3, absmax scaled
per row and per column of every matmul operand): the step below the bf16
the configuration states.  At each position it reads the gap of the token
the float8 reference puts first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.work import dims

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
#: sequences are padded to a multiple of this, so a run compiles the
#: reference for a few lengths only
BUCKET = 512


def _q8(x, axis):
    """Round ``x`` to float8 e4m3 with an absmax scale along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(a, b, mode):
    """a [..., M, K] @ b [..., K, N] in float32, or with both operands
    rounded to float8 along K first."""
    if mode == "fp8":
        a, b = _q8(a, -1), _q8(b, -2)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [..., S, D]; rotary over split halves."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _forward(k: tuple, w: dict, tokens, mode: str):
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


def _key(c: dict) -> tuple:
    k = dims(c)
    return (k["L"], k["d"], k["H"], k["Hkv"], k["hd"], k["qk_norm"],
            k["tied"], float(c["rms_norm_eps"]), float(c["rope_theta"]))


def reference_weights(c: dict, seed: int) -> dict:
    """The weights served for ``seed`` (in the configuration's dtype),
    widened to float32."""
    w = W.make(c, seed, c["torch_dtype"])
    return jax.tree.map(lambda a: a.astype(jnp.float32), w)


def logits(c: dict, w: dict, tokens: np.ndarray, mode: str = "f32"):
    """Reference logits [len(tokens), V] as host float32."""
    n = len(tokens)
    padded = np.zeros(-(-n // BUCKET) * BUCKET, np.int32)
    padded[:n] = tokens
    out = _forward(_key(c), w, jnp.asarray(padded), mode)
    return np.asarray(out[:n], np.float32)


def served_gaps(ref: np.ndarray, prompt_len: int, served) -> np.ndarray:
    """Gap of each served token below the reference's best logit at its
    position; ``ref`` are the reference logits of prompt + served."""
    rows = ref[prompt_len - 1: prompt_len - 1 + len(served)]
    best = rows.max(-1)
    return best - rows[np.arange(len(served)), np.asarray(served)]


def max_logit_gap(c: dict, seed: int, sample, control: bool = False):
    """Widest served-token gap over ``sample`` [(prompt, served)], or with
    ``control`` the widest gap of the tokens the float8 reference puts
    first at the same positions.  Returns (gap, tokens compared)."""
    w = reference_weights(c, seed)
    worst, count = 0.0, 0
    for prompt, served in sample:
        toks = np.concatenate([np.asarray(prompt, np.int32),
                               np.asarray(served, np.int32)])
        ref = logits(c, w, toks)
        if control:
            picks = logits(c, w, toks, "fp8")[
                len(prompt) - 1: len(prompt) - 1 + len(served)].argmax(-1)
        else:
            picks = served
        gaps = served_gaps(ref, len(prompt), picks)
        worst = max(worst, float(gaps.max()))
        count += len(served)
    return worst, count

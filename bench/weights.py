"""Random weights for a configuration, made on the device from the seed.

One jitted call makes every weight, in the dtype the model is served in.
The layout is the benchmark's own (names below); the harness hands it to
the program in the program's tree, and the plain reference reads the same
arrays again from the same seed.  Neither takes anything the program made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.work import dims


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


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as two uint32 words."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed out of range: {seed}")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(spec: tuple, words, dtype):
    key = jax.random.fold_in(jax.random.key(words[0]), words[1])
    keys = jax.random.split(key, len(spec))
    out = {}
    for k, (name, shape, init, std) in zip(keys, spec):
        if init == "ones":
            out[name] = jnp.ones(shape, dtype)
        else:
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * std).astype(dtype)
    return out


def make(c: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every weight of ``c`` from ``seed``, in one jitted call."""
    spec = tuple((n, s, i, sd) for n, (s, i, sd) in sorted(shapes(c).items()))
    return _make(spec, seed_words(seed), jnp.dtype(dtype))

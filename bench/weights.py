"""Random weights for a configuration, made on the device from the seed.

One jitted call makes every weight, in the dtype the model is served in.
The layout is the benchmark's own: the names, shapes and initializers that
the configuration's architecture module gives (``shapes`` in
``bench/archs/<model_type>.py``).  The harness hands the weights to the
program in the program's tree, and the plain reference reads the same
arrays again from the same seed.  Neither takes anything the program made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import work


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
        elif init == "normal":
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * std).astype(dtype)
        else:
            raise ValueError(f"{name}: unknown init {init!r}")
    return out


def spec(c: dict) -> tuple:
    """(name, shape, init, std) of every weight of ``c``, sorted by name,
    from its architecture's ``shapes(c)``: the static argument of
    ``_make``, so the same spec gives the same bits from the same seed."""
    return tuple((n, s, i, sd) for n, (s, i, sd)
                 in sorted(work.arch(c).shapes(c).items()))


def make(c: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every weight of ``c`` from ``seed``, in one jitted call: each is
    normal with its std, or ones."""
    return _make(spec(c), seed_words(seed), jnp.dtype(dtype))

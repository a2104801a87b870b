"""Random weights from the seed, made on the device in one jitted call.

The benchmark owns the weights: it makes them in the layout of the
configuration's family (``bench/families/<family>.py``), hands them to the
system under test through the family's ``to_program``, and makes them
again for the plain reference after the window.  Every leaf is drawn in
bfloat16, the type it is served in, from ``fold_in(key(seed), leaf
number)``.  Norm scales are drawn around 1 and biases around 0, so the
comparison with the reference covers them too.
"""
from __future__ import annotations

import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """Two 31-bit words from any non-negative seed, however large."""
    a, b = np.random.SeedSequence(int(seed)).generate_state(2)
    return np.array([a >> 1, b >> 1], np.int32)


def make_weights(spec: dict, seed: int, device=None) -> dict:
    """Every leaf of ``spec`` (a family's ``layout``: name -> (shape,
    mean, std)) in bfloat16, in one jitted call; leaf i of the sorted
    names is drawn from ``fold_in(key, i)``."""
    import jax
    import jax.numpy as jnp

    names = sorted(spec)

    def gen(words):
        key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
        out = {}
        for i, name in enumerate(names):
            shape, mean, std = spec[name]
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.bfloat16)
            out[name] = x * jnp.bfloat16(std) + jnp.bfloat16(mean)
        return out

    words = jnp.asarray(seed_words(seed))
    if device is not None:
        words = jax.device_put(words, device)
    return jax.jit(gen)(words)

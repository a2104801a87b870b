"""Random weights from the seed, made on the device in one jitted call.

The benchmark owns the weights: it makes them, hands them to the system
under test in the program's parameter layout, and makes them again for
the plain reference after the window.  Every leaf is drawn in bfloat16,
the type it is served in, from ``fold_in(key(seed), leaf number)``.
Norm scales are drawn around 1 and biases around 0, so the comparison
with the reference covers them too.
"""
from __future__ import annotations

import numpy as np

from bench.model_dims import Dims


def layout(m: Dims) -> dict:
    """name -> (shape, mean, std).  Layer leaves carry a leading layer
    axis."""
    d, f, L, H, K, Dh, V = m.d, m.f, m.L, m.H, m.K, m.Dh, m.V
    out = {
        "embed": ((V, d), 0.0, d ** -0.5),
        "norm1": ((L, d), 1.0, 0.1),
        "wq": ((L, d, H, Dh), 0.0, d ** -0.5),
        "wk": ((L, d, K, Dh), 0.0, d ** -0.5),
        "wv": ((L, d, K, Dh), 0.0, d ** -0.5),
        "wo": ((L, H, Dh, d), 0.0, (H * Dh) ** -0.5),
        "norm2": ((L, d), 1.0, 0.1),
        "w1": ((L, d, f), 0.0, d ** -0.5),
        "w2": ((L, f, d), 0.0, f ** -0.5),
        "final_norm": ((d,), 1.0, 0.1),
        "lm_head": ((V, d), 0.0, d ** -0.5),
    }
    if m.mlp == "swiglu":
        out["w3"] = ((L, d, f), 0.0, d ** -0.5)
    if m.qkv_bias:
        out["bq"] = ((L, H, Dh), 0.0, 0.1)
        out["bk"] = ((L, K, Dh), 0.0, 0.1)
        out["bv"] = ((L, K, Dh), 0.0, 0.1)
    return out


def seed_words(seed: int) -> np.ndarray:
    """Two 31-bit words from any non-negative seed, however large."""
    a, b = np.random.SeedSequence(int(seed)).generate_state(2)
    return np.array([a >> 1, b >> 1], np.int32)


def make_weights(m: Dims, seed: int, device=None) -> dict:
    """Every leaf of ``layout(m)`` in bfloat16, in one jitted call."""
    import jax
    import jax.numpy as jnp

    spec = layout(m)
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


def to_program(w: dict, m: Dims) -> dict:
    """The same arrays arranged as ``repro.models`` expects its
    parameters (one stacked layer group; no copies)."""
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in w}
    mlp = {k: w[k] for k in ("w1", "w2", "w3") if k in w}
    layer = {"norm1": {"scale": w["norm1"]}, "attn": attn,
             "norm2": {"scale": w["norm2"]}, "mlp": mlp}
    return {"embed": {"tok": w["embed"]}, "layers": [layer],
            "final_norm": {"scale": w["final_norm"]},
            "lm_head": {"w": w["lm_head"]}}

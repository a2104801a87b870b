"""Plain float32 forward of the dense decoder family (the ``reference`` of
``bench/families/dense.py``), which ``bench/check.py`` holds served tokens
against.

Straight ``jax.numpy`` under ``default_matmul_precision("highest")``: no
kernel, no cache, no batching, nothing imported from the program.  It
follows the published description except where the configuration file
lists a departure (the program's choices, which both sides share):

* RMSNorm with a scale, where the published models use LayerNorm;
* rotary embedding over the whole head (split halves), where
  stablelm-3b rotates only the first 25%;
* q/k/v biases only (starcoder2 publishes biases on every linear layer);
* no sliding window (every cell keeps context under the published 4096).

The layers run one at a time, so that the float32 working set is one
layer's weights.
"""
from __future__ import annotations

import functools

import numpy as np

#: largest finite float8_e4m3fn value
_FP8_MAX = 448.0
#: input axes of each matrix (its output channels keep their own scale)
_IN_AXES = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
            "w1": (0,), "w3": (0,), "w2": (0,)}
#: sequences are padded to a multiple of this, so few programs compile
_PAD = 256


def fp8_weights(w, in_axes):
    """``w`` rounded through float8_e4m3fn with one scale per output
    channel: the control's lower precision."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True) / _FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rmsnorm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (S, heads, Dh); rotate the two halves of each head."""
    import jax.numpy as jnp
    S, _, Dh = x.shape
    inv = theta ** (-jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :Dh // 2], x[..., Dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.lru_cache(maxsize=None)
def _layer_fn(m, fp8: bool):
    import jax
    import jax.numpy as jnp

    def layer(x, w, i):
        p = {k: w[k][i].astype(jnp.float32) for k in
             ("norm1", "norm2", "wq", "wk", "wv", "wo", "w1", "w2", "w3",
              "bq", "bk", "bv") if k in w}
        if fp8:
            p.update({k: fp8_weights(p[k], ax) for k, ax in _IN_AXES.items()
                      if k in p})
        with jax.default_matmul_precision("highest"):
            h = _rmsnorm(x, p["norm1"], m.eps)
            q = jnp.einsum("sd,dhk->shk", h, p["wq"])
            k = jnp.einsum("sd,dhk->shk", h, p["wk"])
            v = jnp.einsum("sd,dhk->shk", h, p["wv"])
            if m.qkv_bias:
                q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
            q, k = _rope(q, m.rope_theta), _rope(k, m.rope_theta)
            k = jnp.repeat(k, m.H // m.K, axis=1)      # head h reads h // G
            v = jnp.repeat(v, m.H // m.K, axis=1)
            s = jnp.einsum("qhd,khd->hqk", q, k) * m.Dh ** -0.5
            S = x.shape[0]
            causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
            s = jnp.where(causal[None], s, -jnp.inf)
            o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
            x = x + jnp.einsum("qhd,hde->qe", o, p["wo"])
            h = _rmsnorm(x, p["norm2"], m.eps)
            if m.mlp == "swiglu":
                a = jax.nn.silu(h @ p["w1"]) * (h @ p["w3"])
            else:
                a = jax.nn.gelu(h @ p["w1"], approximate=True)
            return x + a @ p["w2"]

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(m, fp8: bool):
    import jax
    import jax.numpy as jnp

    def head(x, w, rows):
        lm = w["lm_head"].astype(jnp.float32)
        if fp8:
            lm = fp8_weights(lm, (1,))
        with jax.default_matmul_precision("highest"):
            h = _rmsnorm(x[rows], w["final_norm"].astype(jnp.float32), m.eps)
            return h @ lm.T

    return jax.jit(head)


@functools.lru_cache(maxsize=None)
def _embed_fn(fp8: bool):
    import jax
    import jax.numpy as jnp

    def embed(w, tokens):
        x = w["embed"][tokens].astype(jnp.float32)
        return fp8_weights(x, (1,)) if fp8 else x

    return jax.jit(embed)


def logits(w: dict, m, tokens: np.ndarray, rows: np.ndarray,
           fp8: bool = False) -> np.ndarray:
    """float32 logits at positions ``rows`` of ``tokens`` (causal, so
    padding the sequence changes nothing before it); ``m`` is the dense
    family's ``Dims``.  ``fp8`` rounds every matrix through float8 first:
    the control."""
    import jax.numpy as jnp
    S = len(tokens)
    padded = np.zeros(-(-S // _PAD) * _PAD, np.int32)
    padded[:S] = tokens
    x = _embed_fn(fp8)(w, jnp.asarray(padded))
    layer = _layer_fn(m, fp8)
    for i in range(m.L):
        x = layer(x, w, jnp.int32(i))
    out = _head_fn(m, fp8)(x, w, jnp.asarray(np.asarray(rows, np.int32)))
    return np.asarray(out, np.float32)

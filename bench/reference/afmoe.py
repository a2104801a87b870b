"""Plain float32 forward of the ``afmoe`` family (the ``reference`` of
``bench/families/afmoe.py``), which ``bench/check.py`` holds served tokens
against.

Straight ``jax.numpy`` under ``default_matmul_precision("highest")``: no
kernel, no cache, no batching, nothing imported from the program.  It
follows the layer equations the family module states: sandwich RMSNorms,
q and k normalised per head, rotary (split halves) and the window only in
sliding layers, the attention output gated by sigmoid(x Wg), and the
expert layer routing over every expert by sigmoid score (top-k of score +
bias, weights renormalised over the chosen and scaled).  Of the routed
result it computes the held experts' part only, densely over every token
and held expert, and adds the shared expert.  The departures the
configuration file lists are the program's choices, which both sides
share (bfloat16 weights, random and drawn from the seed).

The float8 control (``fp8``) rounds every matrix through float8_e4m3fn
with a scale per output channel, as ``reference/dense.py`` does.  Layers
run one at a time, so the float32 working set is one layer's weights.
"""
from __future__ import annotations

import functools
import sys

import numpy as np

from bench.reference.dense import _PAD, _rmsnorm, _rope, fp8_weights

#: vocabulary rows of the head computed at once, at most
_HEAD_ROWS = 32768
#: input axes of each matrix (its output channels keep their own scale)
_IN_AXES = {"wq": (0,), "wk": (0,), "wv": (0,), "wg": (0,), "wo": (0, 1),
            "w1": (-2,), "w3": (-2,), "w2": (-2,), "router": (0,),
            "shared_w1": (0,), "shared_w3": (0,), "shared_w2": (0,)}


def slot(w: dict, s: int) -> dict:
    """Slot ``s``'s leaves of the family's layout, by their short names:
    layer ``l`` is group ``l // period`` of slot ``l % period``."""
    pre = f"s{s:02d}."
    return {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}


def teams(v, m):
    """Every expert's leaf from one drawn per team (last axis ``E // k``):
    expert ``e`` is in team ``e % (E // k)``, so a team's ``k`` experts
    lie spread over every share of the experts (``bench/families/afmoe.py``
    ``layout``)."""
    import jax.numpy as jnp
    return jnp.tile(v, (1,) * (v.ndim - 1) + (m.E // v.shape[-1],))


def _swiglu(h, w1, w3, w2):
    import jax
    return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2


def route(h, router, bias, m):
    """(chosen experts (S, k), their weights (S, k), margin (S,)) of
    normalised inputs ``h`` (S, d), over every expert.  The margin is how
    far the k-th selection score (score + bias) lies above the (k+1)-th:
    how close the token came to choosing another expert."""
    import jax
    import jax.numpy as jnp
    scores = jax.nn.sigmoid(h @ router)
    top, idx = jax.lax.top_k(scores + bias, m.k + 1)
    idx = idx[:, :m.k]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return (idx, w / jnp.sum(w, -1, keepdims=True) * m.route_scale,
            top[:, m.k - 1] - top[:, m.k])


@functools.lru_cache(maxsize=None)
def _layer_fn(m, window, rope: bool, dense: bool, fp8: bool):
    import jax
    import jax.numpy as jnp

    def layer(x, w, g):
        """(the layer's output, each token's routing margin: inf in a
        dense layer)."""
        p = {k: v[g].astype(jnp.float32) for k, v in w.items()}
        if fp8:
            p.update({k: fp8_weights(p[k], ax) for k, ax in _IN_AXES.items()
                      if k in p})
        with jax.default_matmul_precision("highest"):
            S = x.shape[0]
            h = _rmsnorm(x, p["norm1"], m.eps)
            q = jnp.einsum("sd,dhk->shk", h, p["wq"])
            k = jnp.einsum("sd,dhk->shk", h, p["wk"])
            v = jnp.einsum("sd,dhk->shk", h, p["wv"])
            q = _rmsnorm(q, p["q_norm"], m.eps)
            k = _rmsnorm(k, p["k_norm"], m.eps)
            if rope:
                q, k = _rope(q, m.rope_theta), _rope(k, m.rope_theta)
            k = jnp.repeat(k, m.H // m.K, axis=1)      # head h reads h // G
            v = jnp.repeat(v, m.H // m.K, axis=1)
            s = jnp.einsum("qhd,khd->hqk", q, k) * m.Dh ** -0.5
            i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
            mask = j <= i
            if window is not None:
                mask &= i - j < window
            s = jnp.where(mask[None], s, -jnp.inf)
            o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
            o = o * jax.nn.sigmoid(jnp.einsum("sd,dhk->shk", h, p["wg"]))
            a = jnp.einsum("qhd,hde->qe", o, p["wo"])
            x = x + _rmsnorm(a, p["post_attn_norm"], m.eps)
            h = _rmsnorm(x, p["norm2"], m.eps)
            margin = jnp.full((S,), jnp.inf, jnp.float32)
            if dense:
                y = _swiglu(h, p["w1"], p["w3"], p["w2"])
            else:
                idx, wt, margin = route(h, teams(p["router"], m),
                                        teams(p["expert_bias"], m), m)
                # each token's weight on each held expert (0 if not chosen)
                held = jnp.arange(m.first, m.first + m.n)
                c = jnp.sum(jnp.where(idx[..., None] == held, wt[..., None],
                                      0.0), axis=1)                 # (S, n)
                a = jax.nn.silu(jnp.einsum("sd,edf->esf", h, p["w1"]))
                a = a * jnp.einsum("sd,edf->esf", h, p["w3"])
                out = jnp.einsum("esf,efd->esd", a, p["w2"])
                y = jnp.einsum("se,esd->sd", c, out)
                y = y + _swiglu(h, p["shared_w1"], p["shared_w3"],
                                p["shared_w2"])
            return x + _rmsnorm(y, p["post_mlp_norm"], m.eps), margin

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _ends_fn(m, fp8: bool):
    import jax
    import jax.numpy as jnp

    def embed(w, tokens):
        x = w["embed"][tokens].astype(jnp.float32)
        return (fp8_weights(x, (1,)) if fp8 else x) * m.embed_scale

    def head(x, w, rows, start):
        """The logits of vocabulary rows [start, start + block): a float32
        copy of the whole 200192-row head would not fit beside the rest."""
        lm = jax.lax.dynamic_slice_in_dim(w["lm_head"], start, block)
        lm = lm.astype(jnp.float32)
        if fp8:
            lm = fp8_weights(lm, (1,))
        with jax.default_matmul_precision("highest"):
            h = _rmsnorm(x[rows], w["final_norm"].astype(jnp.float32), m.eps)
            return h @ lm.T

    n = -(-m.V // _HEAD_ROWS)
    while m.V % n:
        n += 1
    block = m.V // n
    return jax.jit(embed), jax.jit(head), block


def _forward(w: dict, m, tokens: np.ndarray, fp8: bool):
    """(the last layer's output (padded S, d), each position's smallest
    routing margin over the expert layers (padded S,))."""
    import jax.numpy as jnp
    S = len(tokens)
    padded = np.zeros(-(-S // _PAD) * _PAD, np.int32)
    padded[:S] = tokens
    embed, _, _ = _ends_fn(m, fp8)
    x = embed(w, jnp.asarray(padded))
    margin = jnp.full(padded.shape, jnp.inf, jnp.float32)
    P = m.period
    slots = [slot(w, s) for s in range(P)]
    for layer in range(m.L):
        s = layer % P
        fn = _layer_fn(m, m.windows[s], m.rope[s], s < m.dense, fp8)
        x, mg = fn(x, slots[s], jnp.int32(layer // P))
        margin = jnp.minimum(margin, mg)
    return x, margin


def margins(w: dict, m, tokens: np.ndarray) -> np.ndarray:
    """Each position's smallest routing margin over the expert layers
    (``route``), in the float32 forward of ``tokens``."""
    return np.asarray(_forward(w, m, tokens, False)[1])[:len(tokens)]


def logits(w: dict, m, tokens: np.ndarray, rows: np.ndarray,
           fp8: bool = False) -> np.ndarray:
    """float32 logits at positions ``rows`` of ``tokens`` (causal, so
    padding the sequence changes nothing before it); ``m`` is the family's
    ``Dims``.  ``fp8`` rounds every matrix through float8 first: the
    control.

    A position where some expert layer's routing margin is below
    ``m.route_margin`` is one where bfloat16's rounding can choose
    another expert than float32 does, and that choice, not the precision
    of the arithmetic, would decide the comparison there.  Its row is
    returned as zeros, so ``bench/check.py`` reads a gap of 0 for any
    token there (for the program and the control alike), and the number
    of such rows is written to stderr."""
    import jax.numpy as jnp
    x, margin = _forward(w, m, tokens, fp8)
    _, head, block = _ends_fn(m, fp8)
    rows = np.asarray(rows, np.int32)
    out = np.concatenate([np.asarray(head(x, w, jnp.asarray(rows),
                                          jnp.int32(v)), np.float32)
                          for v in range(0, m.V, block)], axis=1)
    if not fp8 and m.route_margin:
        near = np.asarray(margin)[rows] < m.route_margin
        out[near] = 0.0
        print(f"reference: {int(near.sum())} of {len(rows)} positions "
              f"skipped (a routing margin below {m.route_margin})",
              file=sys.stderr, flush=True)
    return out

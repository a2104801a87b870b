"""Mixture-of-Experts FFN, on two paths.

Inference (``moe_dropless``): every (token, expert) pair is computed, with
no capacity, so a token's result never depends on which tokens share its
batch.  The layer holds a share of the experts (``MoEConfig.experts_held``,
all by default): it routes over every expert, keeps the pairs whose expert
it holds, sorts them by expert and runs grouped matmuls over the held
experts' weights alone (``kernels.moe_gmm`` with Pallas, else
``lax.ragged_dot``).  A share's result is its experts' part of the layer;
the shared expert is everyone's.

Training (``moe_apply``): GShard-style grouped top-k dispatch with
capacity, shared experts (qwen2-moe), router z-loss and load-balance loss.

Tokens are processed in groups of ``moe.group_size`` so the one-hot
dispatch/combine tensors stay O(group * E * capacity) instead of
O(tokens * E * capacity_global).  The group dim carries the batch sharding
(data axis); the expert dim carries expert parallelism (model axis) when
``E % model_axis == 0`` (see core/sharding.py).

FLOPs are capacity-bounded: compiled compute ≈ active-expert compute *
capacity_factor, which is what the roofline's MODEL_FLOPS/HLO_FLOPs ratio
checks against.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.core.actshard import constrain
from repro.models.mlp import mlp_apply

#: additive counters of the inference path, over the rows a pass counts
#: (the engine's decode passes, their live lanes): pairs that reached a
#: held expert, passes where some did, the busiest held expert's pairs,
#: and held experts that got any pair (``moe_dropless``'s ``stats``)
MOE_STATS = ("moe_routed_pairs", "moe_expert_passes", "moe_load_max_sum",
             "moe_experts_reached")


def route(p: dict, x: jax.Array, moe: MoEConfig):
    """x: (T, d) -> (chosen experts (T, k) int32, their weights (T, k)
    float32).  Scores are the softmax or sigmoid of the float32 router
    logits over every expert; ``expert_bias`` shifts only which k are
    chosen; the chosen scores are renormalised over the k (counting
    experts held elsewhere) and scaled by ``route_scale``."""
    logits = jnp.einsum("td,de->te", x, p["router"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    if moe.score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    pick = scores
    if moe.expert_bias:
        pick = scores + p["expert_bias"].astype(jnp.float32)
    _, idx = jax.lax.top_k(pick, moe.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    if moe.route_scale != 1.0:
        w = w * moe.route_scale
    return idx.astype(jnp.int32), w


def _grouped_xla(p: dict, rows, sizes):
    """Each held expert's SwiGLU over its rows with ``lax.ragged_dot``."""
    dtype = rows.dtype

    def gdot(a, w):
        return jax.lax.ragged_dot(a, w.astype(dtype), sizes,
                                  preferred_element_type=jnp.float32)

    h = jax.nn.silu(gdot(rows, p["w1"])) * gdot(rows, p["w3"])
    return gdot(h.astype(dtype), p["w2"])


def moe_dropless(p: dict, x: jax.Array, cfg: ModelConfig,
                 use_pallas: bool = False, valid=None,
                 name: str = "moe_experts"):
    """x: (B, S, d) -> (y (B, S, d), stats (4,) int32 in ``MOE_STATS``
    order).  y is the held experts' part of every token's routed result,
    plus the shared expert.  Every row is computed; ``stats`` counts the
    rows ``valid`` ((B, S) bool, default all) marks, so finished lanes and
    bucket padding, whose rows share one hidden state and one routing, do
    not read as load.  ``name`` prefixes the Pallas kernels' names in the
    device trace."""
    moe = cfg.moe
    assert cfg.mlp_type == "swiglu", "the held experts are SwiGLU"
    B, S, d = x.shape
    T, k = B * S, moe.top_k
    lo, n = moe.held()
    xt = x.reshape(T, d)
    idx, w = route(p, xt, moe)
    local = idx - lo
    held = (local >= 0) & (local < n)                         # (T, k)
    # pairs sorted by held expert; pairs held elsewhere sort last, apart
    key = jnp.where(held, local, n).reshape(T * k)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.zeros(n + 1, jnp.int32).at[key].add(1)[:n]
    rows = jnp.take(xt, order // k, axis=0)                   # (T*k, d)
    if use_pallas:
        from repro.kernels import moe_gmm, ops
        pad = -(T * k) % moe_gmm.ROW_TILE
        out = moe_gmm.expert_swiglu(
            jnp.pad(rows, ((0, pad), (0, 0))), p["w1"].astype(x.dtype),
            p["w3"].astype(x.dtype), p["w2"].astype(x.dtype), sizes,
            name=name, interpret=ops._on_cpu())[:T * k]
    else:
        out = _grouped_xla(p, rows, sizes)
    # back to (token, choice) order; a token's k parts add in that order,
    # and rows of experts held elsewhere are never read
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))
    parts = jnp.take(out, inv, axis=0).reshape(T, k, d)
    y = jnp.sum(jnp.where(held[..., None], w[..., None] * parts, 0.0), axis=1)
    if moe.num_shared:
        y = y + mlp_apply(p["shared"], xt, cfg.mlp_type).astype(jnp.float32)
    y = y.astype(x.dtype)
    counted = sizes
    if valid is not None:
        live = jnp.broadcast_to(valid.reshape(T, 1), (T, k)).reshape(T * k)
        counted = jnp.zeros(n + 1, jnp.int32).at[key].add(
            live.astype(jnp.int32))[:n]
    stats = jnp.stack([jnp.sum(counted), jnp.any(counted > 0),
                       jnp.max(counted), jnp.sum(counted > 0)]
                      ).astype(jnp.int32)
    return y.reshape(B, S, d), stats


def _capacity(group: int, top_k: int, num_experts: int, factor: float) -> int:
    c = int(math.ceil(group * top_k / num_experts * factor))
    return max(4, ((c + 3) // 4) * 4)  # pad to a multiple of 4


def moe_apply(p: dict, x: jax.Array, cfg: ModelConfig):
    """x: (B, S, d) -> (y: (B, S, d), aux: dict of scalar losses)."""
    moe = cfg.moe
    assert moe.held() == (0, moe.num_experts), \
        "capacity dispatch trains every expert; a held share is inference"
    B, S, d = x.shape
    E, k = moe.num_experts, moe.top_k
    T = B * S
    gs = min(moe.group_size, T)
    xt = x.reshape(T, d)
    T_pad = ((T + gs - 1) // gs) * gs
    if T_pad != T:
        # padded tokens route like real ones but are sliced off at the end;
        # capacity waste is bounded by one group.
        xt = jnp.pad(xt, ((0, T_pad - T), (0, 0)))
    G = T_pad // gs
    C = _capacity(gs, k, E, moe.capacity_factor)
    dtype = x.dtype

    xt = constrain(xt.reshape(G, gs, d), "moe_tokens")
    logits = (xt @ p["router"].astype(dtype)).astype(jnp.float32)  # (G,gs,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                  # (G,gs,k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # ---- aux losses (Switch/GShard) ----
    me = jnp.mean(probs, axis=1)                                   # (G,E)
    ce = jnp.mean(jax.nn.one_hot(gate_idx[..., 0], E, dtype=jnp.float32),
                  axis=1)                                          # (G,E)
    balance_loss = E * jnp.mean(jnp.sum(me * ce, axis=-1))
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    # ---- capacity-based dispatch ----
    dispatch = jnp.zeros((G, gs, E, C), dtype)
    combine = jnp.zeros((G, gs, E, C), jnp.float32)
    counts = jnp.zeros((G, E), jnp.int32)
    for j in range(k):
        oh = jax.nn.one_hot(gate_idx[..., j], E, dtype=jnp.int32)  # (G,gs,E)
        pos = jnp.cumsum(oh, axis=1) - 1 + counts[:, None, :]      # (G,gs,E)
        pos_j = jnp.sum(pos * oh, axis=-1)                         # (G,gs)
        within = pos_j < C
        sel = (oh * within[..., None].astype(jnp.int32))           # (G,gs,E)
        cap_oh = jax.nn.one_hot(pos_j, C, dtype=jnp.float32)       # (G,gs,C)
        d_j = sel[..., :, None].astype(jnp.float32) * cap_oh[..., None, :]
        dispatch = dispatch + d_j.astype(dtype)
        combine = combine + d_j * gate_vals[..., j][..., None, None]
        counts = counts + jnp.sum(oh, axis=1)

    # ---- expert compute (einsum over capacity slots) ----
    dispatch = constrain(dispatch, "moe_dispatch")
    ein = constrain(jnp.einsum("gsec,gsd->gecd", dispatch, xt),
                    "moe_expert_d")                                # (G,E,C,d)
    h = constrain(jnp.einsum("gecd,edf->gecf", ein, p["w1"].astype(dtype)),
                  "moe_expert_f")
    if cfg.mlp_type == "swiglu":
        h = jax.nn.silu(h) * constrain(
            jnp.einsum("gecd,edf->gecf", ein, p["w3"].astype(dtype)),
            "moe_expert_f")
    elif cfg.mlp_type == "gelu":
        h = jax.nn.gelu(h)
    else:
        h = jnp.square(jax.nn.relu(h))
    eout = constrain(jnp.einsum("gecf,efd->gecd", h, p["w2"].astype(dtype)),
                     "moe_expert_d")                               # (G,E,C,d)
    y = constrain(jnp.einsum("gsec,gecd->gsd", combine.astype(dtype), eout),
                  "moe_tokens")

    if moe.num_shared:
        y = y + mlp_apply(p["shared"], xt, cfg.mlp_type)

    y = y.reshape(T_pad, d)[:T]

    aux = {
        "moe_balance_loss": balance_loss,
        "moe_z_loss": z_loss,
        "moe_overflow": 1.0 - jnp.mean(
            dispatch.astype(jnp.float32).sum(axis=(2, 3))) / k,
    }
    return y.reshape(B, S, d), aux

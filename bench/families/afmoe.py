"""The ``afmoe`` family (Arcee Trinity): sparse experts beside gated QK-norm
attention with sliding-window and full layers.

Each layer: ``x += post_attn_norm(Attn(input_norm(x)))`` then ``x +=
post_mlp_norm(FFN(pre_mlp_norm(x)))``.  Attention is GQA with RMSNorm of q
and k per head, rotary only in the sliding layers, and its output scaled by
``sigmoid(x Wg)`` per head before ``Wo``.  The first ``num_dense_layers``
FFNs are SwiGLU of ``intermediate_size``; every later one is a shared
SwiGLU expert plus routed SwiGLU experts: sigmoid scores over every
expert, the top ``num_experts_per_tok`` of score + a selection-only bias,
weighted by their scores renormalised over the chosen and scaled by
``route_scale``.  Embeddings are scaled by sqrt(hidden) (muP).

A configuration may hold a share of each expert layer
(``experts_held``: the first expert and how many, of ``routed_over``):
routing is over every expert, and only the held experts' part is computed,
by the program and the reference alike.

The module interface is ``bench/families/dense.py``'s; ``moe_work`` adds
the held experts' work for the expert-layer metrics.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

from bench.reference import afmoe as reference  # noqa: F401  (the family's)
from bench.reference.afmoe import slot, teams


@dataclass(frozen=True)
class Dims:
    d: int            # hidden size
    f: int            # dense SwiGLU width
    fe: int           # expert SwiGLU width
    L: int            # layers
    H: int            # query heads
    K: int            # key/value heads
    Dh: int           # head size
    V: int            # vocabulary
    E: int            # experts routed over
    first: int        # first expert held
    n: int            # experts held
    k: int            # experts per token
    shared: int       # shared experts (one SwiGLU of shared * fe)
    dense: int        # leading dense layers
    windows: tuple    # per layer: window or None
    rope: tuple       # per layer: rotary or not
    rope_theta: float
    eps: float
    route_scale: float
    embed_scale: float
    route_margin: float   # the check skips positions routed closer (PERF.md)

    @property
    def kv_bytes_per_token(self) -> int:
        return self.L * 2 * self.K * self.Dh * 2

    @property
    def period(self) -> int:
        """Smallest period of the layers' (window, rotary, FFN) kinds:
        the program stacks layer ``g * period + s`` as group g of slot s."""
        kinds = [(w, r, i < self.dense) for i, (w, r) in
                 enumerate(zip(self.windows, self.rope))]
        return next(p for p in range(1, self.L + 1) if self.L % p == 0 and
                    all(kinds[i] == kinds[i % p] for i in range(self.L)))


def dims(cfg: dict) -> Dims:
    held = cfg["experts_held"]
    assert held["count"] == cfg["num_experts"], held
    assert cfg["score_func"] == "sigmoid" and cfg["route_norm"], cfg
    L = cfg["num_hidden_layers"]
    types = cfg["layer_types"]
    assert len(types) == L, types
    sliding = [t == "sliding_attention" for t in types]
    return Dims(
        d=cfg["hidden_size"], f=cfg["intermediate_size"],
        fe=cfg["moe_intermediate_size"], L=L, H=cfg["num_attention_heads"],
        K=cfg["num_key_value_heads"], Dh=cfg["head_dim"], V=cfg["vocab_size"],
        E=held["routed_over"], first=held["first"], n=held["count"],
        k=cfg["num_experts_per_tok"], shared=cfg["num_shared_experts"],
        dense=cfg["num_dense_layers"],
        windows=tuple(cfg["sliding_window"] if s else None for s in sliding),
        rope=tuple(sliding), rope_theta=float(cfg["rope_theta"]),
        eps=float(cfg["rms_norm_eps"]), route_scale=float(cfg["route_scale"]),
        embed_scale=cfg["hidden_size"] ** 0.5 if cfg["mup_enabled"] else 1.0,
        route_margin=float(cfg["check"]["route_margin"]))


# ------------------------------------------------------------- weights ----

def layout(m: Dims) -> dict:
    """name -> (shape, mean, std), drawn by ``bench.weights.make_weights``.
    Layer leaves are named by the program's slot, ``s<slot>.<leaf>``, with
    a leading axis over the slot's layer groups, so ``to_program`` hands
    them over without a copy: layer ``l`` is group ``l // period`` of slot
    ``l % period``.

    Every sublayer's output passes its own RMSNorm before the residual, so
    the output projections' scales do not reach the stream; the embedding
    is drawn at 1/sqrt(d) so that with the muP scale it enters at unit
    RMS, like each sublayer.  q and k are normalised per head, so the
    query's norm scale (about 1) sets the scores' spread, std about 1:
    sharper attention (3, the dense family's lesson) lets bfloat16's
    rounding grow faster through the layers, and with it the share of
    tokens routed otherwise than in float32; the expert layers keep the
    served token tied to the prompt.  Every expert is drawn as the shared
    one is.

    The router and the selection bias are drawn per team of k experts
    (``reference.teams``: expert e is in team e % (E/k), so each share of
    the experts holds k*n/E of every team): a token's k highest scores
    are one team's, tied, and the next team's lie a team's gap below.
    Drawn per expert, 128 unit-spread logits put the k-th and (k+1)-th
    within bfloat16's noise at 5-37% of (token, layer), and each such
    flip, carried on through attention, moved later tokens as far as a
    fault does (PERF.md section 2).  The router at 1/sqrt(d) gives the
    teams unit-spread logits; the bias at 0.02 decides between teams
    whose scores lie close, so a program that ignores it chooses other
    teams.  Where a token's two best teams still lie within bfloat16's
    noise, the reference leaves the position out of the comparison
    (``route_margin``)."""
    d, Dh, H, K = m.d, m.Dh, m.H, m.K
    G, P = m.L // m.period, m.period
    out = {"embed": ((m.V, d), 0.0, d ** -0.5),
           "final_norm": ((d,), 1.0, 0.1),
           "lm_head": ((m.V, d), 0.0, d ** -0.5)}
    for s in range(P):
        leaves = {
            "norm1": ((d,), 1.0, 0.1),
            "wq": ((d, H, Dh), 0.0, d ** -0.5),
            "wk": ((d, K, Dh), 0.0, d ** -0.5),
            "wv": ((d, K, Dh), 0.0, d ** -0.5),
            "wg": ((d, H, Dh), 0.0, d ** -0.5),
            "wo": ((H, Dh, d), 0.0, (H * Dh) ** -0.5),
            "q_norm": ((Dh,), 1.0, 0.1),
            "k_norm": ((Dh,), 1.0, 0.1),
            "post_attn_norm": ((d,), 1.0, 0.1),
            "norm2": ((d,), 1.0, 0.1),
            "post_mlp_norm": ((d,), 1.0, 0.1),
        }
        if s < m.dense:
            leaves.update(w1=((d, m.f), 0.0, d ** -0.5),
                          w3=((d, m.f), 0.0, d ** -0.5),
                          w2=((m.f, d), 0.0, m.f ** -0.5))
        else:
            fs = m.fe * m.shared
            leaves.update(
                router=((d, m.E // m.k), 0.0, d ** -0.5),
                expert_bias=((m.E // m.k,), 0.0, 0.02),
                w1=((m.n, d, m.fe), 0.0, d ** -0.5),
                w3=((m.n, d, m.fe), 0.0, d ** -0.5),
                w2=((m.n, m.fe, d), 0.0, m.fe ** -0.5),
                shared_w1=((d, fs), 0.0, d ** -0.5),
                shared_w3=((d, fs), 0.0, d ** -0.5),
                shared_w2=((fs, d), 0.0, fs ** -0.5))
        for name, (shape, mean, std) in leaves.items():
            out[f"s{s:02d}.{name}"] = ((G,) + shape, mean, std)
    return out


def to_program(w: dict, m: Dims) -> dict:
    """The same arrays arranged as ``repro.models`` expects its
    parameters (one stacked group per slot; no copies but the router's
    and the bias's, widened from teams to experts)."""
    layers = []
    for s in range(m.period):
        x = slot(w, s)
        attn = {k: x[k] for k in ("wq", "wk", "wv", "wg", "wo")}
        attn.update(q_norm={"scale": x["q_norm"]},
                    k_norm={"scale": x["k_norm"]})
        layer = {"norm1": {"scale": x["norm1"]}, "attn": attn,
                 "post_attn_norm": {"scale": x["post_attn_norm"]},
                 "norm2": {"scale": x["norm2"]},
                 "post_mlp_norm": {"scale": x["post_mlp_norm"]}}
        if s < m.dense:
            layer["mlp"] = {k: x[k] for k in ("w1", "w2", "w3")}
        else:
            layer["moe"] = {
                "router": teams(x["router"], m),
                "expert_bias": teams(x["expert_bias"], m),
                "w1": x["w1"], "w2": x["w2"], "w3": x["w3"],
                "shared": {"w1": x["shared_w1"], "w2": x["shared_w2"],
                           "w3": x["shared_w3"]}}
        layers.append(layer)
    return {"embed": {"tok": w["embed"]}, "layers": layers,
            "final_norm": {"scale": w["final_norm"]},
            "lm_head": {"w": w["lm_head"]}}


def check_program(cfg: dict, m: Dims, program_cfg):
    """The program's configuration cut as the file says (its depth, its
    layer kinds, the experts held), checked against every size and switch
    the file states; exits where they differ."""
    pc = dataclasses.replace(
        program_cfg, num_layers=m.L, layer_types=tuple(cfg["layer_types"]),
        moe=dataclasses.replace(program_cfg.moe,
                                experts_held=(m.first, m.n)))
    moe = pc.moe
    got = (pc.d_model, pc.d_ff, moe.d_ff, pc.num_layers, pc.num_heads,
           pc.num_kv_heads, pc.head_dim, pc.vocab_size, moe.num_experts,
           moe.held(), moe.top_k, moe.num_shared, moe.first_dense,
           moe.every, moe.score_func, float(moe.route_scale),
           moe.expert_bias, tuple(a for a in pc.attn_kinds()),
           float(pc.rope_theta), float(pc.norm_eps), pc.qk_norm,
           pc.attn_gate, pc.sandwich_norm, pc.embed_scale, pc.mlp_type,
           pc.tie_embeddings, pc.qkv_bias)
    want = (m.d, m.f, m.fe, m.L, m.H, m.K, m.Dh, m.V, m.E, (m.first, m.n),
            m.k, m.shared, m.dense, 1, "sigmoid", m.route_scale, True,
            tuple(zip(m.windows, m.rope)), m.rope_theta, m.eps, True, True,
            True, m.embed_scale != 1.0, "swiglu",
            bool(cfg["tie_word_embeddings"]), False)
    if got != want:
        raise SystemExit(f"{cfg['arch']}: the program's configuration {got} "
                         f"is not the file's {want}")
    return pc


# -------------------------------------------------------------- counts ----

def _ctx(runs, window) -> tuple:
    """(tokens, sum of their attended lines), each token's lines capped at
    ``window`` (None: uncapped), in integers."""
    tokens = ctx = 0
    for first, n in runs:
        tokens += n
        last = first + n - 1
        if window is None or last <= window:
            ctx += n * first + n * (n - 1) // 2
        else:
            below = max(0, window - first + 1)   # tokens at <= window lines
            ctx += below * first + below * (below - 1) // 2
            ctx += (n - below) * window
    return tokens, ctx


def _lines(m: Dims, runs) -> tuple:
    """(tokens, attended lines summed over every layer)."""
    tokens, total = 0, 0
    for w in m.windows:
        tokens, ctx = _ctx(runs, w)
        total += ctx
    return tokens, total


def matmul_params(m: Dims) -> int:
    """Weights that multiply each token once, with an expert layer's
    routed experts counted as the token's expected share of the held ones
    (k of E routed, n/E of them here), beside its router and shared
    expert; the embedding is a lookup."""
    attn = 2 * m.d * m.H * m.Dh + 2 * m.d * m.K * m.Dh + m.H * m.Dh * m.d
    dense = 3 * m.d * m.f
    expert = (m.d * m.E + 3 * m.d * m.fe * m.shared
              + 3 * m.d * m.fe * m.k * m.n / m.E)
    return int(m.L * attn + m.dense * dense + (m.L - m.dense) * expert
               + m.V * m.d)


def model_flops(m: Dims, runs) -> float:
    """Forward FLOPs of the tokens of ``runs``: 2 per active weight per
    token, and 4 * H * Dh per attended line per layer."""
    tokens, lines = _lines(m, runs)
    return 2.0 * matmul_params(m) * tokens + 4.0 * m.H * m.Dh * lines


def paged_decode_work(m: Dims, runs) -> tuple:
    """(FLOPs, HBM bytes) the paged decode attention needs for the decoded
    tokens of ``runs``: each layer reads each attended bfloat16 K and V
    line once and each token's query and output once."""
    tokens, lines = _lines(m, runs)
    flops = 4.0 * m.H * m.Dh * lines
    kv = 2 * m.K * m.Dh * 2 * lines
    qo = m.L * 2 * m.H * m.Dh * 2 * tokens
    return flops, float(kv + qo)


def moe_work(m: Dims, counters: dict) -> tuple:
    """(FLOPs, HBM bytes) the held experts need for the expert-layer
    passes that ``counters`` (the program's ``moe_*`` counters) count:
    6 * d * fe FLOPs per routed pair (two SwiGLU inputs and the output
    projection), each reached expert's bfloat16 weights once per pass, and
    each pair's bfloat16 input and output rows."""
    pairs = counters["moe_routed_pairs"]
    flops = 6.0 * m.d * m.fe * pairs
    weights = counters["moe_experts_reached"] * 3 * m.d * m.fe * 2
    return flops, float(weights + pairs * 2 * m.d * 2)


# ----------------------------------------------------------- CPU tests ----

def tiny(cfg: dict) -> dict:
    """The keys that cut ``cfg`` to the program's ``reduced()`` preset:
    one sliding layer with a dense FFN and one full layer holding 2 of 4
    experts, 256 wide, 4 query and 2 key/value heads of 64, vocabulary
    512, a 128-token window.  The program computes in float32 there: with
    4 experts and top-2, bfloat16 flips the routing at near ties often
    enough to swamp the comparison (PERF.md, section 2), so this size
    checks the math and the chip's cell the precision.  The limit lies
    between the float32 program's widest gap and the float8 control's
    narrowest at that size."""
    return {"hidden_size": 256, "intermediate_size": 512,
            "moe_intermediate_size": 128, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 64, "vocab_size": 512, "num_experts": 2,
            "experts_held": {"first": 0, "count": 2, "routed_over": 4},
            "num_experts_per_tok": 2, "num_dense_layers": 1,
            "layer_types": ["sliding_attention", "full_attention"],
            "sliding_window": 128,
            "dtypes": dict(cfg["dtypes"], compute="float32"),
            "check": dict(cfg["check"], max_logit_gap=0.05)}

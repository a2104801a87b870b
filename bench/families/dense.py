"""The dense decoder family: every layer full causal attention (MHA or GQA,
rotary) and one MLP.

A family module holds whatever of the benchmark computes with the model:
the sizes read from the configuration file (``dims``), the weights'
layout and how the program takes them (``layout``, ``to_program``), the
check of the program's own configuration against the file
(``check_program``), the plain float32 reference (``reference``), the
operations and bytes the algorithm needs (``model_flops``,
``paged_decode_work``) and the sizes of the CPU tests (``tiny``).  A
configuration file names its family in ``"family"``; the harness loads
``bench/families/<family>.py``.

Token counts come as runs: ``(first_ctx, n)`` is ``n`` consecutive tokens
of one request, the first of which attends over ``first_ctx`` lines (itself
included), the next over one more, and so on.
"""
from __future__ import annotations

from dataclasses import dataclass

from bench.reference import dense as reference  # noqa: F401  (the family's)


@dataclass(frozen=True)
class Dims:
    d: int            # hidden size
    f: int            # MLP hidden size
    L: int            # layers
    H: int            # query heads
    K: int            # key/value heads
    Dh: int           # head size
    V: int            # vocabulary
    mlp: str          # "swiglu" (silu, gated) or "gelu_tanh" (not gated)
    qkv_bias: bool
    rope_theta: float
    eps: float

    @property
    def kv_bytes_per_token(self) -> int:
        """bfloat16 K and V lines of one token over all layers."""
        return self.L * 2 * self.K * self.Dh * 2


_ACT = {"silu": "swiglu", "gelu_pytorch_tanh": "gelu_tanh"}


def dims(cfg: dict) -> Dims:
    """The few numbers the weights, the reference and the counts need,
    read from the published config keys."""
    H = cfg["num_attention_heads"]
    return Dims(
        d=cfg["hidden_size"], f=cfg["intermediate_size"],
        L=cfg["num_hidden_layers"], H=H, K=cfg["num_key_value_heads"],
        Dh=cfg["hidden_size"] // H, V=cfg["vocab_size"],
        mlp=_ACT[cfg["hidden_act"]],
        qkv_bias=bool(cfg.get("use_qkv_bias", False)),
        rope_theta=float(cfg["rope_theta"]),
        eps=float(cfg.get("layer_norm_eps", cfg.get("norm_epsilon"))))


# ------------------------------------------------------------- weights ----

def layout(m: Dims) -> dict:
    """name -> (shape, mean, std).  Layer leaves carry a leading layer
    axis.  ``bench.weights.make_weights`` draws leaf i of the sorted names
    from ``fold_in(key, i)``: a name added here moves every later leaf.

    Drawn all at 1/sqrt(fan-in), the 30-layer stack collapses onto one
    direction that every position shares and serves one token everywhere
    (PERF.md, section 2), which no comparison of served tokens can judge.
    So the embedding is drawn at unit scale and the two projections that
    write into the residual stream (``wo``, ``w2``) at 1/sqrt(2L) of
    theirs, so that the stream keeps the token at any depth; ``w1`` at 0.3
    of its, so that the MLP's pre-activations (std 0.3) leave GELU little
    mean to add alike at every position; and ``wq`` at three times its, so
    that scores of std about 3 make each position read a few lines of its
    context rather than their mean, and the token depends on them."""
    d, f, L, H, K, Dh, V = m.d, m.f, m.L, m.H, m.K, m.Dh, m.V
    res = (2 * L) ** -0.5
    out = {
        "embed": ((V, d), 0.0, 1.0),
        "norm1": ((L, d), 1.0, 0.1),
        "wq": ((L, d, H, Dh), 0.0, 3 * d ** -0.5),
        "wk": ((L, d, K, Dh), 0.0, d ** -0.5),
        "wv": ((L, d, K, Dh), 0.0, d ** -0.5),
        "wo": ((L, H, Dh, d), 0.0, (H * Dh) ** -0.5 * res),
        "norm2": ((L, d), 1.0, 0.1),
        "w1": ((L, d, f), 0.0, 0.3 * d ** -0.5),
        "w2": ((L, f, d), 0.0, f ** -0.5 * res),
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


def check_program(cfg: dict, m: Dims, program_cfg):
    """The program's configuration with the file's rope_theta, checked
    against every size the file states; exits where they differ."""
    import dataclasses
    pc = dataclasses.replace(program_cfg, rope_theta=m.rope_theta)
    got = (pc.family, pc.d_model, pc.d_ff, pc.num_layers, pc.num_heads,
           pc.num_kv_heads, pc.head_dim, pc.vocab_size, pc.qkv_bias,
           float(pc.rope_theta), pc.sliding_window)
    want = ("dense", m.d, m.f, m.L, m.H, m.K, m.Dh, m.V, m.qkv_bias,
            m.rope_theta, cfg.get("sliding_window"))
    if got != want:
        raise SystemExit(f"{cfg['arch']}: the program's configuration {got} "
                         f"is not the file's {want}")
    return pc


# -------------------------------------------------------------- counts ----

def _fold(runs) -> tuple:
    """(tokens, sum of their context lengths), in integers."""
    tokens = ctx = 0
    for first, n in runs:
        tokens += n
        ctx += n * first + n * (n - 1) // 2
    return tokens, ctx


def matmul_params(m: Dims) -> int:
    """Weights that multiply each token once: every projection of every
    layer and the output head (the embedding is a lookup)."""
    attn = m.d * m.H * m.Dh + 2 * m.d * m.K * m.Dh + m.H * m.Dh * m.d
    mlp = (3 if m.mlp == "swiglu" else 2) * m.d * m.f
    return m.L * (attn + mlp) + m.V * m.d


def model_flops(m: Dims, runs) -> float:
    """Forward FLOPs of the tokens of ``runs``: 2 per weight per token, and
    4 * H * Dh per layer per attended line (scores and the weighted
    sum)."""
    tokens, ctx = _fold(runs)
    return 2.0 * matmul_params(m) * tokens + 4.0 * m.L * m.H * m.Dh * ctx


def paged_decode_work(m: Dims, runs) -> tuple:
    """(FLOPs, HBM bytes) the paged decode attention needs for the decoded
    tokens of ``runs``: every layer reads each live bfloat16 K and V line
    once and each token's query and output once, and computes its scores
    and weighted sum."""
    tokens, ctx = _fold(runs)
    flops = 4.0 * m.L * m.H * m.Dh * ctx
    kv = m.L * 2 * m.K * m.Dh * 2 * ctx
    qo = m.L * 2 * m.H * m.Dh * 2 * tokens
    return flops, float(kv + qo)


# ----------------------------------------------------------- CPU tests ----

def tiny(cfg: dict) -> dict:
    """The keys that cut ``cfg`` to the program's ``reduced()`` preset of
    its architecture: 2 layers, 256 wide, 4 heads of 64, vocabulary 512;
    MHA stays MHA (4 key/value heads), GQA keeps 2.  The check's limit at
    that size is set as the full size's is (PERF.md, section 2): there the
    program reads 0.0-0.0182 and the float8 control 0.0796-0.1930, where
    the full size's limit would pass the control."""
    kv = 4 if cfg["num_key_value_heads"] == cfg["num_attention_heads"] else 2
    return {"hidden_size": 256, "intermediate_size": 512,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": kv, "vocab_size": 512,
            "check": dict(cfg["check"], max_logit_gap=0.045)}

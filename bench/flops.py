"""Operations and bytes the algorithm needs, counted from shapes.

These are the yardstick's counts, not the program's: a later change to how
the program computes cannot change them.
"""
from __future__ import annotations

from bench.model_dims import Dims


def matmul_params(m: Dims) -> int:
    """Weights that multiply each token once: every projection of every
    layer and the output head (the embedding is a lookup)."""
    attn = m.d * m.H * m.Dh + 2 * m.d * m.K * m.Dh + m.H * m.Dh * m.d
    mlp = (3 if m.mlp == "swiglu" else 2) * m.d * m.f
    return m.L * (attn + mlp) + m.V * m.d


def model_flops(m: Dims, tokens: int, ctx_sum: int) -> float:
    """Forward FLOPs of ``tokens`` tokens whose context lengths (the lines
    each attends over, itself included) sum to ``ctx_sum``: 2 per weight
    per token, and 4 * H * Dh per layer per attended line (scores and the
    weighted sum)."""
    return 2.0 * matmul_params(m) * tokens + 4.0 * m.L * m.H * m.Dh * ctx_sum


def paged_decode_work(m: Dims, tokens: int, ctx_sum: int) -> tuple:
    """(FLOPs, HBM bytes) the paged decode attention needs for ``tokens``
    decoded tokens over ``ctx_sum`` live KV lines in all: every layer reads
    each live bfloat16 K and V line once and each token's query and output
    once, and computes its scores and weighted sum."""
    flops = 4.0 * m.L * m.H * m.Dh * ctx_sum
    kv = m.L * 2 * m.K * m.Dh * 2 * ctx_sum
    qo = m.L * 2 * m.H * m.Dh * 2 * tokens
    return flops, float(kv + qo)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip needs for the work: the larger of its
    compute and its memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])

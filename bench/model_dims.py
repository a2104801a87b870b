"""The sizes the benchmark computes with, read from a configuration file.

One place maps the published config keys to the few numbers the weight
generator, the plain reference and the FLOP counts need, so none of them
reads the program's own configuration.
"""
from __future__ import annotations

from dataclasses import dataclass


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
    H = cfg["num_attention_heads"]
    return Dims(
        d=cfg["hidden_size"], f=cfg["intermediate_size"],
        L=cfg["num_hidden_layers"], H=H, K=cfg["num_key_value_heads"],
        Dh=cfg["hidden_size"] // H, V=cfg["vocab_size"],
        mlp=_ACT[cfg["hidden_act"]],
        qkv_bias=bool(cfg.get("use_qkv_bias", False)),
        rope_theta=float(cfg["rope_theta"]),
        eps=float(cfg.get("layer_norm_eps", cfg.get("norm_epsilon"))))

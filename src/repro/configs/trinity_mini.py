"""Trinity-Mini (Arcee, ``afmoe``) — sparse experts beside gated QK-norm
attention, three sliding-window layers to each full one.

[hf:arcee-ai/Trinity-Mini config.json] 32L d_model=2048 32H (GQA kv=4,
head 128), 2 leading dense SwiGLU layers of 6144, then 128 routed SwiGLU
experts of 1024 (top-8, sigmoid scores, a selection-only expert bias,
renormalised and scaled by 2.826) and one shared expert; sliding window
2048 with rotary only there, full layers without position encoding;
sandwich norms; embeddings scaled by sqrt(d) (muP); vocab 200192, untied.
"""
from dataclasses import replace

from repro.configs.base import ModelConfig, MoEConfig

_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)

CONFIG = ModelConfig(
    name="trinity-mini",
    family="moe",
    source="hf:arcee-ai/Trinity-Mini",
    num_layers=32,
    d_model=2048,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=6144,                           # the leading dense layers
    vocab_size=200192,
    rope_theta=1e4,
    sliding_window=2048,
    layer_types=_PERIOD * 8,
    rope_full_layers=False,
    qk_norm=True,
    attn_gate=True,
    sandwich_norm=True,
    embed_scale=True,
    moe=MoEConfig(num_experts=128, top_k=8, num_shared=1, d_ff=1024,
                  every=1, first_dense=2, score_func="sigmoid",
                  route_scale=2.826, expert_bias=True),
)


def reduced() -> ModelConfig:
    """One sliding layer with a dense FFN, one full layer with experts;
    the window (128) covers every CPU test's cache."""
    return replace(
        CONFIG, num_layers=2, d_model=256, num_heads=4, num_kv_heads=2,
        head_dim=64, d_ff=512, vocab_size=512, sliding_window=128,
        layer_types=("sliding_attention", "full_attention"),
        moe=replace(CONFIG.moe, num_experts=4, top_k=2, d_ff=128,
                    first_dense=1, group_size=64),
    )

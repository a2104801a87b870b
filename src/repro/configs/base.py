"""Config system: model architecture + input shape + run (parallelism) configs.

Every assigned architecture gets one ``<arch>.py`` in this package exporting
``CONFIG: ModelConfig`` with the exact published dimensions, plus a
``reduced()`` variant used by CPU smoke tests (2 layers, d_model<=512,
<=4 experts — same family, same code paths).
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field, replace
from typing import Optional


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0          # routed experts
    top_k: int = 0
    num_shared: int = 0           # always-on shared experts (qwen2-moe)
    d_ff: int = 0                 # per-expert hidden dim
    every: int = 1                # MoE FFN every `every` layers (others dense)
    capacity_factor: float = 1.25
    group_size: int = 1024        # GShard dispatch group size (tokens)
    router_z_coef: float = 1e-3   # router z-loss
    balance_coef: float = 1e-2    # load-balance aux loss
    # routing at inference (``models.moe.moe_dropless``): scores are the
    # softmax or the sigmoid of the float32 router logits; a selection-only
    # per-expert bias (``expert_bias``) may shift which k are chosen; the
    # chosen scores are renormalised over the k and scaled
    first_dense: int = 0          # leading layers with a dense FFN (d_ff)
    score_func: str = "softmax"   # softmax | sigmoid
    route_scale: float = 1.0
    expert_bias: bool = False
    # this chip's share of each expert layer: experts [first, first + n)
    # of ``num_experts``; None holds every expert
    experts_held: Optional[tuple[int, int]] = None

    def held(self) -> tuple[int, int]:
        """(first expert held, number held)."""
        return self.experts_held or (0, self.num_experts)


@dataclass(frozen=True)
class SSMConfig:
    state: int = 128              # N — SSD state size
    head_dim: int = 64            # P — channels per SSD head
    expand: int = 2               # d_inner = expand * d_model
    conv_width: int = 4
    chunk: int = 256              # SSD chunk length

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def num_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | vlm | audio
    source: str                   # citation from the assignment table
    num_layers: int
    d_model: int
    num_heads: int                # query heads (0 for attn-free)
    num_kv_heads: int
    d_ff: int                     # dense FFN hidden dim (0 if pure-MoE FFN)
    vocab_size: int
    head_dim: int = 128
    # attention details
    rope_theta: float = 1e4
    qkv_bias: bool = False
    pos_embedding: str = "rope"   # rope | sinusoidal
    sliding_window: Optional[int] = None  # None = full causal
    # per-layer attention kinds, one per layer: "sliding_attention" layers
    # take ``sliding_window``, "full_attention" layers none; None makes
    # every layer as ``sliding_window`` says
    layer_types: Optional[tuple[str, ...]] = None
    rope_full_layers: bool = True  # False: rotary on sliding layers only
    qk_norm: bool = False         # RMSNorm of q and k per head
    attn_gate: bool = False       # attention output * sigmoid(x Wg)
    sandwich_norm: bool = False   # RMSNorm of each sublayer's output too
    embed_scale: bool = False     # embeddings * sqrt(d_model) (muP)
    # FFN
    mlp_type: str = "swiglu"      # swiglu | gelu | relu2
    # mixer schedule (hybrid): 1 attention layer per `attn_every` layers,
    # the rest SSM.  attn_every=1 => all attention; 0 => attention-free.
    attn_every: int = 1
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # modality frontend stub: none | vision | audio
    frontend: str = "none"
    num_codebooks: int = 1        # audio (EnCodec streams)
    # numerics
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"       # compute dtype
    param_dtype: str = "float32"  # storage dtype
    tie_embeddings: bool = False

    # ---- derived ----
    def layer_kinds(self) -> list[str]:
        """Mixer kind per layer: 'attn' or 'ssm'."""
        if self.attn_every == 0:
            return ["ssm"] * self.num_layers
        kinds = []
        for i in range(self.num_layers):
            kinds.append("attn" if i % self.attn_every == 0 else "ssm")
        return kinds

    def ffn_kinds(self) -> list[str]:
        """FFN kind per layer: 'dense' or 'moe'."""
        if self.moe is None:
            return ["dense"] * self.num_layers
        return [
            "moe" if (i % self.moe.every == self.moe.every - 1
                      and i >= self.moe.first_dense) else "dense"
            for i in range(self.num_layers)
        ]

    def attn_kinds(self) -> list[tuple[Optional[int], bool]]:
        """(window or None, rotary or not) of each layer's attention."""
        rope = self.pos_embedding == "rope"
        if self.layer_types is None:
            return [(self.sliding_window, rope)] * self.num_layers
        assert len(self.layer_types) == self.num_layers, self.layer_types
        return [(self.sliding_window, rope) if t == "sliding_attention"
                else (None, rope and self.rope_full_layers)
                for t in self.layer_types]

    def with_sliding_window(self, window: int) -> "ModelConfig":
        return replace(self, sliding_window=window)

    def param_count(self) -> int:
        """Total parameter count (exact, mirrors the spec tree)."""
        from repro.models.spec import count_params
        return count_params(self)

    def active_param_count(self) -> int:
        from repro.models.spec import count_params
        return count_params(self, active_only=True)


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                     # train | prefill | decode


INPUT_SHAPES: dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}

# Sliding-window size used for the long_500k variant of full-attention archs.
LONG_CONTEXT_WINDOW = 8_192


@dataclass(frozen=True)
class RunConfig:
    """Parallelism + execution knobs for one (arch x shape x mesh) run."""
    strategy: str = "fsdp_tp"     # dp | tp | fsdp | fsdp_tp | pp
    zero_stage: int = 3           # 1 | 2 | 3 (ZeRO partitioning depth)
    microbatches: int = 1         # gradient-accumulation microbatches
    remat: str = "layer"          # none | layer | full
    opt_state_dtype: str = "float32"
    use_pallas: bool = False      # Pallas kernels (TPU / interpret only)
    seq_shard_decode: bool = False  # shard decode KV cache along sequence
    # Beyond-paper (§Perf): when attention heads don't divide the model
    # axis, shard the SEQUENCE dim of activations over `model` instead of
    # replicating attention compute (context/sequence parallelism).  KV is
    # small under GQA, so the per-layer K/V all-gather is cheap against a
    # model_axis-fold compute replication.
    seq_parallel: bool = False
    # Beyond-paper (§Perf): cast f32 master params to bf16 BEFORE the
    # ZeRO-3 all-gather (halves FSDP gather bytes; grads still f32 at the
    # optimizer).
    gather_bf16: bool = False
    # Beyond-paper (§Perf): with TP-inside-expert (experts % model != 0),
    # don't pin the expert output to full d_model — let the w2 partial
    # sums flow through the (linear) combine einsum so the all-reduce
    # lands on the (G, gs, d) tokens instead of the ~5x larger
    # (G, E, C, d) capacity tensor.
    moe_defer_combine: bool = False
    # Beyond-paper (§Perf): cross-data gradient reductions in bf16 (the
    # local f32 accumulator is unchanged) — halves the per-microbatch
    # weight-grad all-reduce, the dominant collective on MoE trains.
    grad_reduce_bf16: bool = False
    # Unroll layer-group and microbatch loops into straight-line HLO.
    # Production keeps scans (flat compile time); the dry-run cost probes
    # unroll because XLA's cost_analysis counts a while body ONCE — see
    # launch/dryrun.py probe machinery.
    unroll: bool = False


ARCH_IDS = [
    "jamba-1.5-large-398b",
    "starcoder2-3b",
    "pixtral-12b",
    "qwen2-moe-a2.7b",
    "musicgen-large",
    "qwen2-7b",
    "stablelm-3b",
    "mamba2-780m",
    "dbrx-132b",
    "minitron-4b",
    "trinity-mini",
]


def _module_name(arch_id: str) -> str:
    return arch_id.replace("-", "_").replace(".", "_")


def get_config(arch_id: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_module_name(arch_id)}")
    return mod.CONFIG


def get_reduced_config(arch_id: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_module_name(arch_id)}")
    return mod.reduced()


def default_run_config(cfg: ModelConfig, shape: InputShape,
                       batch_divisor: int = 32) -> RunConfig:
    """Sensible production defaults per (arch, shape).

    ``batch_divisor`` = product of batch-carrying mesh axes (pod*data); the
    per-microbatch batch must stay divisible by it so the batch dim shards
    cleanly at every microbatch step.
    """
    micro = 1
    if shape.kind == "train":
        # keep per-device live activations ~ few GB: scale microbatches with
        # d_model * layers (see DESIGN.md memory napkin math).
        cost = cfg.d_model * cfg.num_layers
        if cost >= 400_000:
            micro = 16
        elif cost >= 150_000:
            micro = 8
        elif cost >= 64_000:
            micro = 4
        else:
            micro = 2
        micro = max(1, min(micro, shape.global_batch // batch_divisor))
    opt_dtype = "bfloat16" if cfg.param_count() > 100e9 else "float32"
    return RunConfig(microbatches=micro, opt_state_dtype=opt_dtype)


def shape_for(cfg: ModelConfig, shape: InputShape) -> ModelConfig:
    """Adapt an arch config to an input shape (long-context window)."""
    if shape.name == "long_500k" and cfg.attn_every != 0:
        # sub-quadratic requirement: dense/hybrid archs use sliding window.
        if cfg.sliding_window is None or cfg.sliding_window > LONG_CONTEXT_WINDOW:
            return cfg.with_sliding_window(LONG_CONTEXT_WINDOW)
    return cfg

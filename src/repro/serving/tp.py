"""Serving tensor parallelism: the mesh plan behind a TP DecodeEngine.

The training side already maps logical parameter axes to mesh axes
(``core/sharding.py``); this module is the *serving* counterpart, with
two differences that keep decode fast and bit-identical:

* **Sharding is explicit, not GSPMD.**  The decode hot paths run Pallas
  kernels that the partitioner cannot split, so every jitted engine
  program is wrapped in ``shard_map`` over the mesh's ``model`` axis:
  attention shards along KV-head groups (the GQA flash-decode kernel's
  grid ``(B, K, nk)`` simply sees ``K/tp`` heads per shard and runs
  unchanged), the MLP shards ``d_ff``, and each layer pays exactly one
  ``psum`` at the attention output projection and one at the MLP
  down-projection (``core.actshard.maybe_psum``).  Embedding, LM head
  and norms stay replicated — every shard computes FULL logits, so
  on-device sampling/argmax needs no collective and greedy decode is
  token-for-token identical to TP=1.

* **Reductions run in float32.**  The partial contraction at each psum
  point keeps its f32 accumulator through the reduction
  (``core.actshard.tp_will_reduce``) and rounds once afterwards, so f32
  models decode token-for-token identically at any TP degree.  bf16
  models keep ~1-ulp logit noise from the reassociated sum — standard
  for TP serving — which can flip an argmax whose top-2 logits collide
  in bf16; strict cross-TP reproducibility asks for ``dtype="float32"``.

* **Divisibility falls back, never crashes.**  A head/ffn count that
  does not divide the mesh axis leaves that block replicated (its psum
  point disabled — a reduction over replicas would multiply by ``tp``)
  and records a human-readable notice.  SSM state and MoE experts are
  not sharded by the serving path yet and fall back the same way.

The paged KV pool shards with attention: each device holds the
``(G, num_pages, K/tp, page_size, Dh)`` slice of every page, page *ids*
stay a single host-side space (one logical page = one id = ``tp``
device-local slices), so the allocator, prefix-cache refcounts and COW
forks remain shard-agnostic host logic.  Admission sees the pool
through :class:`repro.models.paging.ShardedAllocatorView`'s per-shard
budget vectors.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.actshard import tp_reduce_scope
from repro.core.sharding import serving_param_pspec
from repro.models.model import cache_kv_head_dim
from repro.models.spec import ParamSpec, layer_schedule, model_spec

#: mesh axis serving TP shards over
TP_AXIS = "model"


@dataclass
class TPPlan:
    """Resolved tensor-parallel plan for one engine instance."""
    mesh: Optional[Mesh]
    tp: int = 1
    axis: str = TP_AXIS
    shard_attn: bool = False
    shard_mlp: bool = False
    #: divisibility/compat fallbacks, human-readable (sdiag, tests)
    notices: list = field(default_factory=list)

    @property
    def active(self) -> bool:
        """True when any block is actually sharded — otherwise the
        engine skips shard_map entirely and runs exactly like TP=1."""
        return (self.mesh is not None and self.tp > 1
                and (self.shard_attn or self.shard_mlp))

    def devices(self) -> list:
        if self.mesh is None:
            return []
        return list(self.mesh.devices.flat)

    def psums_per_token(self, cfg: ModelConfig) -> dict:
        """Cross-shard reductions ONE decode step pays, by kind — the
        sdiag "psum count per dispatch" line (a ``decode_n`` chunk of N
        tokens pays N times this)."""
        sched = layer_schedule(cfg)
        attn = sum(1 for mixer, _ in sched if mixer == "attn")
        mlp = sum(1 for _, ffn in sched if ffn == "dense")
        return {"attn_out": attn if self.shard_attn else 0,
                "mlp_out": mlp if self.shard_mlp else 0}

    def describe(self, cfg: ModelConfig) -> str:
        if self.mesh is None or self.tp <= 1:
            return "tp=1 (single shard)"
        parts = []
        if self.shard_attn:
            parts.append(f"attn(heads {cfg.num_heads}->"
                         f"{cfg.num_heads // self.tp}/shard, kv "
                         f"{cfg.num_kv_heads}->"
                         f"{cfg.num_kv_heads // self.tp}/shard)")
        if self.shard_mlp:
            parts.append(f"mlp(ffn {cfg.d_ff}->"
                         f"{cfg.d_ff // self.tp}/shard)")
        if not parts:
            parts.append("replicated (no shardable dims)")
        return f"tp={self.tp} " + ", ".join(parts)


def plan_tp(cfg: ModelConfig, mesh: Optional[Mesh]) -> TPPlan:
    """Resolve which blocks shard over the mesh's ``model`` axis.

    The divisibility policy mirrors ``core/sharding.py``: a dimension
    shards only when the axis size divides it; otherwise that block
    replicates, with a notice instead of a crash.
    """
    if mesh is None:
        return TPPlan(mesh=None)
    tp = int(mesh.shape[TP_AXIS]) if TP_AXIS in mesh.axis_names else 1
    plan = TPPlan(mesh=mesh, tp=tp)
    if tp <= 1:
        return plan
    if cfg.ssm is not None:
        plan.notices.append(
            f"cfg.ssm set: SSM state is not head-sharded yet — "
            f"attention/SSM blocks replicate across tp={tp}")
    elif cfg.num_kv_heads % tp or cfg.num_heads % tp:
        plan.notices.append(
            f"kv_heads={cfg.num_kv_heads}, heads={cfg.num_heads} not "
            f"divisible by tp={tp}: attention replicates (GQA KV-head "
            f"groups must split evenly across shards)")
    else:
        plan.shard_attn = True
    if cfg.moe is not None:
        plan.notices.append(
            f"cfg.moe set: experts are not sharded by serving TP yet — "
            f"MoE blocks replicate across tp={tp}")
    elif cfg.d_ff % tp:
        plan.notices.append(
            f"d_ff={cfg.d_ff} not divisible by tp={tp}: MLP replicates")
    else:
        plan.shard_mlp = True
    if not (plan.shard_attn or plan.shard_mlp):
        plan.notices.append(
            f"nothing shardable: running replicated on 1 of {tp} shards")
    return plan


# -------------------------------------------------------- partition specs ----

def _shard_axes(plan: TPPlan) -> tuple:
    axes = ()
    if plan.shard_attn:
        axes += ("heads", "kv_heads")
    if plan.shard_mlp:
        axes += ("ffn",)
    return axes


def param_pspecs(cfg: ModelConfig, plan: TPPlan):
    """PartitionSpec pytree matching the parameter pytree (shard_map
    ``in_specs``)."""
    axes = _shard_axes(plan)

    def build(tree):
        if isinstance(tree, ParamSpec):
            return serving_param_pspec(tree, plan.tp, axes, axis=plan.axis)
        if isinstance(tree, dict):
            return {k: build(v) for k, v in tree.items()}
        if isinstance(tree, list):
            return [build(v) for v in tree]
        raise TypeError(type(tree))

    return build(model_spec(cfg))


def cache_pspec(plan: TPPlan, cfg: Optional[ModelConfig] = None,
                paging: bool = False) -> P:
    """PartitionSpec for ONE KV-cache leaf of one layout.

    ``paging=True``: the paged pool ``(G, pages, K, ps, Dh)``, kv heads
    at dim 2.  ``paging=False``: dense rows ``(G, B, slots, K, Dh)`` and
    the one-request prefill output and chunk slices ``(G, B, S, K, Dh)``
    that are scattered into either cache, kv heads at dim 3
    (:func:`repro.models.model.cache_kv_head_dim`).  Used as a pytree
    *prefix* over a whole ``{"layers": [{"k","v"}]}`` cache of that
    layout (SSM leaves never co-exist with ``shard_attn``)."""
    if not plan.shard_attn:
        return P()
    kv_dim = ((2 if paging else 3) if cfg is None
              else cache_kv_head_dim(cfg, paging=paging))
    spec = [None] * 5
    spec[kv_dim] = plan.axis
    return P(*spec)


def param_shardings(cfg: ModelConfig, plan: TPPlan):
    """NamedSharding pytree for placing the params on the mesh."""
    mesh = plan.mesh
    axes = _shard_axes(plan)

    def build(tree):
        if isinstance(tree, ParamSpec):
            return NamedSharding(mesh, serving_param_pspec(
                tree, plan.tp, axes, axis=plan.axis))
        if isinstance(tree, dict):
            return {k: build(v) for k, v in tree.items()}
        if isinstance(tree, list):
            return [build(v) for v in tree]
        raise TypeError(type(tree))

    return build(model_spec(cfg))


def cache_shardings(cache, plan: TPPlan,
                    cfg: Optional[ModelConfig] = None, paging: bool = False):
    """NamedSharding pytree for placing the engine cache (the paged pool
    when ``paging``, else dense rows) on the mesh."""
    spec = cache_pspec(plan, cfg, paging=paging)

    def leaf(x):
        return NamedSharding(plan.mesh, spec if x.ndim == 5 else P())

    return jax.tree.map(leaf, cache)


# ------------------------------------------------------- shard_map wrapper ----

def wrap(plan: TPPlan, fn, in_specs: Sequence, out_specs,
         donate: tuple = ()):
    """jit(shard_map(fn)) over the plan's mesh.

    ``in_specs``/``out_specs`` are per-argument PartitionSpecs (pytree
    prefixes — a bare ``P()`` replicates a whole params/cache subtree).
    The body installs :func:`repro.core.actshard.tp_reduce_scope` so the
    model's ``maybe_psum`` points emit cross-shard reductions exactly
    where the plan sharded; ``check_vma=False`` because the Pallas
    decode kernels define no replication rules — the ``P()`` out_specs
    are correct by construction (full logits per shard after the psums).

    ``jit`` wraps *outside* so donation and the engine's
    ``_cache_size()`` compile counters keep working unchanged.
    """
    if not plan.active:
        if donate:
            return jax.jit(fn, donate_argnums=donate)
        return jax.jit(fn)

    @functools.wraps(fn)
    def body(*args):
        with tp_reduce_scope(plan.axis, plan.shard_attn, plan.shard_mlp):
            return fn(*args)

    mapped = shard_map(body, mesh=plan.mesh, in_specs=tuple(in_specs),
                       out_specs=out_specs, check_vma=False)
    if donate:
        return jax.jit(mapped, donate_argnums=donate)
    return jax.jit(mapped)

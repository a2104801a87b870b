"""Pipeline parallelism (paper §7.1 "PipelineParallel") — GPipe-style
microbatch schedule over a ``pipe`` mesh axis, written with ``shard_map`` +
``lax.ppermute``.

The model's stacked layer groups are split contiguously across stages
(vertical split, exactly the paper's description: "the model is split up
vertically (layer-level) across multiple GPUs").  Each tick every stage runs
its slice on one microbatch (masked out during fill/drain bubbles) and
passes activations to the next stage over ``ppermute`` — the TPU analogue of
NCCL P2P sends.  Differentiable end-to-end, so training works through it.

This is a selectable strategy demonstrated on small meshes in tests and
examples; the production dry-run default composes FSDP x TP instead (same
choice most TPU deployments make — PP earns its bubble cost only on very
deep models over slow inter-node links).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def split_stages(stacked, n_stages: int):
    """Check the stacked-layer-group pytree divides across stages."""
    def check(x):
        assert x.shape[0] % n_stages == 0, (
            f"layer groups {x.shape[0]} not divisible by {n_stages} stages")
        return x
    return jax.tree.map(check, stacked)


def pipeline_apply(stage_fn, stacked_params, x_micro, mesh: Mesh,
                   axis: str = "pipe"):
    """Run microbatches through the pipeline.

    stage_fn(stage_params, x) -> y          (one stage's layer slice)
    stacked_params: leaves (n_groups, ...) — sharded over `axis` on dim 0
    x_micro: (n_micro, mb, S, d)            — replicated across `axis`
    Returns (n_micro, mb, S, d) from the last stage (replicated).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    split_stages(stacked_params, n_stages)

    p_params = jax.tree.map(lambda _: P(axis), stacked_params)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(p_params, P()),
        out_specs=P(),
        check_vma=False)
    def run(params_local, x_local):
        my = jax.lax.axis_index(axis)
        is_first = my == 0
        is_last = my == n_stages - 1
        carry = jnp.zeros_like(x_local[0])
        outs = jnp.zeros_like(x_local)
        for t in range(n_micro + n_stages - 1):
            mb = t - my                                   # my microbatch idx
            active = jnp.logical_and(mb >= 0, mb < n_micro)
            mb_c = jnp.clip(mb, 0, n_micro - 1)
            inp = jnp.where(is_first,
                            jax.lax.dynamic_index_in_dim(
                                x_local, mb_c, keepdims=False),
                            carry)
            y = stage_fn(params_local, inp)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage records its finished microbatch
            write = jnp.logical_and(is_last, active)
            upd = jnp.where(write, y, jax.lax.dynamic_index_in_dim(
                outs, mb_c, keepdims=False))
            outs = jax.lax.dynamic_update_index_in_dim(outs, upd, mb_c, 0)
            # hand activations to the next stage
            carry = jax.lax.ppermute(y, axis, perm)
        # broadcast last stage's buffer to everyone
        outs = jax.lax.psum(
            jnp.where(is_last, outs, jnp.zeros_like(outs)), axis)
        return outs

    return run(stacked_params, x_micro)


def make_pipeline_mesh(n_stages: int, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()[:n_stages]
    # Auto, not make_mesh's default Explicit axes: the stage pytree is laid
    # out with NamedSharding and moved by shard_map, not by sharding-in-types
    return jax.make_mesh((n_stages,), ("pipe",), devices=devices,
                         axis_types=(AxisType.Auto,))

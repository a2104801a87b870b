"""Allocation -> jax.Mesh bridge: the point where the SLURM layer hands a
chip grid to the JAX layer.

A job allocated N hosts x 4 chips owns a contiguous chip rectangle (the
scheduler enforces host-rect contiguity).  This module maps that rectangle
onto however many JAX devices actually exist in the process:

* real deployment — one process per host, `jax.devices()` = the job's chips;
* this container — CPU devices (1, or 512 under the dry-run XLA flag), and
  the bridge folds the logical (data, model) mesh onto them.

The mesh axes follow DESIGN.md: ``("data", "model")`` within a pod,
``("pod", "data", "model")`` across pods.
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from repro.cluster.cluster import Cluster
from repro.cluster.job import Job


def allocation_chip_count(cluster: Cluster, job: Job,
                          gres_name: str = "tpu") -> int:
    return sum(cluster.nodes[nm].gres.get(gres_name, 0)
               for nm in job.nodes_alloc)


def factor_mesh(n_chips: int, model_parallel: int) -> tuple[int, int]:
    """(data, model) shape for n_chips total."""
    model = math.gcd(model_parallel, n_chips)
    return n_chips // model, model


def mesh_for_job(cluster: Cluster, job: Job, model_parallel: int = 1,
                 devices=None) -> Mesh:
    """Build the (data, model) mesh for a running job's allocation."""
    assert job.nodes_alloc, f"job {job.job_id} has no allocation"
    n_chips = allocation_chip_count(cluster, job)
    devices = list(jax.devices()) if devices is None else list(devices)
    if len(devices) < n_chips:
        # container fallback: fold the logical mesh onto available devices
        n_chips = max(1, (len(devices) // 1))
        n_chips = 2 ** int(math.log2(n_chips))
    data, model = factor_mesh(n_chips, model_parallel)
    dev = np.asarray(devices[:data * model]).reshape(data, model)
    return Mesh(dev, ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))

"""Mesh construction for the SVM distributed layers and the dry runs.

Every mesh in the repo is built by ``make_mesh``: ``Auto`` axis types,
so jit's sharding propagation (not the array types) decides layouts.
``jax.make_mesh`` defaults to ``Explicit`` axes, under which slicing a
padded sample-sharded result (``alpha[:n]``) raises ``ShardingTypeError``.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  512 chips as (pod=2, data=16, model=16) — the "pod" axis is
the slowest (DCN/ICI-sparse) dimension and only ever carries
data-parallel traffic (gradient all-reduce), matching how real multi-pod
slices are scheduled.

FUNCTIONS, not module constants: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with ``Auto`` axis types on every axis.
    ``devices`` picks the devices (default: all visible ones)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(n_workers: int = 1, axis: str = "workers"):
    """1-D task-parallel mesh over the first ``n_workers`` visible
    devices — the OvO "MPI" layer (``SVC(mesh=..., shard="task")``)."""
    return make_mesh((n_workers,), (axis,),
                     devices=jax.devices()[:n_workers])


def make_shard_mesh(n_shards: int | None = None, axis: str = "shards"):
    """1-D mesh for the data-parallel single-problem SVM path
    (``smo.sharded_binary_smo`` / ``SVC(shard="data")``): the named axis
    carries the SAMPLE dimension of one QP, not independent tasks.

    ``n_shards=None`` takes every visible device. An explicit count above
    the visible device count raises instead of silently under-sharding.
    """
    n_avail = len(jax.devices())
    if n_shards is None:
        n_shards = n_avail
    if n_shards > n_avail:
        raise ValueError(
            f"requested {n_shards} shards but only {n_avail} devices are "
            f"visible (force more with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N before "
            f"jax initializes)")
    return make_mesh((n_shards,), (axis,),
                     devices=jax.devices()[:n_shards])

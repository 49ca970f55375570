"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single pod: (data=16, model=16) = 256 chips of a
v5e pod. Multi-pod: (pod=2, data=16, model=16) = 512 chips; clients shard
across pods, params replicate across pods (hybrid FSDP), so only the
DP-FedAvg round-sum block partials cross the inter-pod links — the engine's
canonical cross-pod reduction (`repro.fl.reduction.fold_pods`) folds each
pod's blocks pod-locally and sends only the pod partials over the ``pod``
axis.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax

from repro.configs.base import MULTI_POD, SINGLE_POD, MeshConfig

# Axis layouts make_cohort_mesh accepts: the cohort's batch axes only (the
# 1-D sim layout, or the multi-pod batch slice of the production mesh).
COHORT_AXES = (("data",), ("pod", "data"))


def make_production_mesh(*, multi_pod: bool = False,
                         shape: Optional[Tuple[int, ...]] = None):
    """Concrete production mesh: ``(data, model)`` or, with ``multi_pod``,
    ``(pod, data, model)``. ``shape`` overrides the chip counts (same axis
    order) for test-scale meshes on forced host devices; it must keep one
    entry per axis."""
    cfg = mesh_config(multi_pod=multi_pod)
    shape = cfg.shape if shape is None else tuple(shape)
    if len(shape) != len(cfg.axes):
        raise ValueError(
            f"make_production_mesh: shape {shape} must have one entry per "
            f"axis {cfg.axes}")
    # the sharding rules (sharding.specs, launch.steps) are written for Auto
    # axes; jax.make_mesh would otherwise make every axis Explicit
    return jax.make_mesh(shape, cfg.axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MULTI_POD if multi_pod else SINGLE_POD


def make_cohort_mesh(mesh_cfg: MeshConfig):
    """Concrete device mesh for the simulation engine's sharded cohort: the
    1-D ``(data,)`` sim layout or the 2-D ``(pod, data)`` batch slice of the
    multi-pod production mesh.

    Takes the first ``n_devices`` local devices (CPU included — CI forces
    host devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)
    and lays them out over the config's batch axes. The engine owns the
    cross-pod round reduction on this mesh (pod-local canonical block folds;
    only the pod partials cross the ``pod`` axis — see `repro.fl.engine`);
    model-parallel axes stay the launch layer's job, so a config carrying a
    ``model`` axis is rejected here.
    """
    if tuple(mesh_cfg.axes) not in COHORT_AXES:
        raise ValueError(
            "make_cohort_mesh expects a cohort MeshConfig over the batch "
            f"axes only — ('data',) or ('pod', 'data') — got {mesh_cfg}. "
            "Model-parallel axes are the launch layer's job; build the "
            "cohort slice with sharding.specs.sim_mesh_config(num_shards, "
            "num_pods).")
    n = mesh_cfg.n_devices
    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"cohort mesh needs {n} devices but only {len(devices)} are "
            "visible. On CPU, force host devices with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} (set it before "
            "importing jax).")
    return jax.sharding.Mesh(
        np.asarray(devices[:n]).reshape(mesh_cfg.shape), mesh_cfg.axes)

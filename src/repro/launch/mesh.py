"""Production meshes. Functions, not module constants — importing this module
never touches jax device state (required by smoke tests that must see 1 CPU
device). Construction goes through the compat layer, which spells the
typed mesh axes."""
from __future__ import annotations

from typing import Optional

import numpy as np

import jax
from jax.sharding import Mesh

from repro.kernels import compat


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 (data, model) single pod; 2x16x16 (pod, data, model) multi-pod.

    One pod = 256 chips (TPU v5e-256); the pod axis crosses DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes, axis_types=("auto",) * len(axes))


def make_shard_mesh(P: Optional[int] = None) -> Mesh:
    """Flat ("shards",) mesh over the first P devices — the mesh the sharded
    constraint-checking backends (core/engine.py) run the full prune pipeline
    on. Defaults to every device this process sees (e.g. 8 under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``)."""
    devs = jax.devices()
    P = len(devs) if P is None else P
    if P > len(devs):
        raise ValueError(f"asked for {P} shards but only {len(devs)} devices")
    return compat.make_mesh(
        (P,), ("shards",), axis_types=("auto",),
        devices=np.asarray(devs[:P]))


def make_local_mesh() -> Mesh:
    """Whatever this host has — used by examples and tests."""
    n = len(jax.devices())
    return compat.make_mesh((n, 1), ("data", "model"))


def mesh_chips(mesh: Mesh) -> int:
    return mesh.devices.size

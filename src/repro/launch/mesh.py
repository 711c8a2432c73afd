"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.

Production topology (TPU v5e numbers):
  single pod : (data=16, model=16)            = 256 chips
  multi-pod  : (pod=2, data=16, model=16)     = 512 chips
The 'pod' axis only ever carries data parallelism + cross-pod gradient
reduction — model/expert sharding stays intra-pod (ICI), which is what makes
the 2-pod extension DCN-feasible.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, devices=None) -> Mesh:
    """The repo's one mesh constructor. Axes are Auto: the sharding code
    (``models.sharding.shard_act``, the vocab-sharded embedding gather)
    places activations with ``with_sharding_constraint``, which
    ``jax.make_mesh``'s default Explicit axes reject."""
    if devices is None:
        devices = jax.devices()[:math.prod(shape)]
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)} — "
            "run under launch/dryrun.py (which forces 512 host devices) or "
            "on real hardware")
    return make_mesh(shape, axes, devices[:need])


def make_elastic_mesh(n_pods_alive: int, *, pod_shape=(16, 16)) -> Mesh:
    """Degraded multi-pod mesh after pod failures (elastic re-mesh): same
    (data, model) inner shape, 'pod' axis shrunk to the surviving pods."""
    shape = (n_pods_alive, *pod_shape)
    axes = ("pod", "data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(f"need {need} devices for elastic mesh {shape}")
    return make_mesh(shape, axes, devices[:need])

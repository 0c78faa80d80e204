"""Production mesh builders (functions — importing this module never touches
jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16×16 = 256 chips (data, model).
    Multi-pod: 2×16×16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh_for(devices: int, model_parallel: int = 1):
    """Elastic helper: build a (data, model) mesh from whatever devices
    survive — used by the elastic-restart path (checkpoints are
    mesh-agnostic, so resuming on a different device count just re-shards)."""
    assert devices % model_parallel == 0
    return jax.make_mesh((devices // model_parallel, model_parallel),
                         ("data", "model"))

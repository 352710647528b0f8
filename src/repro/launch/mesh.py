"""Production mesh builders.

Single-pod : (data=16, model=16)          = 256 chips (one v5e pod)
Multi-pod  : (pod=2, data=16, model=16)   = 512 chips (2 pods over DCN/ICI)

Functions (not module-level constants) so importing never touches jax
device state — the dry-run sets XLA_FLAGS before any jax import instead.

Every mesh uses ``Auto`` axis types: the sharding in this repo is
GSPMD-style (NamedSharding in/out specs plus shard_map), which
``jax.make_mesh``'s default ``Explicit`` axes reject.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """Mesh of ``shape`` over ``axes`` (tests use small ones, e.g. (2, 2));
    ``devices`` defaults to ``jax.devices()``."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def chips(mesh) -> int:
    return mesh.devices.size

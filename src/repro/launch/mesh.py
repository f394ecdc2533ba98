"""Mesh construction for the production pods.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
driver forces 512 host platform devices while tests/benches must see 1.

Production topology (TPU v5e target):
  single pod : 16 x 16  = 256 chips, axes (data, model)
  multi-pod  : 2 x 16 x 16 = 512 chips, axes (pod, data, model)
The 'pod' axis crosses DCN; 'data'/'model' stay on intra-pod ICI.

Every axis is ``AxisType.Auto``: the models place activations with
``with_sharding_constraint`` (``distributed.sharding.constrain``) and leave
the rest to the partitioner, which Explicit axes (``jax.make_mesh``'s
default) refuse.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_mesh", "single_device_mesh"]


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def single_device_mesh() -> Mesh:
    """1-device mesh with the standard axis names (CPU tests)."""
    return make_mesh((1, 1), ("data", "model"))

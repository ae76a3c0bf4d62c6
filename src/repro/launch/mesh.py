"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): 16x16 = 256 chips single-pod, 2x16x16 = 512 chips
multi-pod.  The dry-run (launch/dryrun.py) materializes these over 512
placeholder host devices; real deployments get them from the TPU topology.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the logical-axis rules (models/sharding.py) constrain a few
    # values and leave the rest to sharding propagation
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh():
    """Whatever devices this process has, as a (data, model=1) mesh — used by
    tests and the CPU training examples."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)

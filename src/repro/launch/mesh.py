"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing never touches jax
device state.  Single pod: 16x16 = 256 chips (TPU v5e pod), axes
(data, model).  Multi-pod: 2x16x16 = 512 chips, axes (pod, data, model) —
the 'pod' axis carries data parallelism across the DCN/ICI-superpod boundary.
"""

from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType, Mesh

from ..models.sharding import ShardCtx


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_ctx(mesh: Mesh) -> ShardCtx:
    names = mesh.axis_names
    dp = ("pod", "data") if "pod" in names else ("data",)
    return ShardCtx(mesh=mesh, dp_axes=dp, tp_axis="model")


def make_host_mesh(n_devices: int = 0, model_axis: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = n_devices or len(jax.devices())
    assert n % model_axis == 0
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

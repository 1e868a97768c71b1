"""Production mesh construction.

A FUNCTION (not module-level constant) so importing never touches jax device
state. Single pod: (16, 16) = 256 chips, axes (data, model). Multi-pod:
(2, 16, 16) = 512 chips, axes (pod, data, model); the pod axis is a pure
data-parallel/FSDP axis crossing the inter-pod links. Every mesh is built
with explicit ``Auto`` axis types.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax


def _auto_mesh(shape: Tuple[int, ...], axes: Sequence[str]):
    return jax.make_mesh(
        shape, tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(shape),
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(model: int = 4, data: int = 2):
    """Small host-device mesh for tests (requires device_count >= data*model)."""
    return _auto_mesh((data, model), ("data", "model"))


def make_mesh_shape(spec: str):
    """Custom logical view over the same chips, e.g. '64x4' -> (data, model).

    §Perf: the (data, model) SPLIT of a pod is a tuning knob — small models
    waste ICI at model=16 (row-parallel all-reduce and residual-stream bytes
    scale with tokens/device). The pod hardware is unchanged; only the
    logical mesh differs from the baseline (16, 16)."""
    dims = tuple(int(x) for x in spec.split("x"))
    if len(dims) == 2:
        return _auto_mesh(dims, ("data", "model"))
    assert len(dims) == 3, dims
    return _auto_mesh(dims, ("pod", "data", "model"))

"""Production mesh construction.

Single pod: (data=16, model=16) — 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the ``pod`` axis is
pure data parallelism across the slower inter-pod (DCN/ICI-X) links, so
only gradient all-reduces cross it.

Defined as functions, not module constants: importing this module must not
touch jax device state (the dry-run sets XLA_FLAGS before first init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mk(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (1 on this container) — used by
    smoke tests and CPU benchmarks."""
    n = len(jax.devices())
    return _mk((1, n), ("data", "model"))


def make_serve_mesh(model_size: int | None = None):
    """Serving mesh: (data=1, model=n) over the first n local devices.

    Unlike ``make_host_mesh`` this takes an explicit model-axis size so a
    4-device host can also build 1- and 2-wide meshes (the sharded-serving
    test tier compares them).  jax.make_mesh always consumes all devices,
    so build the Mesh over an explicit device subset."""
    import numpy as np

    devs = jax.devices()
    n = len(devs) if model_size is None else int(model_size)
    if not 1 <= n <= len(devs):
        raise ValueError(
            f"mesh model_size={n} needs 1..{len(devs)} devices")
    arr = np.asarray(devs[:n]).reshape(1, n)
    axes = ("data", "model")
    return Mesh(arr, axes, axis_types=(AxisType.Auto,) * len(axes))


def batch_axes(mesh) -> tuple:
    """Mesh axes that carry the batch dim: everything except 'model'."""
    return tuple(a for a in mesh.axis_names if a != "model")


def model_axis_size(mesh) -> int:
    return mesh.shape["model"]

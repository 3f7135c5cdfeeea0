"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init — dryrun.py must be
able to set XLA_FLAGS before any jax call).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto (GSPMD propagates shardings
    from the arguments; no explicit-sharding axes)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Single-device (or tiny) mesh for CPU tests/examples."""
    n = len(jax.devices())
    data = max(n // model, 1)
    return _make_mesh((data, model), ("data", "model"))


def make_pipeline_mesh(pp: int, model: int = 1, *, devices=None):
    """``pp`` pipeline stages x ``model`` TP chips per stage.

    Stage ``s`` owns the device row ``mesh.devices[s]``; the PP engine
    places its per-stage params/cache there.  On CPU CI the stage devices
    come from ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    import numpy as np
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < pp * model:
        raise ValueError(
            f"pipeline mesh {pp}x{model} needs {pp * model} devices, have "
            f"{len(devs)}; set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={pp * model} before the first jax call")
    arr = np.asarray(devs[: pp * model]).reshape(pp, model)
    return jax.sharding.Mesh(arr, ("stage", "model"))


def batch_axes(multi_pod: bool):
    """Mesh axes over which the global batch is sharded."""
    return ("pod", "data") if multi_pod else ("data",)

"""Production meshes. A function (not a module-level constant) so importing
this module never touches jax device state."""
from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    import os

    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    # test hook: REPRO_MESH="2,2" shrinks the mesh for the mini dry-run test
    env = os.environ.get("REPRO_MESH")
    if env:
        base = tuple(int(x) for x in env.split(","))
        shape = ((2,) + base) if multi_pod else base
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) != n:
        if len(devices) < n:
            raise RuntimeError(
                f"need {n} devices for mesh {shape}, have {len(devices)} — "
                "set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
                "before importing jax (see launch/dryrun.py)")
        devices = devices[:n]
        dev_array = np.asarray(devices).reshape(shape)
        from jax.sharding import Mesh
        return Mesh(dev_array, axes)
    # Auto axes: the model's with_sharding_constraint calls need them, and
    # make_mesh defaults to Explicit
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for tests (requires xla_force_host_platform_device_count)."""
    import jax
    from jax.sharding import Mesh
    shape = (pod, data, model) if pod else (data, model)
    axes = ("pod", "data", "model") if pod else ("data", "model")
    n = int(np.prod(shape))
    dev = np.asarray(jax.devices()[:n]).reshape(shape)
    return Mesh(dev, axes)

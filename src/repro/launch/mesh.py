"""Production mesh builders.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets a 512-device placeholder
platform before any jax import; tests and benches keep 1 device).
"""
from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (data, model) or 2×16×16 multi-pod
    (pod, data, model).  Uses the first 256 devices for single-pod when
    more are available (the dry-run platform exposes 512)."""
    import jax
    from jax.sharding import Mesh

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devs)} — the "
            "dry-run entrypoint must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import")
    return Mesh(np.asarray(devs[:need]).reshape(shape), axes)


def make_test_mesh(dp: int, tp: int, pod: int = 0):
    """(dp, tp) "data" x "model" mesh (or (pod, dp, tp)) over the first
    devices JAX exposes.  On a TPU host the devices are the chips, laid
    out by `mesh_utils.create_device_mesh` so the minor "model" axis
    follows the interconnect ring (on a v5e 2x2 that is chips 0, 1, 3,
    2, not JAX's list order); the CPU backend keeps list order."""
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    if pod:
        shape, axes = (pod, dp, tp), ("pod", "data", "model")
    else:
        shape, axes = (dp, tp), ("data", "model")
    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"a {dict(zip(axes, shape))} mesh needs {need} devices, JAX "
            f"has {len(devs)} {devs[0].platform} device(s); the CPU "
            f"backend exposes more only when asked before it starts "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count={need})")
    return Mesh(mesh_utils.create_device_mesh(shape, devices=devs[:need]),
                axes)

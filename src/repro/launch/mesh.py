"""Mesh construction. Functions only — importing this module never touches
jax device state (jax locks the device count on first real init).

Every mesh has ``Auto`` axes: the sharding hints in ``sharding.constrain``
are hints for the partitioner, and under ``Explicit`` axes (the default of
``jax.make_mesh``) ``with_sharding_constraint`` becomes an assertion.
"""
from __future__ import annotations

import numpy as np


def auto_mesh(shape, axes, devices):
    """``jax.make_mesh`` over ``devices`` with every axis ``Auto``."""
    import jax
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))

SINGLE_POD = (16, 16)                       # 256 chips (TPU v5e pod)
MULTI_POD = (2, 16, 16)                     # 2 pods = 512 chips


def make_production_mesh(*, multi_pod: bool = False):
    """(pod, data, model) = (2,16,16) or (data, model) = (16,16).

    Uses the first prod(shape) devices so it works inside the 512-device
    dry-run process for both mesh sizes.
    """
    import jax
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for {axes}={shape}, have {len(devs)} "
            "(run under XLA_FLAGS=--xla_force_host_platform_device_count=512)")
    return auto_mesh(shape, axes, devs[:n])


def make_sim_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Small virtual mesh for CPU tests (e.g. 8 forced host devices)."""
    import jax
    n = int(np.prod(shape))
    return auto_mesh(shape, axes, jax.devices()[:n])


def make_host_mesh():
    """Trivial 1-device mesh for smoke-scale runs."""
    import jax
    return auto_mesh((1, 1), ("data", "model"), jax.devices()[:1])

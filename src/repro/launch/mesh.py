"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run forces 512 host
devices via XLA_FLAGS before first jax init, while smoke tests must see the
real single device.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], devices=None):
    """An Auto-axes mesh over the first prod(shape) of `devices` (default
    `jax.devices()`)."""
    devices = list(devices if devices is not None else jax.devices())
    n = math.prod(shape)
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — the "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count "
            "BEFORE any jax import (see launch/dryrun.py)")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """The target deployment: one v5e pod 16x16 = 256 chips, or 2 pods = 512.

    Axes: ("data", "model") single-pod; ("pod", "data", "model") multi-pod.
    Under SEDAR dual-replication the "pod" axis carries the two replicas
    (DESIGN.md §2/§6); in the unprotected baseline it is an extra data axis.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_pod_mesh(devices=None):
    """Spatial replication over the devices at hand (default
    `jax.devices()`): ("pod", "data", "model") = (2, n/2, 1), the two
    replicas on the "pod" axis, each over half of the devices."""
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < 2 or len(devices) % 2:
        raise RuntimeError(f"pod replication needs an even number of "
                           f"devices >= 2, found {len(devices)}")
    return make_mesh((2, len(devices) // 2, 1), ("pod", "data", "model"),
                     devices=devices)


def make_test_mesh(shape: Tuple[int, ...] = (2, 2, 2),
                   axes: Tuple[str, ...] = ("pod", "data", "model")):
    """Small mesh for CPU multi-device tests (needs forced host devices)."""
    return make_mesh(shape, axes)

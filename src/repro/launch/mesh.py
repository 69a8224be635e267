"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the ``pod``
axis is pure data parallel; cross-pod traffic is one (optionally
compressed) gradient reduction per step.

Defined as functions so importing this module never touches jax device
state (the dry-run overrides the platform device count BEFORE first use).
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def _axis_kwargs(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kwargs(len(axes)))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes, **_axis_kwargs(len(axes)))


def make_test_mesh(n_devices: int | None = None, model: int = 2):
    """Small mesh over however many (virtual) devices exist — tests only."""
    n = n_devices or len(jax.devices())
    assert n % model == 0, (n, model)
    return make_mesh((n // model, model), ("data", "model"))


def mesh_from_plan(plan, devices=None):
    """Concrete ``jax.sharding.Mesh`` from a fault-tolerance
    :class:`repro.train.fault_tolerance.ElasticPlan` over whatever
    devices are alive now — the elastic-restart walk is
    ``plan_remesh(alive_chips, ...)`` → ``mesh_from_plan(plan)`` →
    ``checkpoint.restore(..., shardings=on the new mesh)``.

    Builds the Mesh directly from the first ``plan.n_chips`` devices (a
    shrunken plan must work in the same process that drove the larger
    mesh, so it cannot assume the plan covers every visible device)."""
    import numpy as np
    devs = list(jax.devices()) if devices is None else list(devices)
    if len(devs) < plan.n_chips:
        raise ValueError(
            f"elastic plan needs {plan.n_chips} devices, "
            f"only {len(devs)} visible")
    arr = np.empty(plan.n_chips, dtype=object)
    arr[:] = devs[: plan.n_chips]
    return jax.sharding.Mesh(arr.reshape(plan.mesh_shape),
                             plan.axis_names)


def dp_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


def dp_size(mesh) -> int:
    out = 1
    for a in dp_axes(mesh):
        out *= mesh.shape[a]
    return out


# Hardware constants (TPU v5e) — used by the roofline model.
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~per-chip usable)
HBM_BYTES = 16 * 1024**3        # 16 GiB per chip

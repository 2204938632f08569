"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; real launches get devices from the TPU runtime.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devices)} "
            "(dry-runs must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before importing jax)"
        )
    return _auto_mesh(shape, axes, devices[:need])


def make_host_mesh(data: int = 2, model: int = 2):
    """(data, model) mesh over the first ``data * model`` devices: CPU
    multi-device tests (subprocess-scoped XLA_FLAGS) and one-host chip runs."""
    need = data * model
    return _auto_mesh((data, model), ("data", "model"), jax.devices()[:need])


def _auto_mesh(shape, axes, devices):
    # The step builders place activations with with_sharding_constraint and
    # leave the rest to GSPMD propagation, which needs Auto axes (make_mesh
    # defaults to Explicit).
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))

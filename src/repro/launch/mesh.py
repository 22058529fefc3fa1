"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
touches no jax device state.  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benchmarks see the real single CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (data, model) single pod, or 2x16x16 (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices but only {len(devices)} present; "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(launch/dryrun.py does this automatically)"
        )
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(shape),
                         devices=devices[:need])


def make_host_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Small emulated mesh for CPU pipeline tests (e.g. (1, 4) stages)."""
    need = 1
    for s in shape:
        need *= s
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(f"need {need} devices, have {len(devices)}")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(shape),
                         devices=devices[:need])

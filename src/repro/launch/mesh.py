"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis extends data parallelism across pods (gradient reduction becomes
hierarchical: reduce-scatter intra-pod over ICI, all-reduce inter-pod over
DCI), and extends index/sequence sharding for serving shapes.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state; the dry-run sets
``--xla_force_host_platform_device_count=512`` before any jax import.
"""
from __future__ import annotations

import jax

from jax.sharding import AxisType


def _make_mesh(shape, axes, devices):
    return jax.make_mesh(
        shape, axes, devices=devices,
        axis_types=(AxisType.Auto,) * len(axes),
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)}; the "
            "dry-run entrypoint must set XLA_FLAGS="
            "--xla_force_host_platform_device_count=512 before importing jax"
        )
    return _make_mesh(shape, axes, devices[:n])


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    return _make_mesh(
        (data, model), ("data", "model"), jax.devices()[: data * model]
    )

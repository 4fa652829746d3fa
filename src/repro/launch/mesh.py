"""Production meshes.

Single pod: (data=16, model=16) — 256 chips (one v5e-256 pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips across 2 pods; the
``pod`` axis composes with ``data`` for batch/FSDP sharding so the DCN/
inter-pod boundary only ever carries data-parallel gradient traffic.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: the sharding rules place arrays
    with ``with_sharding_constraint``, which Explicit axes refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices but only {len(devs)} are "
            f"visible; the dry-run entrypoint must set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            f"any jax import")
    return make_mesh(shape, axes, devices=devs[:need])


def make_host_mesh(model: Optional[int] = None):
    """Degenerate mesh over whatever devices exist (tests on 1-8 CPUs)."""
    n = len(jax.devices())
    model = model or 1
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))
